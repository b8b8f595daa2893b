"""Plain float32 reference of qwen1.5-0.5b served with its FFN worker
partials fused through the OCS channel.

It imports nothing of the system under test.  A decoder-only transformer
(hf Qwen/Qwen1.5-0.5B): token embedding; per layer RMSNorm, multi-head
causal attention with q/k/v biases and rotary position embeddings, RMSNorm,
SwiGLU FFN; final RMSNorm; logits on the tied embedding.  Departures, each
also the program's semantics:

* RoPE rotates adjacent pairs of head dimensions (the published model
  rotates the two halves; for random weights a fixed permutation).
* The FFN's ``d_ff`` is split over ``n_workers`` wireless workers, each
  with its own slice of the gate/up/down weights.  Their full-width
  partials sum (no channel) or, at positions the channel decoded, pool by
  noisy OCS (``bench/lib/ocs_ref.py``): the winner's 8-bit code, as its
  bfloat16 representative.
* The channel's sensing stream: the decode tick ``t`` that processed a
  position draws with ``fold_in(PRNGKey(engine_seed), t)``, split into one
  key per layer, folded with the block's position in the layer pattern
  (0); each contention frame spans every slot of the batch, ``(N, slots *
  d_model)`` sub-frames, with the hear probability in bfloat16, the
  served model's activation dtype.  The reference draws the whole frame
  and reads its slot's columns.

Prompt positions are processed without the channel (the prefill); every
later position with the key of the tick that decoded it.  Matrix products
run at ``precision="highest"``; ``precision="fp8"`` rounds every product's
operands to float8_e4m3fn first: the control, one step below the
bfloat16 the configuration serves in.  ``precision="bf16"`` computes in the
configuration's bfloat16: every activation a layer hands on (projections,
attention probabilities and outputs, each worker's partial, their fused
sum, the residual stream, the norms' outputs) is rounded to bfloat16, and
products of bfloat16 operands accumulate in float32; the logits are read
unrounded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.ocs_ref import codes, contend, dequant

F32 = jnp.float32


def init_params(config: dict, key):
    """Random weights in the program's value layout, in the served dtype:
    every matrix, the embedding and the biases normal with the published
    ``initializer_range``; norm scales one plus the same noise."""
    L, d = config["n_layers"], config["d_model"]
    h, kv, hd = config["n_heads"], config["n_kv_heads"], config["head_dim"]
    n, f = config["n_workers"], config["d_ff"] // config["n_workers"]
    std = config["initializer_range"]
    dt = jnp.dtype(config["dtype"])
    shapes = {
        "wq": (L, d, h, hd), "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
        "wo": (L, n, h // n, hd, d), "bq": (L, h, hd), "bk": (L, kv, hd),
        "bv": (L, kv, hd), "w_up": (L, n, d, f), "w_gate": (L, n, d, f),
        "w_down": (L, n, f, d), "tokens": (config["vocab_size"], d)}
    keys = jax.random.split(key, len(shapes) + 3)
    w = {name: (std * jax.random.normal(k, shp, F32)).astype(dt)
         for k, (name, shp) in zip(keys, shapes.items())}

    def scale(k, shp):
        return (1.0 + std * jax.random.normal(k, shp, F32)).astype(dt)

    return {
        "embed": {"tokens": w["tokens"]},
        "blocks": {"pos0": {
            "norm1": {"scale": scale(keys[-3], (L, d))},
            "norm2": {"scale": scale(keys[-2], (L, d))},
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                        "bv")},
            "ffn": {k: w[k] for k in ("w_up", "w_gate", "w_down")}}},
        "final_norm": {"scale": scale(keys[-1], (d,))},
    }


def _ein(spec, a, b, precision):
    if precision == "fp8":
        a = a.astype(jnp.float8_e4m3fn)
        b = b.astype(jnp.float8_e4m3fn)
    elif precision == "bf16":
        a, b = _round(a, precision), _round(b, precision)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


def _round(x, precision):
    """An activation as the precision holds it between layers: rounded to
    bfloat16 at ``bf16``, float32 otherwise."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, pos, theta):
    """x: (T, H, hd); adjacent pairs rotate by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv                    # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def site_key(engine_seed, tick, layer, n_layers):
    """Sensing key of one FFN site of the decode tick ``tick``."""
    k = jax.random.fold_in(jax.random.PRNGKey(engine_seed), tick)
    return jax.random.fold_in(jax.random.split(k, n_layers)[layer], 0)


def forward(config: dict, values, tokens, chan: dict,
            precision: str = "highest"):
    """Logits ``(T, vocab)`` of one request's tokens ``(T,)``.

    ``chan`` is ``None`` (every FFN sums) or a dict: ``ticks (T,)`` the
    decode tick of each position, ``on (T,)`` where the channel pooled,
    ``slot`` the request's slot, ``slots`` the batch's slot count,
    ``engine_seed``, and ``p_miss`` (N,)."""
    L, d = config["n_layers"], config["d_model"]
    h, hd = config["n_heads"], config["head_dim"]
    eps, theta = config["norm_eps"], config["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    emb = values["embed"]["tokens"]
    x = jnp.take(emb, tokens, axis=0).astype(F32)
    blk = values["blocks"]["pos0"]
    mask = pos[None, :] <= pos[:, None]

    def r(y):
        return _round(y, precision)

    if chan is not None:
        ch = config["channel"]
        p_keep = 1.0 - jnp.asarray(chan["p_miss"],
                                   jnp.dtype(config["dtype"]))[:, None]

    def pool_at(layer, partial_t, tick):
        """One position's OCS pool of its (N, d) partials."""
        key = site_key(chan["engine_seed"], tick, layer, L)
        code = codes(partial_t, ch["bits"])
        win = contend(code, key, p_keep, ch["bits"], ch["max_rounds"],
                      frame_k=chan["slots"] * d, col0=chan["slot"] * d)
        return dequant(jnp.take_along_axis(code, win[None], 0)[0],
                       ch["bits"], 16)

    def layer(x, xs):
        li, p1, p2, mx, ffn = xs
        a = r(_rms(x, p1, eps))
        q = r(r(_ein("td,dhk->thk", a, mx["wq"], precision)) + mx["bq"])
        k = r(r(_ein("td,dhk->thk", a, mx["wk"], precision)) + mx["bk"])
        v = r(r(_ein("td,dhk->thk", a, mx["wv"], precision)) + mx["bv"])
        q, k = r(_rope(q, pos, theta)), r(_rope(k, pos, theta))
        s = _ein("qhk,thk->hqt", q, k, precision) * hd ** -0.5
        s = jnp.where(mask[None], s, -1e9)
        o = r(_ein("hqt,thk->qhk", r(jax.nn.softmax(s, -1)), v, precision))
        wo = mx["wo"]                                     # (N, h/N, hd, d)
        heads = o.reshape(o.shape[0], *wo.shape[:3])
        attn = r(_ein("tnhk,nhkd->ntd", heads, wo, precision))
        x = r(x + r(jnp.sum(attn, 0)))
        a = r(_rms(x, p2, eps))
        gate = r(_ein("td,ndf->ntf", a, ffn["w_gate"], precision))
        up = r(_ein("td,ndf->ntf", a, ffn["w_up"], precision))
        partial = r(_ein("ntf,nfd->ntd", r(jax.nn.silu(gate) * up),
                         ffn["w_down"], precision))         # (N, T, d)
        out = r(jnp.sum(partial, 0))
        if chan is not None:
            pooled = jax.vmap(pool_at, in_axes=(None, 1, 0))(
                li, partial, chan["ticks"])
            out = jnp.where(chan["on"][:, None], pooled, out)
        return r(x + out), None

    xs = (jnp.arange(L), blk["norm1"]["scale"], blk["norm2"]["scale"],
          blk["mixer"], blk["ffn"])
    x, _ = jax.lax.scan(layer, x, xs)
    x = r(_rms(x, values["final_norm"]["scale"], eps))
    return _ein("td,vd->tv", x, emb, precision)
