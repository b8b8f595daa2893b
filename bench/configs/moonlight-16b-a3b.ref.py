"""Plain float32 reference of moonlight-16b-a3b (the DeepSeek-V3 block),
served with its FFN worker partials summed.

It imports nothing of the system under test.  A decoder-only transformer
(hf moonshotai/Moonlight-16B-A3B, ``model_type`` deepseek_v3): token
embedding; per layer RMSNorm, multi-head latent attention, RMSNorm, FFN;
final RMSNorm; logits on an untied head.

* Attention (MLA, q_lora_rank null): ``q = x W_q`` per head, split into
  ``q_nope`` (qk_nope_head_dim) and ``q_pe`` (qk_rope_head_dim);
  ``[c_kv, k_pe] = x W_kv_a``, ``c_kv`` RMS-normalised (kv_a_layernorm),
  ``k_pe`` one for every head; ``k_nope = c_kv W_uk`` and ``v = c_kv W_uv``
  per head (kv_b_proj, its two halves).  Scores ``(q_nope.k_nope +
  q_pe.k_pe) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax,
  values, output projection.  Expanded, at every position.
* FFN: the first ``first_k_dense_replace`` layers a SwiGLU of width
  ``d_ff`` (intermediate_size); every later layer ``n_routed_experts``
  SwiGLU experts of width ``moe_intermediate_size`` plus
  ``n_shared_experts`` shared experts as one SwiGLU of that width times
  their count.  Router (scoring_func sigmoid, topk_method noaux_tc,
  n_group = topk_group = 1): scores ``sigmoid(x W_r)`` in float32; the
  top ``num_experts_per_tok`` of ``scores + e_score_correction_bias``
  are chosen, weighted by their scores, renormalised (norm_topk_prob) and
  scaled by ``routed_scaling_factor``.  Every expert runs on every token
  and the router's dense weights (zero where not chosen) combine them.

Departures, each also the program's semantics:

* RoPE rotates adjacent pairs of ``q_pe``/``k_pe``: DeepSeek's code reads
  those dimensions as interleaved pairs (it permutes them before its
  rotate-half), so this is its rotation; no rope scaling.
* Each FFN's width (the dense layer's and the shared experts') is split
  over ``n_workers`` wireless workers, each with its own slice of the
  gate/up/down weights; their full-width partials sum.  The attention
  output projection is split likewise by heads.  Routed experts stay
  whole.
* ``kv_b_proj`` is held as its two halves ``w_uk``, ``w_uv``; the router
  weight and the selection bias are float32.
* No channel: ``chan`` must be ``None``.

Matrix products run at ``precision="highest"`` (the router's too, in
float32 as the program computes it); ``precision="fp8"`` rounds every
product's operands to float8_e4m3fn first: the control, one step below the
bfloat16 the configuration serves in.  ``precision="bf16"`` rounds every
activation a layer hands on to bfloat16 and accumulates products of
bfloat16 operands in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FAULTS = ("no_bias", "no_shared")


def _normal(key, shape, std, dt):
    """Normal(0, std) in ``dt``; a stacked tensor (three axes or more) is
    drawn one layer at a time, so that no float32 copy of it is held
    whole."""
    def draw(k, shp):
        return (std * jax.random.normal(k, shp, F32)).astype(dt)

    if len(shape) < 3:
        return draw(key, shape)
    return jax.lax.map(lambda k: draw(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def init_params(config: dict, key):
    """Random weights in the program's value layout, in the served dtype:
    every matrix and the embedding normal with the published
    ``initializer_range``, norm scales one plus the same noise, the
    router's selection bias normal with ``router_bias_std``."""
    L0 = config["first_k_dense_replace"]
    L = config["n_layers"] - L0
    d, h, n = config["d_model"], config["n_heads"], config["n_workers"]
    r, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope, vd = config["qk_rope_head_dim"], config["v_head_dim"]
    e, fe = config["n_routed_experts"], config["moe_intermediate_size"]
    fs = config["n_shared_experts"] * fe // n
    fd = config["d_ff"] // n
    std = config["initializer_range"]
    dt = jnp.dtype(config["dtype"])
    keys = iter(jax.random.split(key, 32))

    def w(shape, dtype=dt, s=std):
        return _normal(next(keys), shape, s, dtype)

    def scale(shape):
        return (1.0 + w(shape, F32)).astype(dt)

    def mixer(m):
        return {"wq": w((m, d, h, nope + rope)), "wkv_a": w((m, d, r + rope)),
                "kv_norm": {"scale": scale((m, r))},
                "w_uk": w((m, r, h, nope)), "w_uv": w((m, r, h, vd)),
                "wo": w((m, n, h // n, vd, d))}

    def swiglu(m, f):
        return {"w_up": w((m, n, d, f)), "w_down": w((m, n, f, d)),
                "w_gate": w((m, n, d, f))}

    return {
        "embed": {"tokens": w((config["vocab_size"], d))},
        "head": w((d, config["vocab_size"])),
        "final_norm": {"scale": scale((d,))},
        "lead": {"pos0": {
            "norm1": {"scale": scale((L0, d))}, "mixer": mixer(L0),
            "norm2": {"scale": scale((L0, d))}, "ffn": swiglu(L0, fd)}},
        "blocks": {"pos0": {
            "norm1": {"scale": scale((L, d))}, "mixer": mixer(L),
            "norm2": {"scale": scale((L, d))},
            "ffn": {"router": w((L, d, e), F32),
                    "select_bias": w((L, e), F32,
                                     config["router_bias_std"]),
                    "w_up": w((L, e, d, fe)), "w_gate": w((L, e, d, fe)),
                    "w_down": w((L, e, fe, d)),
                    "shared": swiglu(L, fs)}}},
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
    """x: (T, H, k); adjacent pairs rotate by pos * theta^(-2i/k)."""
    k = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, k, 2, dtype=F32) / k)
    ang = pos[:, None].astype(F32) * inv                    # (T, k/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def route(config: dict, ffn, a, precision: str = "highest", bias=True):
    """Dense combine weights ``(T, E)`` of the router: each token's chosen
    experts' renormalised, scaled scores, zero elsewhere.  ``bias=False``
    selects by the scores alone (a planted fault)."""
    k = config["num_experts_per_tok"]
    # the gate's product is float32 (bf16 rounds nothing here); fp8 rounds
    scores = jax.nn.sigmoid(_ein("td,de->te", a, ffn["router"],
                                 "fp8" if precision == "fp8" else "highest"))
    sel = scores + ffn["select_bias"].astype(F32) if bias else scores
    _, idx = jax.lax.top_k(sel, k)
    w = jnp.take_along_axis(scores, idx, -1)
    if config["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * config["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1], dtype=F32)   # (T, k, E)
    return jnp.sum(onehot * w[..., None], 1)


def forward(config: dict, values, tokens, chan=None,
            precision: str = "highest"):
    """Logits ``(T, vocab)`` of one request's tokens ``(T,)``.

    ``precision`` is ``highest``, ``bf16`` or ``fp8`` (above), or names a
    planted fault computed at ``highest`` that the check must catch in the
    program's place: ``no_bias`` selects experts without the selection
    bias, ``no_shared`` leaves the shared experts out."""
    if chan is not None:
        raise NotImplementedError("this reference sums every FFN's worker "
                                  "partials; it does not model the channel")
    fault = precision if precision in FAULTS else ""
    precision = "highest" if fault else precision
    r, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope = config["qk_rope_head_dim"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    x = jnp.take(values["embed"]["tokens"], tokens, axis=0).astype(F32)

    def rd(y):
        return _round(y, precision)

    def ein(spec, a, b):
        return rd(_ein(spec, a, b, precision))

    def attention(a, mx):
        q = ein("td,dhk->thk", a, mx["wq"])
        kv = ein("td,dk->tk", a, mx["wkv_a"])
        c = rd(_rms(kv[:, :r], mx["kv_norm"]["scale"], eps))
        k_pe = rd(_rope(kv[:, None, r:], pos, theta))[:, 0]
        q_nope, q_pe = q[..., :nope], rd(_rope(q[..., nope:], pos, theta))
        k_nope = ein("tr,rhk->thk", c, mx["w_uk"])
        v = ein("tr,rhk->thk", c, mx["w_uv"])
        s = (_ein("qhk,thk->hqt", q_nope, k_nope, precision)
             + _ein("qhk,tk->hqt", q_pe, k_pe, precision))
        s = jnp.where(mask[None], s * (nope + rope) ** -0.5, -1e9)
        o = ein("hqt,thk->qhk", rd(jax.nn.softmax(s, -1)), v)
        wo = mx["wo"]                                   # (N, h/N, v, d)
        heads = o.reshape(T, *wo.shape[:3])
        return rd(jnp.sum(ein("tnhk,nhkd->ntd", heads, wo), 0))

    def swiglu(a, f):
        gate = ein("td,ndf->ntf", a, f["w_gate"])
        up = ein("td,ndf->ntf", a, f["w_up"])
        return rd(jnp.sum(ein("ntf,nfd->ntd", rd(jax.nn.silu(gate) * up),
                              f["w_down"]), 0))

    def experts(a, ffn):
        comb = route(config, ffn, a, precision, bias=fault != "no_bias")

        def one(acc, ex):
            wg, wu, wd, c = ex
            gate = ein("td,df->tf", a, wg)
            up = ein("td,df->tf", a, wu)
            out = _ein("tf,fd->td", rd(jax.nn.silu(gate) * up), wd,
                       precision)
            return acc + c[:, None] * out, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(a),
                            (ffn["w_gate"], ffn["w_up"], ffn["w_down"],
                             comb.T))
        return rd(y)

    def layer(moe):
        def body(x, blk):
            a = rd(_rms(x, blk["norm1"]["scale"], eps))
            x = rd(x + attention(a, blk["mixer"]))
            a = rd(_rms(x, blk["norm2"]["scale"], eps))
            if not moe:
                return rd(x + swiglu(a, blk["ffn"])), None
            y = experts(a, blk["ffn"])
            if fault != "no_shared":
                y = rd(y + swiglu(a, blk["ffn"]["shared"]))
            return rd(x + y), None
        return body

    x, _ = jax.lax.scan(layer(False), x, values["lead"]["pos0"])
    x, _ = jax.lax.scan(layer(True), x, values["blocks"]["pos0"])
    x = rd(_rms(x, values["final_norm"]["scale"], eps))
    return _ein("td,dv->tv", x, values["head"], precision)
