"""Multi-head latent attention (DeepSeek-V2/V3 MLA) with a latent KV cache.

Per token, with ``r = kv_lora_rank``:

  q            = x W_q          per head (qk_nope_head_dim + qk_rope_head_dim)
  [c_kv, k_pe] = x W_kv_a       c_kv (r) normalised by ``kv_norm``; k_pe
                                (qk_rope_head_dim) one for every head
  k_nope, v    = c_kv W_uk, c_kv W_uv    per head (kv_b, split in two)

q_pe and k_pe rotate by position (adjacent pairs: DeepSeek's interleaved
RoPE layout).  Scores are ``(q_nope.k_nope + q_pe.k_pe) /
sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, weighted sum
of v.  The query is projected directly (q_lora_rank null).  Heads split
over the workers as in ``attention.py``; the worker-factored out-projection
partials fuse through ``attention._project_out``.

Two paths over the same mathematics:

* full / prefill (expanded): the latent goes up through W_uk and W_uv into
  per-head k_nope and v, attention as usual.
* decode (absorbed): W_uk folds into the query (``q_lat = q_nope W_uk^T``,
  r wide) and W_uv applies after the weighted sum of latents, so the step
  attends over the cache without expanding it.

The decode cache holds, per token and layer, ``c_kv`` (r) after the norm
and the rotated ``k_pe``: ``r + qk_rope_head_dim`` values in place of
``n_heads * (qk_nope + qk_rope + v_head_dim)``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, layers
from repro.parallel.sharding import Tagged, constrain

CACHE_AXES = {
    "c_kv": ("batch", "kv_seq", None),
    "k_pe": ("batch", "kv_seq", None),
}


def mla_init(cfg, rng) -> dict:
    d, h, n = cfg.d_model, cfg.n_heads, cfg.n_workers
    r, nope, rope, vd = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    assert h % n == 0, (cfg.name, h, n)
    k = layers.rsplit(rng, 5)
    pd = cfg.param_dtype
    return {
        "wq": layers.param(k[0], (d, h, nope + rope),
                           ("embed", "heads", None), pd, scale=d ** -0.5),
        "wkv_a": layers.param(k[1], (d, r + rope), ("embed", None), pd,
                              scale=d ** -0.5),
        "kv_norm": {"scale": Tagged(jnp.ones((r,), pd), (None,))},
        "w_uk": layers.param(k[2], (r, h, nope), (None, "heads", None), pd,
                             scale=r ** -0.5),
        "w_uv": layers.param(k[3], (r, h, vd), (None, "heads", None), pd,
                             scale=r ** -0.5),
        "wo": layers.param(k[4], (n, h // n, vd, d),
                           ("worker", None, None, "embed"), pd,
                           scale=(h * vd) ** -0.5),
    }


def init_cache(cfg, batch: int, max_seq: int, dtype) -> dict:
    return {"c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_seq, cfg.qk_rope_head_dim), dtype)}


def _scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _project(cfg, p, x, positions):
    """x (B,S,d) -> q_nope (B,S,H,nope), rotated q_pe (B,S,H,rope), normed
    c_kv (B,S,r) and rotated k_pe (B,S,rope)."""
    d = cfg.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(d))
    q = constrain(q, ("batch", "seq", "heads", None))
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(d))
    c_kv = layers.norm_apply(cfg, p["kv_norm"], kv[..., :r])
    k_pe = layers.apply_rope(cfg, kv[..., None, r:], positions)[:, :, 0]
    q_pe = layers.apply_rope(cfg, q[..., nope:], positions)
    return q[..., :nope], q_pe, c_kv, k_pe


def _softmax(cfg, scores, mask):
    scores = jnp.where(mask[:, None], scores, attention.NEG_INF)
    return jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)


def attend_expanded(cfg, p, q_nope, q_pe, c_kv, k_pe, mask) -> jax.Array:
    """Attention with the latent lifted to per-head keys and values.
    q_nope/q_pe: (B,S,H,.); c_kv (B,T,r); k_pe (B,T,rope); mask (B,S,T).
    Returns (B,S,H,v_head_dim)."""
    d = cfg.dtype
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, p["w_uk"].astype(d))
    v = jnp.einsum("btr,rhk->bthk", c_kv, p["w_uv"].astype(d))
    with jax.named_scope("mla.attend"):
        s = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshk,btk->bhst", q_pe, k_pe,
                          preferred_element_type=jnp.float32))
        probs = _softmax(cfg, s * _scale(cfg), mask)
        return jnp.einsum("bhst,bthk->bshk", probs, v)


def attend_absorbed(cfg, p, q_nope, q_pe, c_kv, k_pe, mask) -> jax.Array:
    """The same attention over the latent: W_uk folded into the query,
    W_uv applied after the weighted sum.  Shapes as
    :func:`attend_expanded`."""
    d = cfg.dtype
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(d))
    with jax.named_scope("mla.attend"):
        s = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshk,btk->bhst", q_pe, k_pe,
                          preferred_element_type=jnp.float32))
        probs = _softmax(cfg, s * _scale(cfg), mask)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv)
    return jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"].astype(d))


def mla_full(cfg, p: dict, x: jax.Array, positions: jax.Array,
             causal: bool = True, return_cache: bool = False):
    """Full-sequence MLA (train / prefill), expanded path. x: (B, S, d).
    With ``return_cache`` also the latent cache entries of every
    position."""
    q_nope, q_pe, c_kv, k_pe = _project(cfg, p, x, positions)
    mask = positions[:, None, :] <= positions[:, :, None]
    if not causal:
        mask = jnp.ones_like(mask)
    out = attend_expanded(cfg, p, q_nope, q_pe, c_kv, k_pe, mask)
    y = attention._project_out(cfg, p, out)
    if return_cache:
        return y, {"c_kv": c_kv, "k_pe": k_pe}
    return y


def mla_step(cfg, p: dict, x: jax.Array, positions: jax.Array,
             cache: dict) -> Tuple[jax.Array, dict]:
    """Single decode step, absorbed path. x: (B, 1, d); positions: (B,)
    the write index; cache entries below it are valid."""
    q_nope, q_pe, c_new, pe_new = _project(cfg, p, x, positions[:, None])

    def upd(c, new, pos):
        return jax.lax.dynamic_update_slice(
            c, new.astype(c.dtype), (pos, jnp.zeros((), pos.dtype)))

    c_kv = constrain(jax.vmap(upd)(cache["c_kv"], c_new, positions),
                     CACHE_AXES["c_kv"])
    k_pe = constrain(jax.vmap(upd)(cache["k_pe"], pe_new, positions),
                     CACHE_AXES["k_pe"])
    t = jnp.arange(c_kv.shape[1], dtype=positions.dtype)
    mask = (t[None, :] <= positions[:, None])[:, None, :]      # (B,1,T)
    out = attend_absorbed(cfg, p, q_nope, q_pe, c_kv, k_pe, mask)
    return attention._project_out(cfg, p, out), {"c_kv": c_kv,
                                                  "k_pe": k_pe}
