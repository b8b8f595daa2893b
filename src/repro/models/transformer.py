"""Block and stack composition: (mixer x ffn) blocks, scanned over periods.

A config's layer plan is a cyclic pattern of ``(mixer, ffn)`` pairs
(``ModelConfig.layer_plan``); the stack scans over ``n_periods`` repetitions
with one parameter subtree per position in the period.  Heterogeneous
interleaves (jamba's 7:1 mamba:attn with alternating MoE, xlstm's
mLSTM/sLSTM mix) thus still lower to a single compact ``lax.scan`` —
essential for keeping 72-layer HLO small enough to compile 512-way SPMD
on the dry-run host.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, fusion, layers, mamba, mla, mlp, moe, ssm
from repro.parallel.sharding import Tagged, retag_stacked, constrain


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(cfg, rng, mixer: str, ffn: str, cross: bool = False) -> dict:
    r = layers.rsplit(rng, 5)
    p: Dict[str, Any] = {"norm1": layers.norm_init(cfg, r[0])}
    if mixer in ("attn", "attn_nocausal"):
        p["mixer"] = attention.attn_init(cfg, r[1])
    elif mixer == "mla":
        p["mixer"] = mla.mla_init(cfg, r[1])
    elif mixer == "mamba":
        p["mixer"] = mamba.mamba_init(cfg, r[1])
    elif mixer == "mlstm":
        p["mixer"] = ssm.mlstm_init(cfg, r[1])
    elif mixer == "slstm":
        p["mixer"] = ssm.slstm_init(cfg, r[1])
    else:
        raise ValueError(mixer)
    if cross:
        p["norm_cross"] = layers.norm_init(cfg, r[2])
        p["cross"] = attention.attn_init(cfg, r[2], cross=True)
    if ffn == "mlp":
        p["norm2"] = layers.norm_init(cfg, r[3])
        p["ffn"] = mlp.mlp_init(cfg, r[4])
    elif ffn == "moe":
        p["norm2"] = layers.norm_init(cfg, r[3])
        p["ffn"] = moe.moe_init(cfg, r[4])
    return p


def _apply_mixer_full(cfg, p, x, positions, mixer, enc_out):
    if mixer == "attn":
        return attention.attn_full(cfg, p, x, positions, causal=True)
    if mixer == "attn_nocausal":
        return attention.attn_full(cfg, p, x, positions, causal=False)
    if mixer == "mla":
        return mla.mla_full(cfg, p, x, positions)
    if mixer == "mamba":
        return mamba.mamba_full(cfg, p, x)
    if mixer == "mlstm":
        return ssm.mlstm_full(cfg, p, x)
    if mixer == "slstm":
        return ssm.slstm_full(cfg, p, x)
    raise ValueError(mixer)


def block_full(cfg, p: dict, x: jax.Array, positions: jax.Array,
               mixer: str, ffn: str,
               enc_out: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Training / prefill block. Returns (x, aux_loss)."""
    h = layers.norm_apply(cfg, p["norm1"], x)
    x = x + _apply_mixer_full(cfg, p["mixer"], h, positions, mixer, enc_out)
    if "cross" in p:
        h = layers.norm_apply(cfg, p["norm_cross"], x)
        x = x + attention.attn_full(cfg, p["cross"], h, positions,
                                    causal=False, kv_x=enc_out)
    aux = jnp.zeros((), jnp.float32)
    if ffn == "mlp":
        h = layers.norm_apply(cfg, p["norm2"], x)
        x = x + mlp.mlp_apply(cfg, p["ffn"], h)
    elif ffn == "moe":
        h = layers.norm_apply(cfg, p["norm2"], x)
        y, aux = moe.moe_apply(cfg, p["ffn"], h)
        x = x + y
    return x, aux


def block_cache_init(cfg, mixer: str, batch: int, max_seq: int, dtype,
                     cross_len: int = 0) -> dict:
    c: Dict[str, Any] = {}
    if mixer in ("attn", "attn_nocausal"):
        c["self"] = attention.init_cache(cfg, batch, max_seq, dtype)
    elif mixer == "mla":
        c["self"] = mla.init_cache(cfg, batch, max_seq, dtype)
    elif mixer == "mamba":
        c["self"] = mamba.init_cache(cfg, batch, dtype)
    elif mixer == "mlstm":
        c["self"] = ssm.mlstm_state_init(cfg, batch)
    elif mixer == "slstm":
        c["self"] = ssm.slstm_state_init(cfg, batch)
    if cross_len:
        c["cross"] = attention.init_cache(cfg, batch, cross_len, dtype)
    return c


def block_cache_axes(cfg, mixer: str, has_cross: bool) -> dict:
    c: Dict[str, Any] = {}
    if mixer in ("attn", "attn_nocausal"):
        c["self"] = dict(attention.CACHE_AXES)
    elif mixer == "mla":
        c["self"] = dict(mla.CACHE_AXES)
    elif mixer == "mamba":
        c["self"] = dict(mamba.MAMBA_CACHE_AXES)
    elif mixer == "mlstm":
        c["self"] = ssm.MLSTM_CACHE_AXES
    elif mixer == "slstm":
        c["self"] = ssm.SLSTM_CACHE_AXES
    if has_cross:
        c["cross"] = dict(attention.CACHE_AXES)
    return c


def block_step(cfg, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict, mixer: str, ffn: str,
               protocol=None, rng=None, layer=None):
    """Decode step. x: (B,1,d). Returns (x, cache, aux).

    With a ``protocol`` the FFN's worker-partial fusion routes through the
    simulated channel (``mlp_apply(protocol=, rng=)``; for an MoE FFN its
    shared expert's) and the return grows a fourth element — the
    channel-accounting dict of this block's fusion site
    (``fusion.chan_zeros()`` for an FFN with no worker-factored part;
    mixer fusions stay on the ideal ``tp_fusion`` collective).  With
    ``protocol=None`` the 3-tuple return is the historical one.  MoE FFNs
    route dropless (``moe_apply(dropless=True)``); a ``layer`` index says
    that their routed expert leaves are the whole stack's
    (:func:`stack_experts`).
    """
    h = layers.norm_apply(cfg, p["norm1"], x)
    new_cache = dict(cache)
    if mixer in ("attn", "attn_nocausal"):
        out, new_cache["self"] = attention.attn_step(
            cfg, p["mixer"], h, positions, cache["self"])
    elif mixer == "mla":
        out, new_cache["self"] = mla.mla_step(cfg, p["mixer"], h, positions,
                                              cache["self"])
    elif mixer == "mamba":
        out, new_cache["self"] = mamba.mamba_step(cfg, p["mixer"], h,
                                                  cache["self"])
    elif mixer == "mlstm":
        out, new_cache["self"] = ssm.mlstm_step(cfg, p["mixer"], h,
                                                cache["self"])
    elif mixer == "slstm":
        out, new_cache["self"] = ssm.slstm_step(cfg, p["mixer"], h,
                                                cache["self"])
    else:
        raise ValueError(mixer)
    x = x + out
    if "cross" in p:
        h = layers.norm_apply(cfg, p["norm_cross"], x)
        out, _ = attention.attn_step(cfg, p["cross"], h, positions,
                                     cache["cross"], cross=True)
        x = x + out
    aux = jnp.zeros((), jnp.float32)
    chan = None if protocol is None else fusion.chan_zeros()
    if ffn == "mlp":
        h = layers.norm_apply(cfg, p["norm2"], x)
        if protocol is None:
            x = x + mlp.mlp_apply(cfg, p["ffn"], h)
        else:
            y, acct = mlp.mlp_apply(cfg, p["ffn"], h, protocol=protocol,
                                    rng=rng)
            x = x + y
            chan = fusion.chan_from_acct(acct)
    elif ffn == "moe":
        h = layers.norm_apply(cfg, p["norm2"], x)
        if protocol is None:
            y, aux = moe.moe_apply(cfg, p["ffn"], h, dropless=True,
                                   layer=layer)
        else:
            y, aux, acct = moe.moe_apply(cfg, p["ffn"], h, dropless=True,
                                         protocol=protocol, rng=rng,
                                         layer=layer)
            if acct is not None:
                chan = fusion.chan_from_acct(acct)
        x = x + y
    if protocol is None:
        return x, new_cache, aux
    return x, new_cache, aux, chan


def block_prefill(cfg, p: dict, x: jax.Array, positions: jax.Array,
                  mixer: str, ffn: str, max_seq: int,
                  enc_out: Optional[jax.Array] = None, layer=None
                  ) -> Tuple[jax.Array, dict, jax.Array]:
    """Full-sequence forward that also materializes the decode cache
    (padded to ``max_seq``); MoE FFNs route dropless (``layer`` as in
    :func:`block_step`)."""
    h = layers.norm_apply(cfg, p["norm1"], x)
    cache: Dict[str, Any] = {}
    if mixer in ("attn", "attn_nocausal"):
        out, kv = attention.attn_full(cfg, p["mixer"], h, positions,
                                      causal=(mixer == "attn"),
                                      return_kv=True)
        if max_seq > kv["k"].shape[1]:
            buf = attention.init_cache(cfg, x.shape[0], max_seq, cfg.dtype)
            kv = jax.tree.map(
                lambda b, new: jax.lax.dynamic_update_slice(
                    b, new, (0, 0, 0, 0)), buf, kv)
        cache["self"] = kv
    elif mixer == "mla":
        out, lat = mla.mla_full(cfg, p["mixer"], h, positions,
                                return_cache=True)
        buf = mla.init_cache(cfg, x.shape[0], max_seq, cfg.dtype)
        cache["self"] = jax.tree.map(
            lambda b, new: jax.lax.dynamic_update_slice(
                b, new.astype(b.dtype), (0, 0, 0)), buf, lat)
    elif mixer == "mamba":
        out, cache["self"] = mamba.mamba_full(cfg, p["mixer"], h,
                                              return_cache=True)
    elif mixer == "mlstm":
        out, cache["self"] = ssm.mlstm_full(cfg, p["mixer"], h,
                                            return_cache=True)
    elif mixer == "slstm":
        out, cache["self"] = ssm.slstm_full(cfg, p["mixer"], h,
                                            return_cache=True)
    else:
        raise ValueError(mixer)
    x = x + out
    if "cross" in p:
        h = layers.norm_apply(cfg, p["norm_cross"], x)
        out, ckv = attention.attn_full(cfg, p["cross"], h, positions,
                                       causal=False, kv_x=enc_out,
                                       return_kv=True)
        cache["cross"] = ckv
        x = x + out
    aux = jnp.zeros((), jnp.float32)
    if ffn == "mlp":
        h = layers.norm_apply(cfg, p["norm2"], x)
        x = x + mlp.mlp_apply(cfg, p["ffn"], h)
    elif ffn == "moe":
        h = layers.norm_apply(cfg, p["norm2"], x)
        y, aux = moe.moe_apply(cfg, p["ffn"], h, dropless=True, layer=layer)
        x = x + y
    return x, cache, aux


# ---------------------------------------------------------------------------
# stack: scan over periods
# ---------------------------------------------------------------------------

def stack_init(cfg, rng, plan, n_periods: int, cross: bool = False) -> dict:
    def one_period(r):
        rs = layers.rsplit(r, len(plan))
        return {f"pos{i}": block_init(cfg, rs[i], mixer, ffn, cross=cross)
                for i, (mixer, ffn) in enumerate(plan)}

    stacked = jax.vmap(one_period)(jax.random.split(rng, n_periods))
    return retag_stacked(stacked, "layers")


def stack_full(cfg, values: dict, x: jax.Array, positions: jax.Array,
               plan, enc_out: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """values: stacked plain-array tree; x: (B,S,d). Returns (x, aux)."""

    def body(carry, period_params):
        x, aux = carry
        for i, (mixer, ffn) in enumerate(plan):
            x, a = block_full(cfg, period_params[f"pos{i}"], x, positions,
                              mixer, ffn, enc_out)
            aux = aux + a
        return (x, aux), None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(body, prevent_cse=False, policy=policy)
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   values)
    else:
        carry = (x, jnp.zeros((), jnp.float32))
        n = jax.tree.leaves(values)[0].shape[0]
        for i in range(n):
            carry, _ = body(carry, jax.tree.map(lambda v: v[i], values))
        x, aux = carry
    return x, aux


def stack_experts(cfg, values: dict, plan) -> Tuple[dict, dict]:
    """Take the routed expert weights of every MoE position out of a
    stacked tree: ``(values without them, {pos: {leaf: whole stack}})``.

    A serving stack closes over these, (L, E, d, f) and (L, E, f, d) in
    ``cfg.dtype``, and hands them whole to each layer's ragged matmuls with
    the layer's index (``moe._moe_dropless``); scanned as ``xs`` they would
    be sliced, and each slice copied, once per layer.  Positions of any
    other FFN keep every leaf in the scanned tree.
    """
    rest, experts = dict(values), {}
    for i, (_, ffn) in enumerate(plan):
        if ffn != "moe":
            continue
        key = f"pos{i}"
        routed = dict(values[key]["ffn"])
        experts[key] = {n: routed.pop(n).astype(cfg.dtype)
                        for n in moe.EXPERT_WEIGHTS}
        rest[key] = dict(values[key], ffn=routed)
    return rest, experts


def _with_experts(period_params: dict, experts: dict, key: str, layer):
    """One position's block parameters and the ``layer`` index its MoE
    FFN takes: the stacked expert leaves put back for a MoE position,
    ``(params, None)`` for any other."""
    if key not in experts:
        return period_params[key], None
    p = period_params[key]
    return dict(p, ffn=dict(p["ffn"], **experts[key])), layer


def stack_step(cfg, values: dict, x: jax.Array, positions: jax.Array,
               cache: dict, plan, protocol=None, rng=None):
    """Decode step through the whole stack; cache is scanned alongside.

    With a ``protocol`` (+ ``rng``, the tick's sensing key) every mlp-FFN
    fusion site aggregates through the simulated channel: one sensing key
    per period rides the scan as an xs leaf (``jax.random.split`` — a
    fold-in inside the traced body would reuse the key across periods) and
    the per-site accounting dicts accumulate in the carry.  The return then
    grows a fourth element, the summed channel-accounting dict of the whole
    stack; with ``protocol=None`` the scan structure and the 3-tuple return
    are the historical path, unchanged op for op.  MoE positions read their
    routed experts from the whole stacked weights (:func:`stack_experts`),
    the period's index riding the scan as a last xs leaf.
    """
    chan_mode = protocol is not None
    values, experts = stack_experts(cfg, values, plan)

    def body(carry, xs):
        layer = xs[-1] if experts else None
        if chan_mode:
            x, aux, chan = carry
            period_params, period_cache, k = xs[:3]
        else:
            x, aux = carry
            period_params, period_cache = xs[:2]
        new_cache = {}
        for i, (mixer, ffn) in enumerate(plan):
            key = f"pos{i}"
            p, lyr = _with_experts(period_params, experts, key, layer)
            if chan_mode:
                x, c, a, ch = block_step(
                    cfg, p, x, positions, period_cache[key],
                    mixer, ffn, protocol=protocol,
                    rng=jax.random.fold_in(k, i), layer=lyr)
                chan = fusion.chan_merge(chan, ch)
            else:
                x, c, a = block_step(cfg, p, x, positions,
                                     period_cache[key], mixer, ffn,
                                     layer=lyr)
            new_cache[key] = c
            aux = aux + a
        carry = (x, aux, chan) if chan_mode else (x, aux)
        return carry, new_cache

    n = jax.tree.leaves(values)[0].shape[0]
    init = (x, jnp.zeros((), jnp.float32))
    xs = (values, cache)
    if chan_mode:
        init = init + (fusion.chan_zeros(),)
        xs = xs + (jax.random.split(rng, n),)
    if experts:
        xs = xs + (jnp.arange(n),)
    if cfg.scan_layers:
        carry, new_cache = jax.lax.scan(body, init, xs)
    else:
        carry = init
        outs = []
        for i in range(n):
            carry, c = body(carry, jax.tree.map(lambda v: v[i], xs))
            outs.append(c)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    if chan_mode:
        x, aux, chan = carry
        return x, new_cache, aux, chan
    x, aux = carry
    return x, new_cache, aux


def stack_prefill(cfg, values: dict, x: jax.Array, positions: jax.Array,
                  plan, max_seq: int,
                  enc_out: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, dict, jax.Array]:
    """Full forward that also builds the stacked decode cache; MoE
    positions read their routed experts as in :func:`stack_step`."""
    values, experts = stack_experts(cfg, values, plan)
    n = jax.tree.leaves(values)[0].shape[0]
    xs = (values, jnp.arange(n)) if experts else values

    def body(carry, xs):
        period_params, layer = xs if experts else (xs, None)
        x, aux = carry
        cache = {}
        for i, (mixer, ffn) in enumerate(plan):
            key = f"pos{i}"
            p, lyr = _with_experts(period_params, experts, key, layer)
            x, c, a = block_prefill(cfg, p, x, positions,
                                    mixer, ffn, max_seq, enc_out, layer=lyr)
            cache[key] = c
            aux = aux + a
        return (x, aux), cache

    if cfg.scan_layers:
        (x, aux), cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs)
    else:
        carry = (x, jnp.zeros((), jnp.float32))
        outs = []
        for i in range(n):
            carry, c = body(carry, jax.tree.map(lambda v: v[i], xs))
            outs.append(c)
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        x, aux = carry
    return x, cache, aux


def stack_cache_init(cfg, plan, n_periods: int, batch: int, max_seq: int,
                     dtype, cross_len: int = 0) -> dict:
    one = {f"pos{i}": block_cache_init(cfg, mixer, batch, max_seq, dtype,
                                       cross_len)
           for i, (mixer, _) in enumerate(plan)}
    return jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (n_periods,) + v.shape), one)


def stack_cache_axes(cfg, plan, has_cross: bool) -> dict:
    one = {f"pos{i}": block_cache_axes(cfg, mixer, has_cross)
           for i, (mixer, _) in enumerate(plan)}
    return jax.tree.map(
        lambda ax: ("layers",) + tuple(ax), one,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))
