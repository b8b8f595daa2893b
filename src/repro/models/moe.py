"""Mixture-of-Experts FFN with expert parallelism (EP).

Experts are sharded over the ``model`` mesh axis (one shard owns
``n_experts / N`` whole expert FFNs).

Router: scores are a softmax over the experts (default) or, with
``moe_score="sigmoid"``, each expert's sigmoid; a router with
``moe_select_bias`` adds a per-expert bias to the scores to *select* the
top-k (DeepSeek-V3 ``noaux_tc``) and weights the chosen experts by the
scores alone.  The weights are renormalised to sum 1 and scaled by
``moe_routed_scale``.

Two dispatch paths over the same routing:

* Dropless (``dropless=True``; prefill and decode, ``transformer.py``):
  every token reaches each of its top-k experts.  The (token, expert)
  rows are sorted by expert and the expert FFNs run as ragged matmuls
  (``jax.lax.ragged_dot``) over the groups, so their FLOPs scale with the
  routed rows.  Inside a layer stack the matmuls read the layer's experts
  straight out of the whole stacked weights (``layer=``): the stack's two
  leading axes (L, E) merge into L*E groups, a bitcast, and the group
  sizes hold the layer's row counts at groups [layer*E, (layer+1)*E) and
  zero everywhere else.  No per-layer slice of the weights is made, so
  none is copied ahead of the matmuls (a custom call takes no fused
  slice as an operand).  XLA's kernel visits no empty group, and is told
  to take each expert's weight as one block (``_ragged_dot``).
* Capacity-bounded (training and the full forward): *per-sequence grouped
  dispatch*, top-k selection, a sort **within each sequence** (vmapped,
  never a global cross-shard sort) and a scatter into per-expert buffers
  of ``capacity_factor``-scaled capacity.  Tokens beyond it are dropped
  (scatter with an out-of-bounds position; JAX drops OOB scatter
  updates), standard Switch/GShard semantics, with the usual
  load-balancing auxiliary loss.  The scatter/gather between the
  batch-sharded token axis and the expert-sharded buffer axis is where
  GSPMD emits the EP all-to-all.

Each traced MoE layer counts its path in :mod:`repro.obs`
(``moe.path.dropless`` / ``moe.path.capacity``) and, on the dropless path,
how its matmuls got their weights (``moe.weights.stacked``: the whole
stack; ``moe.weights.sliced``: one layer's (E, d, f) leaves); its device
work sits under the name scopes ``moe.route`` (router, top-k, sort and
permutation, combine) and ``moe.experts`` (the routed expert matmuls).

The FedOCS fusion law does not apply inside routed expert FFNs: an expert's
FFN lives wholly on one shard, so there is no cross-worker partial
reduction to replace.  The shared expert, which *is* worker-factored, uses
the standard MLP path and with a protocol pools through the channel.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro import obs
from repro.models import layers, mlp
from repro.parallel.sharding import constrain

PATH_COUNTER = "moe.path."
WEIGHTS_COUNTER = "moe.weights."
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
# XLA's ragged matmul on the TPU is a Mosaic kernel whose grid walks
# (row tile, group) pairs, skipping empty groups.  Left to itself it cuts
# a (2048, 1408) expert weight into (512, 128) blocks: thousands of grid
# steps a matmul, 2.6x slower on a v5e than one block per expert.  The
# weight block is double-buffered in the kernel's scoped VMEM, hence the
# bound on its bytes.
ROW_TILE = 128
WEIGHT_TILE_BYTES = 8 << 20


def moe_init(cfg, rng) -> dict:
    e, d = cfg.n_experts, cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    r = layers.rsplit(rng, 5)
    p = {
        "router": layers.param(r[0], (d, e), ("embed", None), jnp.float32,
                               scale=d ** -0.5),
        "w_up": layers.param(r[1], (e, d, f), ("experts", "embed", "ff_local"),
                             cfg.param_dtype, scale=d ** -0.5),
        "w_gate": layers.param(r[2], (e, d, f), ("experts", "embed", "ff_local"),
                               cfg.param_dtype, scale=d ** -0.5),
        "w_down": layers.param(r[3], (e, f, d), ("experts", "ff_local", "embed"),
                               cfg.param_dtype, scale=f ** -0.5),
    }
    if cfg.moe_select_bias:
        p["select_bias"] = layers.param(r[0], (e,), (None,), jnp.float32,
                                        mode="zeros")
    if cfg.moe_shared_expert:
        p["shared"] = mlp.mlp_init(
            cfg, r[4], d_ff=cfg.moe_shared_d_ff or cfg.moe_d_ff or cfg.d_ff)
    return p


def router_scores(cfg, p: dict, x: jax.Array) -> jax.Array:
    """(..., d) -> (..., E) float32 scores of every expert (the router's
    product in full float32, as DeepSeek's gate computes it)."""
    logits = jnp.matmul(x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_score == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def select(cfg, scores: jax.Array, bias=None):
    """Each token's top-k experts and their weights, ``(idx, w)`` each
    (..., k): chosen by ``scores + bias`` (the bias steers selection only),
    weighted by ``scores`` renormalised over the k and scaled by
    ``moe_routed_scale``."""
    k = cfg.experts_per_token
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / jnp.clip(jnp.sum(w, -1, keepdims=True), 1e-9)
    return idx, w * cfg.moe_routed_scale


def _capacity(cfg, tokens_per_seq: int) -> int:
    return max(1, math.ceil(
        tokens_per_seq * cfg.experts_per_token / cfg.n_experts
        * cfg.capacity_factor))


def _route_one_seq(cfg, probs: jax.Array, cap: int, bias=None):
    """probs: (S, E) router scores -> dispatch indices for one sequence.

    Returns (expert_idx, pos_in_expert, token_idx, weight), each (S*k,),
    with pos_in_expert == cap for dropped tokens (OOB scatter -> dropped).
    """
    s, e = probs.shape
    k = cfg.experts_per_token
    idx, w = select(cfg, probs, bias)                    # (S, k)
    e_flat = idx.reshape(-1)                             # (S*k,)
    w_flat = w.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)
    order = jnp.argsort(e_flat, stable=True)             # local per-seq sort
    e_s, w_s, t_s = e_flat[order], w_flat[order], tok_flat[order]
    counts = jnp.bincount(e_flat, length=e)              # (E,)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(s * k, dtype=jnp.int32) - start[e_s].astype(jnp.int32)
    pos = jnp.where(pos < cap, pos, cap)                 # cap == dropped
    return e_s, pos, t_s, w_s


def moe_apply(cfg, p: dict, x: jax.Array, dropless: bool = False,
              protocol=None, rng=None, layer=None):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    ``dropless`` routes every token to all of its top-k experts (serving);
    otherwise per-sequence capacity bounds the dispatch (training).  A
    dropless ``layer`` (an index into the stack) says that ``p``'s routed
    expert leaves are the whole stacked weights (:func:`_moe_dropless`).
    With a ``protocol`` the shared expert's worker partials pool through
    the channel (``mlp_apply(protocol=, rng=)``) and the return grows a
    third element, that call's accounting (``None`` without a shared
    expert).
    """
    if dropless:
        obs.count(PATH_COUNTER + "dropless")
        y, aux = _moe_dropless(cfg, p, x, layer)
    else:
        obs.count(PATH_COUNTER + "capacity")
        if cfg.moe_impl == "gather":
            y, aux = moe_apply_gather(cfg, p, x)
        else:
            y, aux = moe_apply_sort_scatter(cfg, p, x)
    acct = None
    if cfg.moe_shared_expert:
        if protocol is None:
            y = y + mlp.mlp_apply(cfg, p["shared"], x)
        else:
            ys, acct = mlp.mlp_apply(cfg, p["shared"], x, protocol=protocol,
                                     rng=rng)
            y = y + ys
    return (y, aux) if protocol is None else (y, aux, acct)


def _ragged_dot(rows: jax.Array, w: jax.Array, sizes: jax.Array
                ) -> jax.Array:
    """``jax.lax.ragged_dot(rows, w, sizes)`` with the rows padded to a
    multiple of ROW_TILE and, where a group's (K, N) weight fits
    WEIGHT_TILE_BYTES, XLA's kernel told to take it as one block
    (``ragged_dot_tiling``; backends without the kernel ignore it).  The
    padded rows lie past the last group and are dropped.  The padding
    also keeps the TPU compiler from refusing a ragged dot of a few rows
    over some group counts (6 or 30 rows over 320 or 384 groups)."""
    m = rows.shape[0]
    rows = jnp.pad(rows, ((0, -m % ROW_TILE), (0, 0)))
    k, n = w.shape[1:]
    if k * n * w.dtype.itemsize > WEIGHT_TILE_BYTES:
        return jax.lax.ragged_dot(rows, w, sizes)[:m]
    with set_xla_metadata(ragged_dot_tiling=f"{ROW_TILE},{k},{n}"):
        return jax.lax.ragged_dot(rows, w, sizes)[:m]


def _moe_dropless(cfg, p: dict, x: jax.Array, layer=None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Routed experts of every token, no capacity: (token, expert) rows
    sorted by expert, one ragged matmul per expert weight.

    Without ``layer``, ``p``'s expert leaves are one layer's, (E, d, f)
    and (E, f, d), and the groups are the E experts.  With ``layer`` they
    are the whole stack's, (L, E, d, f) and (L, E, f, d): each is viewed
    as (L*E, d, f) or (L*E, f, d) (merging the leading axes is a bitcast)
    and the ragged matmuls run over all L*E groups, the layer's row counts
    at groups [layer*E, (layer+1)*E) and every other group empty.  The
    rows stay sorted by expert as without ``layer``, so the layer's
    experts are selected by the group sizes alone and read in place.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    dt = cfg.dtype
    xt = x.reshape(b * s, d).astype(dt)
    with jax.named_scope("moe.route"):
        idx, w = select(cfg, router_scores(cfg, p, xt), p.get("select_bias"))
        flat = idx.reshape(-1)                           # (T*k,)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        rows = jnp.take(xt, order // k, axis=0)          # (T*k, d)
        if layer is not None:
            groups = p["w_gate"].shape[0] * e
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((groups,), jnp.int32), sizes, (layer * e,))
    with jax.named_scope("moe.experts"):
        w_gate, w_up, w_down = (p[n].astype(dt) for n in EXPERT_WEIGHTS)
        if layer is None:
            obs.count(WEIGHTS_COUNTER + "sliced")
        else:
            obs.count(WEIGHTS_COUNTER + "stacked")
            w_gate, w_up, w_down = (v.reshape((-1,) + v.shape[2:])
                                    for v in (w_gate, w_up, w_down))
        gate = _ragged_dot(rows, w_gate, sizes)
        up = _ragged_dot(rows, w_up, sizes)
        out = _ragged_dot(jax.nn.silu(gate) * up, w_down, sizes)
    with jax.named_scope("moe.route"):
        out = jnp.take(out, jnp.argsort(order), axis=0)  # back to (t, k)
        y = jnp.einsum("tkd,tk->td", out.reshape(b * s, k, d).astype(
            jnp.float32), w).astype(dt)
    return y.reshape(b, s, d), jnp.zeros((), jnp.float32)


def moe_apply_sort_scatter(cfg, p: dict, x: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(cfg, s)
    dt = cfg.dtype

    probs = router_scores(cfg, p, x)                     # (B, S, E)
    bias = p.get("select_bias")
    e_s, pos, t_s, w_s = jax.vmap(
        lambda pr: _route_one_seq(cfg, pr, cap, bias))(probs)  # (B, S*k)

    # dispatch: (B, S, d) -> (B, E, cap, d); OOB pos rows are dropped
    def scatter_one(xb, eb, pb, tb):
        buf = jnp.zeros((e, cap, d), dt)
        return buf.at[eb, pb].set(xb[tb], mode="drop")

    buf = jax.vmap(scatter_one)(x.astype(dt), e_s, pos, t_s)
    buf = constrain(buf, ("batch", "experts", None, "embed"))

    # expert FFN (SwiGLU), batched over (B, E): weights indexed by E
    gate = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dt))
    up = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dt))
    hidden = jax.nn.silu(gate) * up
    hidden = constrain(hidden, ("batch", "experts", None, "ff_local"))
    out_buf = jnp.einsum("becf,efd->becd", hidden, p["w_down"].astype(dt))
    out_buf = constrain(out_buf, ("batch", "experts", None, "embed"))

    # combine: gather back and weight
    def gather_one(ob, eb, pb, tb, wb):
        vals = ob[eb, jnp.minimum(pb, cap - 1)]          # (S*k, d)
        keep = (pb < cap).astype(dt)[:, None]
        y = jnp.zeros((s, d), dt)
        return y.at[tb].add(vals * wb[:, None].astype(dt) * keep)

    y = jax.vmap(gather_one)(out_buf, e_s, pos, t_s, w_s)
    y = constrain(y, ("batch", "seq", "embed"))

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=(0, 1))                    # (E,)
    dispatch_frac = jnp.zeros((e,), jnp.float32).at[e_s.reshape(-1)].add(
        1.0 / (b * s * k))
    aux = cfg.n_experts * jnp.sum(dispatch_frac * me)
    return y, aux.astype(jnp.float32)


def moe_apply_gather(cfg, p: dict, x: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Gather-dispatch / scatter-combine EP (hillclimb lever, §Perf).

    Against ``sort_scatter``, this formulation keeps the expensive tensors
    local: tokens ``x`` are replicated over the model axis between blocks, so
    each shard *gathers* its own experts' token rows (zero collective), runs
    its expert FFNs, and scatter-adds its partial outputs into token space —
    the only collective is one all-reduce(add) of the (B, S, d) combine,
    identical to a dense TP block.  The sort_scatter formulation instead
    gathers from the expert-sharded buffer with replicated indices, which
    GSPMD must realize as an all-gather of the whole (B, E, cap, d) buffer —
    the dominant collective in the qwen3-moe baseline.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = _capacity(cfg, s)
    dt = cfg.dtype

    probs = router_scores(cfg, p, x)
    bias = p.get("select_bias")
    e_s, pos, t_s, w_s = jax.vmap(
        lambda pr: _route_one_seq(cfg, pr, cap, bias))(probs)  # (B, S*k)

    # slot->token inverse map + slot weights (tiny int/float buffers)
    def invert(eb, pb, tb, wb):
        tok_of = jnp.full((e, cap), s, jnp.int32)        # s == "no token"
        tok_of = tok_of.at[eb, pb].set(tb, mode="drop")
        w_of = jnp.zeros((e, cap), jnp.float32)
        w_of = w_of.at[eb, pb].set(wb, mode="drop")
        return tok_of, w_of

    tok_of, w_of = jax.vmap(invert)(e_s, pos, t_s, w_s)  # (B, E, cap)

    # dispatch: LOCAL gather of each shard's experts' rows (x replicated,
    # tok_of replicated, output expert-sharded)
    xz = jnp.concatenate([x.astype(dt), jnp.zeros((b, 1, d), dt)], axis=1)
    buf = jnp.take_along_axis(
        xz[:, None, :, :],                               # (B, 1, S+1, d)
        tok_of[..., None].astype(jnp.int32), axis=2)     # (B, E, cap, d)
    buf = constrain(buf, ("batch", "experts", None, "embed"))

    gate = jnp.einsum("becd,edf->becf", buf, p["w_gate"].astype(dt))
    up = jnp.einsum("becd,edf->becf", buf, p["w_up"].astype(dt))
    hidden = jax.nn.silu(gate) * up
    hidden = constrain(hidden, ("batch", "experts", None, "ff_local"))
    out_buf = jnp.einsum("becf,efd->becd", hidden, p["w_down"].astype(dt))
    out_buf = out_buf * w_of[..., None].astype(dt)
    out_buf = constrain(out_buf, ("batch", "experts", None, "embed"))

    # combine: scatter-add partials into token space; the cross-expert sum
    # over the sharded E axis lowers to one all-reduce(add) of (B, S, d)
    def combine_one(ob, tof):
        y = jnp.zeros((s + 1, d), dt)
        y = y.at[tof.reshape(-1)].add(ob.reshape(-1, d), mode="drop")
        return y[:s]

    y = jax.vmap(combine_one)(out_buf, tok_of)
    y = constrain(y, ("batch", "seq", "embed"))

    me = jnp.mean(probs, axis=(0, 1))
    dispatch_frac = jnp.zeros((e,), jnp.float32).at[e_s.reshape(-1)].add(
        1.0 / (b * s * k))
    aux = cfg.n_experts * jnp.sum(dispatch_frac * me)
    return y, aux.astype(jnp.float32)
