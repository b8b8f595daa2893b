"""Operation and byte counts of a latent-attention MoE decoder (the
DeepSeek-V3 block of moonlight-16b-a3b), from its configuration's shapes.

The model FLOPs count the *active* parameters: a token runs the attention
projections, its FFN (the dense layer's, or the router, its top-k routed
experts and the shared experts) and, where its logits are computed, the LM
head.  Attention over the context counts as each path computes it: a
prompt position expanded (scores over ``qk_nope + qk_rope`` per head, values
``v_head_dim`` wide, the latent lifted through ``kv_b`` once per
position), a decoded token absorbed (``W_uk`` into the query, scores and
values over the ``kv_lora_rank`` latent, ``W_uv`` after the sum).  The
roofline numerators count the logical work of one decode tick from the
slots it serves, never from the padded cache or buffers.
"""

from __future__ import annotations

import dataclasses
import functools
import re


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_lead: int            # leading dense layers
    n_moe: int             # MoE layers after them
    d: int
    heads: int
    r: int                 # kv_lora_rank
    nope: int
    rope: int
    v: int
    d_ff: int              # the dense layers' FFN
    experts: int
    k: int                 # experts per token
    f: int                 # one expert's width
    f_shared: int          # the shared experts' summed width
    vocab: int
    itemsize: int          # bytes of a weight or cache element

    @property
    def n_layers(self) -> int:
        return self.n_lead + self.n_moe


def shapes(config: dict) -> Shapes:
    """The sizes from a configuration file (``bench/configs/*.json``)."""
    lead = config["first_k_dense_replace"]
    return Shapes(
        n_lead=lead, n_moe=config["n_layers"] - lead,
        d=config["d_model"], heads=config["n_heads"],
        r=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        d_ff=config["d_ff"], experts=config["n_routed_experts"],
        k=config["num_experts_per_tok"],
        f=config["moe_intermediate_size"],
        f_shared=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        vocab=config["vocab_size"],
        itemsize={"bfloat16": 2, "float32": 4}[config["dtype"]])


def projection_flops(s: Shapes) -> int:
    """One token's attention projections in one layer: q, the latent and
    k_pe, the output."""
    return 2 * s.d * (s.heads * (s.nope + s.rope) + s.r + s.rope
                      + s.heads * s.v)


def ffn_flops(s: Shapes, moe: bool) -> int:
    """One token's FFN in one layer: the dense SwiGLU, or the router, its
    k routed experts and the shared experts."""
    if not moe:
        return 6 * s.d * s.d_ff
    return 2 * s.d * s.experts + 6 * s.d * (s.k * s.f + s.f_shared)


def attend_flops(s: Shapes, context: int, absorbed: bool) -> int:
    """One token's attention over ``context`` positions in one layer,
    with the latent's per-token lift (expanded) or the query absorption
    and value lift (absorbed)."""
    lift = 2 * s.heads * s.r * (s.nope + s.v)
    width = 2 * s.r + s.rope if absorbed else s.nope + s.rope + s.v
    return lift + 2 * s.heads * context * width


def token_flops(s: Shapes, context: int, absorbed: bool = True,
                lm_head: bool = True) -> int:
    """Forward FLOPs of one token at ``context`` (itself included)."""
    per_layer = projection_flops(s) + attend_flops(s, context, absorbed)
    body = (s.n_layers * per_layer + s.n_lead * ffn_flops(s, False)
            + s.n_moe * ffn_flops(s, True))
    return body + (2 * s.d * s.vocab if lm_head else 0)


def prefill_flops(s: Shapes, prompt_len: int) -> int:
    """A prompt, expanded: every position's layers at its causal context,
    the LM head of the last position only."""
    return (sum(token_flops(s, c, absorbed=False, lm_head=False)
                for c in range(1, prompt_len + 1)) + 2 * s.d * s.vocab)


def experts_tick_work(s: Shapes, active: float) -> dict:
    """The routed expert matmuls of one decode tick serving ``active``
    slots: every expert's weights read once in each MoE layer, and
    ``2 * 3 * d * f`` FLOPs per routed row."""
    rows = active * s.k
    return {"ops": s.n_moe * rows * 6 * s.d * s.f,
            "bytes": s.n_moe * s.experts * 3 * s.d * s.f * s.itemsize}


def attend_tick_work(s: Shapes, context_sum: float) -> dict:
    """The absorbed attention of one decode tick, its slots' contexts
    summing to ``context_sum``: each slot's latent cache read once per
    layer at its context, the scores (``2 * (r + rope)``) and the weighted
    latent sum (``2 * r``) per head and position."""
    return {"ops": s.n_layers * context_sum * s.heads * 2 * (2 * s.r
                                                             + s.rope),
            "bytes": s.n_layers * context_sum * (s.r + s.rope) * s.itemsize}



# ---------------------------------------------------------------------------
# readings of a traced run
# ---------------------------------------------------------------------------

# XLA's ragged matmul custom calls (and their group metadata) carry no
# scope path: their metadata names only themselves.  Here they are the
# routed experts' matmuls and nothing else.
RAGGED_DOT = "ragged-dot"


def expert_weights(s: Shapes) -> tuple:
    """The sorted dimensions of one MoE layer's stacked expert weights
    (``w_gate`` and ``w_up`` (E, d, f), ``w_down`` (E, f, d)).  The scan
    over the layers slices each from the stack and, since a custom call
    takes no fused slice, copies it before the ragged matmuls; those
    copies carry the scan's path, not the experts' scope, and are known
    by their shape."""
    return (tuple(sorted((s.experts, s.d, s.f))),)


def out_dims(text: str):
    """The dimensions of an operation's (array) result, from its HLO text
    (``%name = bf16[64,2048,1408]{...} ...``); ``None`` for a tuple."""
    m = re.match(r"[^=]*=\s*[a-z][a-z0-9]*\[([0-9,]*)\]", text)
    if m is None:
        return None
    return tuple(int(x) for x in m.group(1).split(",") if x)


@functools.lru_cache(maxsize=None)
def _counts(text: str, path: str, scope: str, names: tuple,
            dims: tuple) -> bool:
    """Whether the operation counts (see :func:`scope_share`); kept per
    distinct operation, since a traced window repeats each one in every
    tick, millions of events in all."""
    from bench.lib import program_trace as P
    from bench.lib import trace as T

    if P.in_scope(path, scope) or T.short_name(text).startswith(
            tuple(names)):
        return True
    out = out_dims(text) if dims else None
    return out is not None and tuple(sorted(out)) in dims


def scope_share(run, scope: str, names=(), dims=()):
    """Device time of the operations under ``scope`` (or named by one of
    ``names``, or whose result has sorted dimensions in ``dims``), in
    percent of every operation's in the window; ``None`` where none is (a
    program without the scope)."""
    from bench.lib import program_trace as P

    pt = P.load(run)
    if pt is None:
        return None
    ops = [op for dev in pt.ops.values() for op in dev]
    part = sum(e - s for text, s, e, path in ops
               if _counts(text, path, scope, names, dims))
    total = sum(e - s for _, s, e, _ in ops)
    return 100.0 * part / total if part else None


def tick_programs(trace) -> dict:
    """Per device, the intervals in which a decode-tick program ran, from
    a trace's program events (``bench.lib.trace.Trace``)."""
    from bench.lib import trace as T

    return {dev: T.merge((s, e) for name, s, e in events if "_tick" in name)
            for dev, events in trace.modules.items()}


def tick_roofline(run, cell, scope: str, work: dict, names=(), dims=()):
    """Roofline share (%) of the decode tick's operations under ``scope``
    (or named by one of ``names``, or whose result has sorted dimensions
    in ``dims``): the least time of ``work`` (one
    tick's logical ``ops`` at the bf16 peak and ``bytes`` at the HBM
    peak) times the tick programs that ran in the traced window, over the
    device time of those operations that ran inside a tick program (the
    driver's ``tick_programs`` of the trace, in ``run.layer``); ``None``
    where the trace holds none."""
    from bench.lib import counts
    from bench.lib import program_trace as P

    pt, s = P.load(run), run.summary
    if pt is None or s is None:
        return None
    ticks = sum(n for name, n in s.module_count.items() if "_tick" in name)
    inside = run.layer.get("tick_programs", {})
    ns = sum(e - b for dev, ops in pt.ops.items() for text, b, e, path in ops
             if _counts(text, path, scope, names, dims)
             and any(lo <= b < hi for lo, hi in inside.get(dev, ())))
    if not ticks or not ns:
        return None
    peak = counts.peaks(run.layer["device_kind"])
    return counts.roofline_share(work["ops"] * ticks, work["bytes"] * ticks,
                                 ns / 1e9 / s.n_devices, peak,
                                 "bf16_flops_per_s")["share_pct"]
