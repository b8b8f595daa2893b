"""Operation and byte counts of the work the benchmark times, from shapes.

These are the yardstick's numerators: a later change that retiles a kernel
or reorders a model is judged against the same logical work, never against
what its own buffers happen to hold.
"""

from __future__ import annotations

import json
import math
import pathlib
from typing import Sequence

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an
    error."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def id_bits(n_workers: int) -> int:
    """Tie-break sub-slots of an OCS contention among ``n_workers``."""
    return max(1, math.ceil(math.log2(max(n_workers, 2))))


# ---------------------------------------------------------------------------
# the vertical FedOCS learner (paper §II)
# ---------------------------------------------------------------------------

def mlp_matmul_flops(dims: Sequence[int]) -> int:
    """Forward multiply-add FLOPs of one sample through dense layers."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def vertical_fwd_flops(n_workers: int, input_dim: int,
                       encoder_dims: Sequence[int], embed_dim: int,
                       head_dims: Sequence[int], n_classes: int) -> int:
    """Forward matmul FLOPs per sample: every worker's encoder, then the
    fusion head on the pooled K-wide embedding."""
    enc = mlp_matmul_flops([input_dim, *encoder_dims, embed_dim])
    head = mlp_matmul_flops([embed_dim, *head_dims, n_classes])
    return n_workers * enc + head


def vertical_train_flops(**shapes) -> int:
    """Forward plus backward (twice the forward) per trained sample."""
    return 3 * vertical_fwd_flops(**shapes)


# ---------------------------------------------------------------------------
# a dense decoder-only transformer (qwen1.5)
# ---------------------------------------------------------------------------

def decoder_matmul_params(n_layers: int, d_model: int, n_heads: int,
                          n_kv_heads: int, head_dim: int, d_ff: int,
                          gated: bool = True) -> int:
    """Weights every token multiplies by: attention projections and the
    FFN, all layers (the LM head is counted apart)."""
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    ffn = d_model * d_ff * (3 if gated else 2)
    return n_layers * (attn + ffn)


def decoder_token_flops(context: int, *, n_layers: int, d_model: int,
                        n_heads: int, n_kv_heads: int, head_dim: int,
                        d_ff: int, vocab_size: int, gated: bool = True,
                        lm_head: bool = True) -> int:
    """Forward FLOPs of one token that attends over ``context`` positions
    (itself included): projections and FFN, scores and weighted values,
    and the LM head when the token's logits are computed."""
    w = decoder_matmul_params(n_layers, d_model, n_heads, n_kv_heads,
                              head_dim, d_ff, gated)
    attn = n_layers * 4 * context * n_heads * head_dim
    head = 2 * d_model * vocab_size if lm_head else 0
    return 2 * w + attn + head


def prefill_flops(prompt_len: int, **model) -> int:
    """Forward FLOPs of a prompt: every position's projections and causal
    attention, and the LM head of the last position only."""
    body = sum(decoder_token_flops(c, lm_head=False, **model)
               for c in range(1, prompt_len + 1))
    return body + 2 * model["d_model"] * model["vocab_size"]


# ---------------------------------------------------------------------------
# the OCS contention kernel (kernels/ocs_contention)
# ---------------------------------------------------------------------------

def contention_work(n: int, k: int, bits: int, idb: int, max_rounds: int
                    ) -> dict:
    """Logical work of one noisy contention over ``n`` workers and ``k``
    sub-frames, from these five numbers alone.

    Bytes: the ``n*k`` contention words of ``bits + idb`` bits in, the
    ``max_rounds * (bits + idb) * n * k`` sensing bits in, ``k`` winners of
    ``idb`` bits and two ``max_rounds``-long int32 accounting rows out.
    Operations: one alive-state update per (round, sub-slot, worker,
    sub-frame)."""
    word = bits + idb
    sensing_bits = max_rounds * word * n * k
    bytes_in = (n * k * word + sensing_bits) / 8
    bytes_out = k * idb / 8 + 2 * max_rounds * 4
    return {"ops": sensing_bits, "bytes": bytes_in + bytes_out}


def roofline_share(ops: float, nbytes: float, seconds: float, peak: dict,
                   ops_key: str) -> dict:
    """Least time the chip could take over the measured time, and which
    of compute (``ops`` at ``peak[ops_key]``) or memory bounds it."""
    t_ops = ops / peak[ops_key]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return {"share_pct": 100.0 * max(t_ops, t_mem) / seconds,
            "bound": "compute" if t_ops >= t_mem else "memory"}


def kernel_roofline(run, cell):
    """Roofline share (%) of the kernel named by the cell's
    ``kernel_marker`` over a traced window, or ``None`` where the trace
    holds none of its events.

    ``run.layer["kernel_work"]`` lists ``(calls per unit, work per call)``
    and ``run.layer["kernel_units"]`` the units (dispatches or ticks) the
    window ran whole."""
    s = run.summary
    if s is None:
        return None
    ns = s.ops_matching(cell.workload["kernel_marker"])
    if ns <= 0:
        return None
    units = run.layer["kernel_units"]
    ops = sum(c * w["ops"] for c, w in run.layer["kernel_work"]) * units
    nbytes = sum(c * w["bytes"] for c, w in run.layer["kernel_work"]) * units
    peak = peaks(run.layer["device_kind"])
    return roofline_share(ops, nbytes, ns / 1e9 / s.n_devices, peak,
                          "int8_ops_per_s")["share_pct"]
