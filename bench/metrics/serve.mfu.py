"""The whole serving step's share of the chip's bf16 peak: the model FLOPs
of the window's work (every decoded token at its context length, LM head
included, and every admitted prompt's prefill) over the window's
host-clock seconds."""

from bench.lib import counts


def read(run, cell):
    lay = run.layer
    if lay.get("window_s", 0) <= 0 or not lay.get("model_flops"):
        return None
    peak = counts.peaks(lay["device_kind"])["bf16_flops_per_s"]
    return 100.0 * lay["model_flops"] / lay["window_s"] / (cell.chips * peak)
