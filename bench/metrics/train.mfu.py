"""The whole training step's share of the chips' bf16 peak: the learner's
forward and backward matmul FLOPs per lane-sample, times the lane-samples
trained in the window, over the window's host-clock seconds."""

from bench.lib import counts


def read(run, cell):
    lay = run.layer
    if lay.get("window_s", 0) <= 0 or not lay.get("samples"):
        return None
    peak = counts.peaks(lay["device_kind"])["bf16_flops_per_s"]
    rate = lay["flops_per_sample"] * lay["samples"] / lay["window_s"]
    return 100.0 * rate / (cell.chips * peak)
