"""The routed experts' share of their roofline in the decode tick: the
least time of one tick's routed work (every expert's weights of the MoE
layers read once, 2 * 3 * d * f FLOPs per routed row of the slots the
tick served) times the ticks in the window, over the device time of the
operations under ``moe.experts``, of the ragged matmul custom calls and
of the copies of each layer's stacked expert weights, that ran inside a
tick program."""

from bench.lib import counts_moe as CM


def read(run, cell):
    s = CM.shapes(cell.config)
    work = CM.experts_tick_work(s, run.layer.get("tick_active", 0.0))
    return CM.tick_roofline(run, cell, "moe.experts", work,
                            (CM.RAGGED_DOT,), CM.expert_weights(s))
