"""The latent attention's share of its roofline in the decode tick: the
least time to read each served slot's latent cache at its context in every
layer, with the absorbed scores and weighted sums, times the ticks in the
window, over the device time of the operations under ``mla.attend`` that
ran inside a tick program."""

from bench.lib import counts_moe as CM


def read(run, cell):
    work = CM.attend_tick_work(CM.shapes(cell.config),
                               run.layer.get("tick_context_sum", 0.0))
    return CM.tick_roofline(run, cell, "mla.attend", work)
