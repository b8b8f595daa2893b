"""The routed experts' share of device time: operations under the
``moe.experts`` name scope, XLA's ragged matmul custom calls (whose
metadata drops the scope; here they are the experts' matmuls over the
(token, expert) rows sorted by expert), and the copies of each layer's
stacked expert weights that the scan makes before them (known by their
shape), in the decode tick and in prefill, over every operation in the
window."""

from bench.lib import counts_moe as CM


def read(run, cell):
    return CM.scope_share(run, "moe.experts", (CM.RAGGED_DOT,),
                          CM.expert_weights(CM.shapes(cell.config)))
