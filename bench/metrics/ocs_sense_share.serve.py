"""The sensing draws' share of device time: operations under the
``ocs.sense`` name scope (the carrier-sensing Bernoulli draws and their
bit-plane packing) over every operation in the window."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.scope_share(P.load(run), "ocs.sense")
