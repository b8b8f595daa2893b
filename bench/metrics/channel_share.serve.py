"""The channel aggregate's share of device time: operations under the
``protocol.aggregate`` name scope (quantization, sensing draws, the
contention kernel, winner routing) over every operation in the window."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.scope_share(P.load(run), "protocol.aggregate")
