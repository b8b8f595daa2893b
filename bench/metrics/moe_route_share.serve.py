"""MoE routing's share of device time: operations under the ``moe.route``
name scope (router, top-k, the sort of (token, expert) rows by expert and
their gather, the weighted combine) over every operation in the window."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.scope_share(P.load(run), "moe.route")
