"""Mean host time of one admission (batch-1 prefill, cache scatter, the
first token's read) from the program's own ``serve.admit`` spans that end
in the window: the in-program twin of ``serve.prefill_ms``."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.mean_ms(P.load(run), "serve.admit")
