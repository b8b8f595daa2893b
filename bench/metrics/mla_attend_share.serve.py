"""The latent attention's share of device time: operations under the
``mla.attend`` name scope (scores, softmax and weighted sum, over the
latent cache in the decode tick and over the prompt in prefill) over every
operation in the window."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.scope_share(P.load(run), "mla.attend")
