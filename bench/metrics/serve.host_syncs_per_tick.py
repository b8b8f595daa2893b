"""Device-to-host reads the serving loop makes per decode tick: the
program's ``serve.sync`` spans starting in the window over its
``serve.tick`` spans starting in it (admissions' first-token reads
included)."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.per_tick(P.load(run), "serve.sync")
