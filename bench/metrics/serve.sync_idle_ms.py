"""Device idle time spent inside the serving loop's device-to-host reads,
per decode tick: the part of the window in which no operation ran on the
device that lies inside the union of the program's ``serve.sync`` spans,
over the ``serve.tick`` spans starting in the window."""

from bench.lib import program_trace as P


def read(run, cell):
    return P.idle_inside_ms_per_tick(P.load(run), "serve.sync")
