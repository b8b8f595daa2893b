"""Device time of the decode-tick program per tick: the summed duration
of its program events in the trace over their count."""

MARKER = "_tick"


def read(run, cell):
    s = run.summary
    if s is None:
        return None
    names = [n for n in s.module_ns if MARKER in n]
    count = sum(s.module_count[n] for n in names)
    if not count:
        return None
    return 1e3 * sum(s.module_ns[n] for n in names) / count / 1e9
