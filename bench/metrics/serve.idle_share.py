"""Share of the traced window in which no operation ran on the device
while the serving engine's host loop drove it."""


def read(run, cell):
    s = run.summary
    if s is None or s.window_ns <= 0 or not s.n_devices:
        return None
    return 100.0 * (1.0 - s.busy_ns / s.window_ns)
