"""Share of the window in which no operation runs on the chip, in % (device
trace).  In an open loop it includes the waits for arrivals."""

from layers import busy_ns


def read(run):
    if run.trace is None:
        return None
    w = run.trace.window()
    return 100.0 * (1.0 - busy_ns(run) / (w.end - w.start))
