"""Time per decision after the argmin plane is on the host, in ms: the root
read, the traceback and the release of the tables (the program's
``ltsp.fetch_root``, ``ltsp.traceback`` and ``ltsp.release`` spans, trace)."""

from program_spans import per_decision_ms


def read(run):
    return per_decision_ms(run, ("ltsp.fetch_root", "ltsp.traceback", "ltsp.release"))
