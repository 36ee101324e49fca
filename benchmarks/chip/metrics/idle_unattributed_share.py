"""Share of the window's device-idle time in which the host was in none of
the program's ``ltsp.*`` spans and no ``verify_schedule`` span, in % (device
trace): what the spans leave unexplained.  Host work added outside the spans
shows here."""

from program_spans import of, overlap_ns
from trace_reduce import idle_gaps


def read(run):
    spans = of(run)
    if spans is None:
        return None
    w = run.trace.window()
    # host time outside every span that names its work
    unnamed = idle_gaps(spans + run.trace.spans("verify_schedule"), w.start, w.end)
    idle = unattributed = 0.0
    for ops in run.trace.device_ops.values():
        gaps = idle_gaps(ops, w.start, w.end)
        idle += sum(e - s for s, e in gaps)
        unattributed += overlap_ns(gaps, unnamed)
    return 100.0 * unattributed / idle if idle else None
