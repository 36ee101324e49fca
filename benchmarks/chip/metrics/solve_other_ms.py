"""Time per decision inside ``solve_batch`` when the wavefront kernel is not
running, in ms: rescale, packing, launches, scatters, the copy of the argmin
plane and the traceback (host spans minus kernel device time, one trace)."""

from layers import span_ns, wavefront_ns


def read(run):
    spans = span_ns(run, "solve_batch")
    kernel = wavefront_ns(run)
    if not spans or kernel is None:
        return None
    return (sum(spans) - kernel) / 1e6 / len(spans)
