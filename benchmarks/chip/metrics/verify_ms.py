"""Time per decision in ``verify_schedule``, in ms (host spans, trace)."""

from layers import span_ns


def read(run):
    spans = span_ns(run, "verify_schedule")
    return sum(spans) / 1e6 / len(spans) if spans else None
