"""Device-to-host rate of the argmin copy, in GB/s: the bytes the window's
launches moved to the host (``LaunchRecord.d2h_bytes``: the argmin plane, and
a few bytes of roots and multiplicities) over the time of the program's
``ltsp.fetch_argmin`` spans."""

from program_spans import of


def read(run):
    spans = of(run)
    moved = [getattr(r, "d2h_bytes", None) for r in run.launches]
    if spans is None or not moved or None in moved:
        return None
    ns = sum(s.end - s.start for s in spans if s.name == "ltsp.fetch_argmin")
    return sum(moved) / ns if ns else None  # bytes per ns is GB/s
