"""The program's own phase spans in a ``--trace 1`` run: ``ltsp.*``.

A traced run attaches a ``repro.obs.KernelProfile``, so every phase of a
device launch inside ``ltsp_solve_batch`` opens a ``jax.profiler``
annotation (``ltsp.rescale`` ... ``ltsp.release``) tagged
``launch=<index of its LaunchRecord>``.  They lie on the device trace's
clock, beside the benchmark's own spans.  :func:`of` gives a run's spans in
its window: loaded once from the newest ``.xplane.pb`` under the harness's
trace directory and kept on the run, or, for a window recorded as test data,
the ones ``record_spans.py`` kept and the test put on the run.  Against a
program that opens no such span every reader here gives ``None``.
"""

from __future__ import annotations

import dataclasses
import warnings

import harness
import trace_reduce
from layers import span_ns

__all__ = ["Span", "PREFIX", "load", "to_json", "from_json", "of", "per_decision_ms",
           "overlap_ns"]

PREFIX = "ltsp."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    end: float  # ns
    launch: int  # index of the launch's record in the run's KernelProfile


def load(path: str) -> list[Span]:
    """The ``ltsp.*`` host events of an ``.xplane.pb`` file, by start."""
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        # reading an event's stats makes the profiler's binding warn about
        # its own type; under -W error that warning would abort the process
        warnings.simplefilter("ignore", DeprecationWarning)
        spans = [
            Span(e.name, e.start_ns, e.end_ns, int(dict(e.stats)["launch"]))
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith(PREFIX)
        ]
    return sorted(spans, key=lambda s: s.start)


def to_json(spans: list[Span], window) -> list[list]:
    """The spans inside ``window`` as ``[name, start, end, launch]``, in ns
    after the window opened (as ``trace_reduce.to_json`` keeps its lists)."""
    return [[s.name, s.start - window.start, s.end - window.start, s.launch]
            for s in spans if s.start >= window.start and s.end <= window.end]


def from_json(rows: list[list]) -> list[Span]:
    """The inverse of :func:`to_json`."""
    return [Span(*row) for row in rows]


def of(run) -> list[Span] | None:
    """The run's ``ltsp.*`` spans inside its window, or ``None`` where it has
    no trace or its trace holds none."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_spans"):
        try:
            path = trace_reduce.find_xplane(str(harness.TRACE_DIR))
        except FileNotFoundError:
            path = None
        run.program_spans = load(path) if path else []
    w = run.trace.window()
    inside = [s for s in run.program_spans if s.start >= w.start and s.end <= w.end]
    return inside or None


def per_decision_ms(run, names: tuple[str, ...]) -> float | None:
    """Time per decision in the spans named ``names``, in ms: their sum over
    the window's ``solve_batch`` spans."""
    spans, decisions = of(run), span_ns(run, "solve_batch")
    if spans is None or not decisions:
        return None
    return sum(s.end - s.start for s in spans if s.name in names) / 1e6 / len(decisions)


def overlap_ns(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint pieces."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
