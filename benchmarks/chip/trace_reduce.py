"""Reduction of a JAX profiler trace to the events the metrics read.

:func:`load` reads the ``.xplane.pb`` file that ``jax.profiler`` writes and
keeps two kinds of interval, in nanoseconds on the trace's one clock:

* device operations: the events of each device plane's op line
  (:data:`DEVICE_OPS_LINE`), the time an operation runs on the chip;
* host spans: the benchmark's own ``TraceAnnotation`` spans (names in
  :data:`HOST_SPANS`), on whichever host thread recorded them.

The rest are pure functions over those lists, so a test can check them on a
small trace recorded on the chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os

__all__ = [
    "HOST_SPANS",
    "Interval",
    "Reduced",
    "load",
    "to_json",
    "from_json",
    "find_xplane",
    "union_ns",
    "idle_gaps",
    "label_at",
    "leaves",
    "short_name",
]

#: device planes are named ``/device:TPU:<i>``; their op line holds one
#: event per operation that ran
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
#: stats of a device event that name the framework operation behind it
DETAIL_STATS = ("tf_op", "long_name")
#: the benchmark's host spans (see harness.py)
HOST_SPANS = ("window", "solve_batch", "verify_schedule", "wait_arrival")


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start: float  # ns
    end: float  # ns
    detail: str = ""  # a device operation's framework name (DETAIL_STATS)


@dataclasses.dataclass
class Reduced:
    """Device operations per chip and the benchmark's host spans."""

    device_ops: dict[str, list[Interval]]
    host_spans: list[Interval]

    def spans(self, name: str) -> list[Interval]:
        return [s for s in self.host_spans if s.name == name]

    def window(self) -> Interval:
        """The measured window: the one ``window`` span."""
        [w] = self.spans("window")
        return w


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Reduced:
    """Read an ``.xplane.pb`` file into device operations and host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Interval]] = {}
    spans: list[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        Interval(e.name, e.start_ns, e.end_ns, _detail(e))
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    Interval(e.name, e.start_ns, e.end_ns)
                    for e in line.events
                    if e.name in HOST_SPANS
                )
    for v in ops.values():
        v.sort(key=lambda i: i.start)
    spans.sort(key=lambda i: i.start)
    return Reduced(ops, spans)


def to_json(reduced: Reduced) -> dict:
    """The window's device operations and host spans as plain lists, in ns
    after the window opened: what a recorded test trace keeps."""
    w = reduced.window()

    def rel(i: Interval) -> list:
        return [i.name, i.start - w.start, i.end - w.start] + ([i.detail] if i.detail else [])

    return {
        "device_ops": {k: [rel(i) for i in v if i.end > w.start and i.start < w.end]
                       for k, v in reduced.device_ops.items()},
        "host_spans": [rel(i) for i in reduced.host_spans
                       if i.start >= w.start and i.end <= w.end],
    }


def from_json(obj: dict) -> Reduced:
    """The inverse of :func:`to_json`."""
    return Reduced(
        {k: [Interval(*e) for e in v] for k, v in obj["device_ops"].items()},
        [Interval(*e) for e in obj["host_spans"]],
    )


def _detail(event) -> str:
    stats = dict(event.stats)
    return " | ".join(str(stats[k]) for k in DETAIL_STATS if k in stats)


def _merged(ivs: list[Interval], lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the intervals clipped to ``[lo, hi]``, as sorted pieces."""
    out: list[list[float]] = []
    for i in sorted(ivs, key=lambda i: i.start):
        s, e = max(i.start, lo), min(i.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(ivs: list[Interval], lo: float, hi: float) -> float:
    """Length of the union of the intervals inside ``[lo, hi]``."""
    return sum(e - s for s, e in _merged(ivs, lo, hi))


def idle_gaps(ivs: list[Interval], lo: float, hi: float) -> list[tuple[float, float]]:
    """The pieces of ``[lo, hi]`` that no interval covers."""
    gaps, at = [], lo
    for s, e in _merged(ivs, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return gaps


def label_at(spans: list[Interval], t: float, default: str = "other") -> str:
    """Name of the innermost host span that holds time ``t``."""
    best = None
    for s in spans:
        if s.start <= t < s.end and s.name != "window":
            if best is None or s.end - s.start < best.end - best.start:
                best = s
    return default if best is None else best.name


def leaves(ivs: list[Interval]) -> list[Interval]:
    """The intervals that hold no other one: a device ``while`` loop's event
    holds the events of its body, which are kept instead."""
    order = sorted(ivs, key=lambda i: (i.start, -i.end))
    return [i for i, nxt in zip(order, order[1:] + [None])
            if nxt is None or not (nxt.start < i.end and nxt.end <= i.end)]


def short_name(op: Interval) -> str:
    """An operation's name for the breakdown: the HLO instruction's name out
    of its text (``%fusion.16 = ...`` gives ``fusion.16``), with a custom
    call's target."""
    text = op.detail or op.name
    if not (text.startswith("%") and " = " in text):
        return text[:120]
    name = text[1:text.index(" = ")]
    key = 'custom_call_target="'
    if key in text:
        start = text.index(key) + len(key)
        target = text[start:text.index('"', start)]
        name += f" ({target})"
    return name
