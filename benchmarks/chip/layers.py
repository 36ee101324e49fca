"""Per-layer arithmetic shared by the metric readers in ``metrics/``.

Every time here is read from the profiler trace of a ``--trace 1`` run, on
its one clock.  On a TPU v5e the device events carry the HLO instruction's
text as their name and no framework name, so the wavefront kernel is found
as the Mosaic custom call (:data:`WAVEFRONT_OP`): the decision path runs no
other Pallas kernel.  A traced run whose window launched the wavefront but
whose trace shows no such event fails (:func:`require_wavefront`).
"""

from __future__ import annotations

from roofline_bytes import wavefront_bytes
from trace_reduce import Interval, union_ns

#: parts of the Pallas wavefront's device event in the trace: a TPU kernel
#: compiled by Mosaic is a custom call with this target
WAVEFRONT_OP = ('custom_call_target="tpu_custom_call"',)


def is_wavefront(op: Interval) -> bool:
    text = f"{op.name} {op.detail}"
    return all(part in text for part in WAVEFRONT_OP)


def _window_ops(run) -> list[list[Interval]]:
    w = run.trace.window()
    return [
        [op for op in ops if op.end > w.start and op.start < w.end]
        for ops in run.trace.device_ops.values()
    ]


def wavefront_ns(run) -> float | None:
    """Device nanoseconds of the wavefront kernel in the window, averaged
    over the chips, or ``None`` where the trace holds no such event."""
    if run.trace is None:
        return None
    chips = [[op for op in ops if is_wavefront(op)] for ops in _window_ops(run)]
    if not any(chips):
        return None
    return sum(op.end - op.start for ops in chips for op in ops) / len(chips)


def require_wavefront(run) -> None:
    """Raise where the program launched the wavefront in the window but the
    trace shows no event of it: the kernel's name changed, and the metrics
    that read it would drop out unseen."""
    if run.launches and wavefront_ns(run) is None:
        raise RuntimeError(
            f"{len(run.launches)} wavefront launches in the window, but no "
            f"device event matches {WAVEFRONT_OP} in the trace")


def wavefront_ms(run) -> float | None:
    """Wavefront device time per decision, in ms."""
    ns = wavefront_ns(run)
    started = [d for d in run.decisions if d.start is not None]
    if ns is None or not started:
        return None
    return ns / 1e6 / len(started)


def wavefront_roofline(run) -> float | None:
    """Share, in %, of the kernel's device time that the recurrence's bytes
    (``roofline_bytes.wavefront_bytes``) would take at the HBM peak."""
    ns = wavefront_ns(run)
    if ns is None:
        return None
    needed = sum(
        wavefront_bytes(d.tape.n_req, d.tape.n, d.span)
        for d in run.decisions
        if d.start is not None
    )
    return 100.0 * needed / run.peaks["hbm_bytes_per_s"] / (ns / 1e9)


def span_ns(run, name: str) -> list[float]:
    """Durations of the benchmark's host spans of one name in the window."""
    if run.trace is None:
        return []
    w = run.trace.window()
    return [s.end - s.start for s in run.trace.spans(name)
            if s.start >= w.start and s.end <= w.end]


def busy_ns(run) -> float:
    """Union of device operations in the window, averaged over the chips."""
    w = run.trace.window()
    chips = list(run.trace.device_ops.values())
    return sum(union_ns(ops, w.start, w.end) for ops in chips) / max(1, len(chips))
