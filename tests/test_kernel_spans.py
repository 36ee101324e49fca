"""Phase spans and byte counters of the device solve (``ltsp_solve_batch``).

With a :class:`~repro.obs.KernelProfile` attached, every phase of a launch
opens a ``jax.profiler.TraceAnnotation`` named ``ltsp.*`` and tagged with
the index of the launch's record; each record counts the bytes the launch
moved each way.  Without a profile no annotation is built.  Checked on the
Pallas interpreter at a small shape, under a real profiler session."""

import glob
import os
import warnings

import numpy as np
import pytest

from repro.core import ExecutionContext, make_instance, solve_batch
from repro.kernels.ltsp_dp.ops import bucket_shape, ltsp_solve_batch
from repro.obs import KernelProfile

pytestmark = pytest.mark.obs

#: the spans of one call, in the order they open
CALL_PHASES = ("ltsp.rescale", "ltsp.guard")
LAUNCH_PHASES = ("ltsp.pack", "ltsp.dispatch", "ltsp.device_wait", "ltsp.fetch_argmin",
                 "ltsp.fetch_root", "ltsp.traceback", "ltsp.release")


def _instance(n_req: int, seed: int):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 50, size=n_req)
    gaps = rng.integers(0, 40, size=n_req + 1)
    left, pos = [], int(gaps[0])
    for i in range(n_req):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    mult = rng.integers(1, 8, size=n_req)
    return make_instance(left, sizes, mult, m=pos, u_turn=int(rng.integers(0, 30)))


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; return its result and the ``ltsp.*``
    host events as ``(name, start_ns, end_ns, launch)`` in start order."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    with warnings.catch_warnings():
        # the profiler's binding warns about its own stats type when read
        warnings.simplefilter("ignore", DeprecationWarning)
        events = [
            (e.name, e.start_ns, e.end_ns, dict(e.stats)["launch"])
            for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith("ltsp.")
        ]
    return out, sorted(events, key=lambda e: e[1])


def test_every_launch_opens_each_phase_span_once_in_order(tmp_path):
    small, large = _instance(3, 1), _instance(12, 2)
    assert bucket_shape(small)[0] != bucket_shape(large)[0]  # two launches
    profile = KernelProfile()
    out, events = _traced(tmp_path, lambda: ltsp_solve_batch(
        [small, large], interpret=True, profile=profile))
    assert out == ltsp_solve_batch([small, large], interpret=True)
    assert len(profile.launches) == 2
    assert [e[0] for e in events] == list(CALL_PHASES + LAUNCH_PHASES * 2)
    # each span carries the index of its launch's record; the call's own
    # phases belong to its first launch
    assert [e[3] for e in events] == [0] * (len(CALL_PHASES) + len(LAUNCH_PHASES)) + [
        1] * len(LAUNCH_PHASES)
    # a later call's spans go on counting from the profile's records
    _, events = _traced(tmp_path / "again", lambda: ltsp_solve_batch(
        [small], interpret=True, profile=profile))
    assert {e[3] for e in events} == {2} and len(profile.launches) == 3


def test_the_spans_tile_the_solve(tmp_path):
    inst = _instance(7, 3)
    ltsp_solve_batch([inst], interpret=True)  # compile outside the trace
    _, events = _traced(tmp_path, lambda: ltsp_solve_batch(
        [inst], interpret=True, profile=KernelProfile()))
    covered = sum(end - start for _, start, end, _ in events)
    assert covered >= 0.95 * (events[-1][2] - events[0][1])
    # the spans follow one another and do not nest
    assert all(a[2] <= b[1] for a, b in zip(events, events[1:]))


@pytest.mark.parametrize("capture", [False, True])
def test_the_byte_counters_equal_the_shapes_arithmetic(capture):
    insts = [_instance(5, 4), _instance(6, 5), _instance(7, 6)]
    profile = KernelProfile()
    ltsp_solve_batch(insts, interpret=True, capture=capture, profile=profile)
    [rec] = profile.launches  # one bucket: R 8, S 128 or 256, B 4
    B, R, S = rec.B_pad, rec.R_pad, rec.S_pad
    assert (B, R) == (4, 8)
    # int32 throughout: left, right, x, nl as [B, R] and u as [B] to the
    # device; the device walk's detours [B, R, 2], detour counts, root values
    # and step counts [B] back, and both planes [B, R, R, S] too when captured
    assert rec.h2d_bytes == 4 * (4 * B * R + B)
    assert rec.d2h_bytes == 4 * (2 * B * R + 3 * B + capture * 2 * B * R * R * S)
    # each walk step consumes one file of 1..R-1, by a skip or a detour's
    # start, so every row (the all-phantom one too) walks R - 1 steps
    assert rec.walk_steps == B * (R - 1)
    summary = profile.summary()
    assert (summary["h2d_bytes"], summary["d2h_bytes"], summary["walk_steps"]) == (
        rec.h2d_bytes, rec.d2h_bytes, rec.walk_steps)


def test_without_a_profile_no_span_is_built(monkeypatch):
    import jax

    def refuse(*args, **kwargs):
        raise AssertionError("a TraceAnnotation was built")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    inst = _instance(5, 7)
    ltsp_solve_batch([inst], interpret=True, profile=None)
    [res] = solve_batch([inst], "dp", context=ExecutionContext(backend="pallas-interpret"))
    assert res.cost == ltsp_solve_batch([inst], interpret=True)[0][0]
    with pytest.raises(AssertionError, match="was built"):  # the patch holds
        ltsp_solve_batch([inst], interpret=True, profile=KernelProfile())
