"""The reduction from a profiler trace to metrics: interval arithmetic on
hand-made intervals and events, the loader on a trace recorded here on the
CPU, and the readers on a short window recorded on a TPU v5e."""

import gzip
import json
from pathlib import Path

import pytest

import trace_reduce
from trace_reduce import Interval


def test_union_and_gaps_on_hand_made_intervals():
    ivs = [Interval("a", 10, 20), Interval("b", 15, 30), Interval("c", 40, 50),
           Interval("d", 45, 47), Interval("e", 90, 120)]
    assert trace_reduce.union_ns(ivs, 0, 100) == 10 + 10 + 10 + 10
    assert trace_reduce.idle_gaps(ivs, 0, 100) == [(0, 10), (30, 40), (50, 90)]
    spans = [Interval("window", 0, 100), Interval("solve_batch", 5, 35),
             Interval("verify_schedule", 35, 60)]
    assert trace_reduce.label_at(spans, 33) == "solve_batch"
    assert trace_reduce.label_at(spans, 55) == "verify_schedule"
    assert trace_reduce.label_at(spans, 80) == "other"


def test_the_loader_reads_the_benchmarks_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("solve_batch"):
                    jnp.ones((64, 64)).sum().block_until_ready()
                with jax.profiler.TraceAnnotation("verify_schedule"):
                    pass
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    w = reduced.window()
    assert len(reduced.spans("solve_batch")) == len(reduced.spans("verify_schedule")) == 3
    assert all(w.start <= s.start and s.end <= w.end for s in reduced.host_spans)
    assert reduced.device_ops == {}  # no TPU plane on the CPU


#: the wavefront's device event as a TPU v5e trace names it (shortened)
KERNEL = ('%closed_call.18 = (s32[1,256,1,4096]) custom-call(s32[1] %bitcast.65), '
          'custom_call_target="tpu_custom_call"')


def hand_made_run(with_kernel=True):
    """Two decisions of a 64-file, 999-request tape in a 1 ms window."""
    import harness
    import numpy as np
    import tapes

    ops = [Interval("scatter.1", 100_000, 150_000)]
    if with_kernel:
        ops += [Interval(KERNEL, 150_000, 350_000), Interval(KERNEL, 600_000, 800_000)]
    spans = [Interval("window", 0, 1_000_000),
             Interval("solve_batch", 50_000, 400_000), Interval("verify_schedule", 400_000, 500_000),
             Interval("solve_batch", 550_000, 850_000), Interval("verify_schedule", 850_000, 900_000)]
    mult = np.ones(64, np.int64)
    mult[-1] += 999 - 64
    tape = tapes.Tape(np.zeros(64), np.ones(64), mult, 1, 0)
    decisions = [harness.Decision(k, tape, None, due, due, due + 4e-4)
                 for k, due in enumerate((5e-5, 5.5e-4))]
    run = harness.Run(harness.load_cell("dp.median"), 0.0, decisions, 0.001)
    run.trace = trace_reduce.Reduced({"/device:TPU:0": ops}, spans)
    run.peaks = {"hbm_bytes_per_s": 819e9}
    return run


def test_the_readers_on_hand_made_events():
    import harness
    from roofline_bytes import wavefront_bytes

    run = hand_made_run()
    read = harness.load_reader
    assert read("wavefront_ms.median")(run) == pytest.approx(0.2)  # 400 us over 2
    assert read("solve_other_ms")(run) == pytest.approx((650_000 - 400_000) / 1e6 / 2)
    assert read("verify_ms")(run) == pytest.approx(0.075)
    assert read("device_idle_share")(run) == pytest.approx(100 * (1 - 0.45))
    needed = 2 * wavefront_bytes(64, 999, None)
    assert read("wavefront_roofline.median")(run) == pytest.approx(
        100 * needed / 819e9 / 400e-6)


def test_a_trace_without_the_kernel_leaves_its_metrics_out():
    import harness

    run = hand_made_run(with_kernel=False)
    for name in ("wavefront_ms.median", "wavefront_roofline.median", "solve_other_ms"):
        assert harness.load_reader(name)(run) is None
    assert harness.load_reader("device_idle_share")(run) == pytest.approx(95.0)


def test_a_traced_run_whose_launches_left_no_kernel_event_fails():
    import layers

    run = hand_made_run(with_kernel=False)
    run.launches = ["one launch"]
    with pytest.raises(RuntimeError, match="no device event"):
        layers.require_wavefront(run)
    run = hand_made_run()
    run.launches = ["one launch"]
    layers.require_wavefront(run)


TESTDATA = Path(__file__).resolve().parent / "testdata" / "dp.median.trace.json.gz"


def recorded_run():
    """The readers' input rebuilt from a 2 s ``dp.median`` window that
    ``record_trace.py`` recorded on a TPU v5e (seed 2147483611): two
    decisions, the reduced trace and the launches' cell counts."""
    import types

    import harness
    import numpy as np
    import tapes
    from roofline_bytes import peaks

    rec = json.loads(gzip.decompress(TESTDATA.read_bytes()))
    decisions = []
    for k, (n_req, n, span, due, start, done) in enumerate(rec["decisions"]):
        mult = np.ones(n_req, np.int64)
        mult[-1] += n - n_req  # the readers use only n_req, n and the span
        tape = tapes.Tape(np.arange(n_req), np.ones(n_req, np.int64), mult, n_req, 0)
        decisions.append(harness.Decision(k, tape, span, due, start, done))
    run = harness.Run(harness.load_cell(rec["workload"]), 0.0, decisions, rec["closed_at"])
    run.trace = trace_reduce.from_json(rec["trace"])
    run.launches = [types.SimpleNamespace(real_cells=r, padded_cells=p)
                    for r, p in rec["launches"]]
    run.peaks = peaks(rec["device_kind"])
    return rec, run


def test_the_readers_on_a_window_recorded_on_the_chip():
    import harness
    import layers

    rec, run = recorded_run()
    assert rec["device_kind"] == "TPU v5 lite" and len(run.decisions) == 2
    [ops] = run.trace.device_ops.values()
    kernel = [op for op in ops if layers.is_wavefront(op)]
    # one wavefront program per anti-diagonal after the first, per decision
    assert len(kernel) == len(run.decisions) * 255
    layers.require_wavefront(run)
    read = {m: harness.load_reader(m)(run) for m in
            ("wavefront_ms.median", "wavefront_roofline.median", "solve_other_ms",
             "verify_ms", "device_idle_share", "pad_useful_share")}
    assert all(v is not None for v in read.values()), read
    assert 100 < read["wavefront_ms.median"] < 2000
    assert 0 < read["wavefront_roofline.median"] <= 100
    assert 0 < read["pad_useful_share"] <= 100 and 0 < read["device_idle_share"] < 100
    # kernel, the rest of solve_batch and verify make up the mean decision
    service_ms = sum(d.done - d.start for d in run.decisions) / 2 * 1e3
    parts = read["wavefront_ms.median"] + read["solve_other_ms"] + read["verify_ms"]
    assert parts == pytest.approx(service_ms, rel=0.02)


def test_the_breakdown_of_the_recorded_window_names_its_operations():
    import harness

    _, run = recorded_run()
    busy_s, window_s, breakdown = harness._device_time(run)
    assert 0 < busy_s < window_s
    names = [n for n, _ in breakdown["device_ops"]]
    assert names[0].endswith("(tpu_custom_call)")  # the wavefront leads
    assert not any(n.startswith("while") for n in names)  # the loop gives way to its body
    assert all(len(n) <= 120 for n in names)


def test_leaves_and_short_names():
    outer = Interval("%while.6 = (s32[]) while(s32[] %t), body=%b", 0, 100)
    inner = [Interval('%closed_call.1 = s32[8] custom-call(s32[8] %x), '
                      'custom_call_target="tpu_custom_call", x=1', 10, 40),
             Interval("%fusion.2 = s32[8] fusion(s32[8] %y), kind=kLoop", 50, 60)]
    assert trace_reduce.leaves([outer] + inner) == inner
    assert [trace_reduce.short_name(i) for i in [outer] + inner] == [
        "while.6", "closed_call.1 (tpu_custom_call)", "fusion.2"]
    assert trace_reduce.short_name(Interval("plain", 0, 1)) == "plain"
