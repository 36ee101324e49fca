"""The readers of the program's ``ltsp.*`` spans and byte counters: on
hand-made events, on a trace of the program recorded here on the CPU, and on
a short window recorded on a TPU v5e; and the older readers, which must read
the window recorded before the spans existed exactly as they did."""

import gzip
import json
import types
from pathlib import Path

import pytest

import harness
import program_spans
import trace_reduce
from program_spans import Span
from trace_reduce import Interval

NEW = ("pack_ms", "argmin_fetch_ms", "argmin_fetch_gbps", "traceback_ms",
       "idle_unattributed_share")
OLD = ("wavefront_ms.median", "wavefront_roofline.median", "solve_other_ms", "verify_ms",
       "device_idle_share", "pad_useful_share")
TESTDATA = Path(__file__).resolve().parent / "testdata"


def read(name, run):
    return harness.load_reader(name)(run)


def hand_made_run(with_spans=True):
    """One decision in a 1 ms window: the program's spans tile its
    ``solve_batch`` up to 0.78 ms; the device runs the kernel while the host
    waits for it, and a slice while the host reads the root."""
    import numpy as np
    import tapes

    ops = [Interval("kernel", 55_000, 300_000), Interval("slice", 605_000, 610_000)]
    spans = [Interval("window", 0, 1_000_000), Interval("solve_batch", 0, 800_000),
             Interval("verify_schedule", 800_000, 900_000)]
    tape = tapes.Tape(np.zeros(8), np.ones(8), np.ones(8, np.int64), 1, 0)
    run = harness.Run(harness.load_cell("dp.median"), 0.0,
                      [harness.Decision(0, tape, None, 0.0, 0.0, 9e-4)], 0.001)
    run.trace = trace_reduce.Reduced({"/device:TPU:0": ops}, spans)
    run.launches = [types.SimpleNamespace(real_cells=1, padded_cells=2, d2h_bytes=600_000)]
    edges = [("ltsp.rescale", 0, 10_000), ("ltsp.guard", 10_000, 20_000),
             ("ltsp.pack", 20_000, 50_000), ("ltsp.dispatch", 50_000, 60_000),
             ("ltsp.device_wait", 60_000, 300_000), ("ltsp.fetch_argmin", 300_000, 600_000),
             ("ltsp.fetch_root", 600_000, 620_000), ("ltsp.traceback", 620_000, 650_000),
             ("ltsp.release", 650_000, 780_000)]
    run.program_spans = [Span(n, s, e, 3) for n, s, e in edges] if with_spans else []
    return run


def test_the_readers_on_hand_made_events():
    run = hand_made_run()
    assert read("pack_ms", run) == pytest.approx(0.05)
    assert read("argmin_fetch_ms", run) == pytest.approx(0.3)
    assert read("traceback_ms", run) == pytest.approx(0.18)
    assert read("argmin_fetch_gbps", run) == pytest.approx(2.0)  # 600 kB in 0.3 ms
    # the device idles 55 + 305 + 390 us; the host is in no named span for
    # 20 us after the release and 100 us after verify_schedule
    assert read("idle_unattributed_share", run) == pytest.approx(100 * 120 / 750)


def test_a_run_without_the_programs_spans_or_counters_leaves_them_out():
    """The readers against a program that opens no ``ltsp.*`` span and
    counts no bytes, and against a run without a trace: nothing, no error."""
    for name in NEW:
        assert read(name, hand_made_run(with_spans=False)) is None
    run = hand_made_run()
    run.launches = [types.SimpleNamespace(real_cells=1, padded_cells=2)]
    assert read("argmin_fetch_gbps", run) is None
    run.trace = None
    for name in NEW:
        assert read(name, run) is None


def test_overlap_of_sorted_pieces():
    assert program_spans.overlap_ns([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == 5 + 5 + 2
    assert program_spans.overlap_ns([], [(0, 1)]) == 0


def test_the_loader_reads_the_programs_spans(tmp_path):
    """The program on the Pallas interpreter under a real profiler session:
    every phase of a launch, in order, each with its launch's index."""
    import jax
    import numpy as np

    from repro.core import make_instance
    from repro.kernels.ltsp_dp.ops import ltsp_solve_batch
    from repro.obs import KernelProfile

    inst = make_instance([0, 10, 30, 45], [5, 8, 3, 9], np.array([1, 3, 2, 1]), m=60,
                         u_turn=4)
    profile = KernelProfile()
    ltsp_solve_batch([inst], interpret=True, profile=profile)  # launch 0, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        ltsp_solve_batch([inst], interpret=True, profile=profile)
    finally:
        jax.profiler.stop_trace()
    spans = program_spans.load(trace_reduce.find_xplane(str(tmp_path)))
    assert [s.name for s in spans] == [
        "ltsp.rescale", "ltsp.guard", "ltsp.pack", "ltsp.dispatch", "ltsp.device_wait",
        "ltsp.fetch_argmin", "ltsp.fetch_root", "ltsp.traceback", "ltsp.release"]
    assert {s.launch for s in spans} == {1}


def recorded_run(name):
    """A window recorded on a TPU v5e (``record_trace.py`` or
    ``record_spans.py``), rebuilt as the readers' input."""
    import numpy as np
    import tapes
    from roofline_bytes import peaks

    rec = json.loads(gzip.decompress((TESTDATA / name).read_bytes()))
    decisions = []
    for k, (n_req, n, span, due, start, done) in enumerate(rec["decisions"]):
        mult = np.ones(n_req, np.int64)
        mult[-1] += n - n_req  # the readers use only n_req, n and the span
        tape = tapes.Tape(np.arange(n_req), np.ones(n_req, np.int64), mult, n_req, 0)
        decisions.append(harness.Decision(k, tape, span, due, start, done))
    run = harness.Run(harness.load_cell(rec["workload"]), 0.0, decisions, rec["closed_at"])
    run.trace = trace_reduce.from_json(rec["trace"])
    keys = ("real_cells", "padded_cells", "h2d_bytes", "d2h_bytes")
    run.launches = [types.SimpleNamespace(**dict(zip(keys, r))) for r in rec["launches"]]
    run.peaks = peaks(rec["device_kind"])
    if "program_spans" in rec:
        run.program_spans = program_spans.from_json(rec["program_spans"])
    return rec, run


def test_the_older_readers_read_the_window_recorded_before_the_spans_as_before():
    """``dp.median.trace.json.gz`` (seed 2147483611) holds no ``ltsp.*`` span:
    the older readers give exactly the values they gave on it, and the new
    ones nothing."""
    _, run = recorded_run("dp.median.trace.json.gz")
    assert {m: read(m, run) for m in OLD} == {
        "wavefront_ms.median": 544.7087785,
        "wavefront_roofline.median": 2.9549753769628815,
        "solve_other_ms": 528.3107505,
        "verify_ms": 1.592715,
        "device_idle_share": 41.119628651061866,
        "pad_useful_share": 24.577409960329533,
    }
    run.program_spans = []  # the harness's look into its trace directory finds none
    assert all(read(m, run) is None for m in NEW)


def test_the_readers_on_a_window_with_the_programs_spans_recorded_on_the_chip():
    """``dp.median.spans.trace.json.gz``: a 2 s ``dp.median`` window that
    ``record_spans.py`` recorded on a TPU v5e (seed 2147489301)."""
    rec, run = recorded_run("dp.median.spans.trace.json.gz")
    assert rec["device_kind"] == "TPU v5 lite" and len(run.decisions) == 3
    values = {m: read(m, run) for m in NEW + OLD}
    assert all(v is not None for v in values.values()), values
    # each decision's solve_batch is tiled by the spans of its one launch
    decisions = run.trace.spans("solve_batch")
    for k, sb in enumerate(decisions):
        inside = [s for s in run.program_spans if sb.start <= s.start and s.end <= sb.end]
        assert {s.launch for s in inside} == {k + 1}  # launch 0 warmed up the shape
        assert sum(s.end - s.start for s in inside) == pytest.approx(sb.end - sb.start, rel=0.02)
    assert values["idle_unattributed_share"] <= 5
    # the copy of the 1 GiB argmin plane, the release and packing name the idle chip
    idle_ms = values["device_idle_share"] / 100 * (run.closed_at * 1e3) / len(decisions)
    named = values["argmin_fetch_ms"] + values["traceback_ms"] + values["pack_ms"]
    assert named == pytest.approx(idle_ms, rel=0.05)
    assert [r.d2h_bytes for r in run.launches] == [4 * (256 * 256 * 4096 + 1 + 256)] * 3
