"""Observability layer: tracing, exact-int metrics, exporters, no-op pins.

Acceptance bars:

* **no-op pin** — with ``ExecutionContext.obs`` unset (or a
  :class:`~repro.obs.NullTracer` attached) every timeline, journal byte,
  and pinned sha is bit-identical to the pre-observability stack; with a
  live bundle attached the *run* is still bit-identical — hooks only read
  already-computed integers;
* **byte determinism** — two identical seeded 240-request constrained-pool
  runs export byte-identical JSONL span logs and Prometheus snapshots;
* **exact agreement** — scraped counters/histograms reconcile with
  :class:`~repro.serving.sim.ServiceReport` /
  :func:`~repro.serving.qos.slo_report` integers with ``==``, deadline
  accounting included;
* **Chrome export** — one thread lane per drive (plus the queue lane), one
  process per fleet shard, loadable ``trace_event`` JSON;
* the fleet differential pin rides along: an instrumented
  ``replica-affinity`` outage run reproduces the uninstrumented sha while
  its spans cover every shard.
"""

import hashlib
import json

import pytest

from repro.core import ExecutionContext
from repro.obs import (
    KernelProfile,
    MetricsRegistry,
    NullTracer,
    Observability,
    Span,
    Tracer,
    chrome_trace,
    prometheus_text,
    spans_jsonl,
    write_chrome_trace,
    write_prometheus,
    write_spans_jsonl,
)
from repro.serving import (
    DriveCosts,
    RetryPolicy,
    ShardOutage,
    demo_library,
    poisson_trace,
    serve_trace,
)

pytestmark = pytest.mark.obs

SEED = 20260731
COSTS = DriveCosts(mount=150_000, unmount=60_000, load_seek=30_000)

#: the PR-7 no-fault pins (test_faults/test_fleet carry the same table):
#: instrumented runs must reproduce them bit-for-bit.
NO_FAULT_BASELINE = {
    "fifo": ("1a79c55063c3f802", 56_368_550_889),
    "accumulate": ("df9ed258ac816c37", 3_809_190_213),
    "preempt": ("668366586042762a", 7_347_259_813),
}

#: the instrumented fleet outage run must reproduce the uninstrumented one.
FLEET_PIN = ("9c548a4ade5a1de6", 1_016_256_963, 120, 0, 17)


def build_library():
    return demo_library(SEED)


def build_trace(n_requests=240, rate=250_000):
    return poisson_trace(
        build_library(), n_requests=n_requests, mean_interarrival=rate, seed=SEED
    )


def _served_sha(report):
    served = tuple(
        (r.req_id, r.arrival, r.dispatched, r.completed) for r in report.served
    )
    return hashlib.sha256(repr(served).encode()).hexdigest()[:16]


def _timeline(report):
    return [
        (r.req_id, r.arrival, r.dispatched, r.completed, r.faulted)
        for r in report.served
    ] + [(f.req_id, f.failed_at, f.reason) for f in report.failed]


def _pool_run(obs=None, trace=None, n_drives=3, **kw):
    lib = build_library()
    ctx = lib.context if obs is None else lib.context.replace(obs=obs)
    return serve_trace(
        lib, trace if trace is not None else build_trace(), "accumulate",
        window=400_000, policy="dp", n_drives=n_drives, drive_costs=COSTS,
        context=ctx, **kw,
    )


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
def test_tracer_records_in_emission_order():
    tr = Tracer()
    tr.span("batch", 10, 50, track="drive0", tape="T1")
    tr.event("arrival", 30, track="queue", req=7)
    assert len(tr) == 2
    a, b = tr.spans
    assert (a.name, a.t0, a.t1, a.seq, a.track) == ("batch", 10, 50, 0, "drive0")
    assert a.attrs == {"tape": "T1"} and a.duration == 40 and not a.instant
    assert b.instant and b.seq == 1 and b.attrs == {"req": 7}
    assert a.wall_ns is None  # wall clocks are opt-in
    with pytest.raises(ValueError, match="ends before it starts"):
        tr.span("bad", 5, 4)


def test_tracer_wall_stamps_are_opt_in():
    tr = Tracer(wall=True)
    tr.span("s", 0, 1)
    assert isinstance(tr.spans[0].wall_ns, int)


def test_null_tracer_records_nothing():
    tr = NullTracer()
    tr.span("s", 0, 1)
    tr.event("e", 2)
    assert len(tr) == 0 and spans_jsonl(tr) == ""


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("served_total")
    m.inc("served_total", 3, policy="dp")
    m.gauge("depth", 4)
    m.gauge("depth", 2)  # last write wins
    for v in (10, 30, 20):
        m.observe("sojourn", v)
    assert m.counter("served_total") == 1
    assert m.counter("served_total", policy="dp") == 3
    assert m.counter("missing") == 0
    assert m.gauge_value("depth") == 2 and m.gauge_value("nope") is None
    assert m.samples("sojourn") == [10, 30, 20]
    assert m.quantile("sojourn", 1, 2) == 20  # exact nearest-rank median
    assert [v for _, v in m.counters_named("served_total")] == [1, 3]
    assert len(m) == 4


def test_registry_rejects_floats_bools_and_negatives():
    m = MetricsRegistry()
    with pytest.raises(TypeError, match="exact integers"):
        m.inc("c", 1.5)
    with pytest.raises(TypeError, match="exact integers"):
        m.observe("h", True)
    with pytest.raises(TypeError, match="exact integers"):
        m.gauge("g", 0.0)
    with pytest.raises(ValueError, match="cannot decrease"):
        m.inc("c", -1)


def test_snapshot_and_prometheus_are_deterministic():
    def build():
        m = MetricsRegistry()
        m.inc("b_total", 2, policy="dp")
        m.inc("a_total")
        m.gauge("g", 7, shard="0")
        m.observe("h", 5)
        m.observe("h", 9)
        return m

    a, b = build(), build()
    assert a.snapshot() == b.snapshot()
    assert prometheus_text(a) == prometheus_text(b)
    snap = a.snapshot()
    assert snap["counters"] == {"a_total": 1, 'b_total{policy="dp"}': 2}
    assert snap["histograms"]["h"]["sum"] == 14
    assert snap["histograms"]["h"]["count"] == 2
    text = prometheus_text(a)
    assert "# TYPE a_total counter" in text
    assert 'g{shard="0"} 7' in text
    assert 'h{quantile="0.5"} 5' in text and "h_sum 14" in text


# ---------------------------------------------------------------------------
# bundle + context plumbing
# ---------------------------------------------------------------------------
def test_empty_bundle_recorders_are_noop_safe():
    obs = Observability()  # all None
    obs.span("s", 0, 1)
    obs.event("e", 2)
    obs.inc("c")
    obs.gauge("g", 1)
    obs.observe("h", 1)
    armed = Observability.enabled()
    assert armed.tracer is not None and armed.metrics is not None
    assert armed.kernel is not None
    armed.inc("c", 2)
    assert armed.metrics.counter("c") == 2


def test_context_validates_obs_field():
    assert ExecutionContext().obs is None
    ctx = ExecutionContext(obs=Observability.enabled())
    assert ctx.obs.tracer is not None
    assert ctx.replace(obs=None).obs is None
    with pytest.raises(TypeError, match="obs"):
        ExecutionContext(obs=42)


# ---------------------------------------------------------------------------
# no-op pins: instrumentation never changes a run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("admission", sorted(NO_FAULT_BASELINE))
def test_instrumented_runs_reproduce_pins(admission):
    sha, total = NO_FAULT_BASELINE[admission]
    trace = build_trace()

    def run(obs):
        lib = build_library()
        ctx = lib.context if obs is None else lib.context.replace(obs=obs)
        return serve_trace(
            lib, trace, admission, window=400_000, policy="dp", n_drives=2,
            drive_costs=COSTS, context=ctx,
        )

    bare = run(None)
    assert (_served_sha(bare), bare.total_sojourn) == (sha, total)
    for obs in (Observability.enabled(),
                Observability(tracer=NullTracer())):
        instrumented = run(obs)
        assert (_served_sha(instrumented), instrumented.total_sojourn) == (
            sha, total,
        )
        assert _timeline(instrumented) == _timeline(bare)
        assert instrumented.summary() == bare.summary()


def test_journal_bytes_identical_with_obs(tmp_path):
    trace = build_trace(60)
    bare = tmp_path / "bare.journal"
    _pool_run(trace=trace, journal=str(bare))
    inst = tmp_path / "inst.journal"
    _pool_run(Observability.enabled(), trace=trace, journal=str(inst))
    assert inst.read_bytes() == bare.read_bytes()


# ---------------------------------------------------------------------------
# acceptance: byte-deterministic exports on the seeded 240-request run
# ---------------------------------------------------------------------------
def test_span_log_is_byte_deterministic(tmp_path):
    runs = []
    for _ in range(2):
        obs = Observability.enabled()
        _pool_run(obs)
        runs.append(obs)
    assert spans_jsonl(runs[0].tracer) == spans_jsonl(runs[1].tracer)
    assert prometheus_text(runs[0].metrics) == prometheus_text(runs[1].metrics)
    assert len(runs[0].tracer) > 0
    # the file exporters round-trip the same bytes
    p = tmp_path / "spans.jsonl"
    n = write_spans_jsonl(runs[0].tracer, p)
    assert n == len(runs[0].tracer)
    assert p.read_text() == spans_jsonl(runs[0].tracer)
    for line in p.read_text().splitlines():
        row = json.loads(line)
        assert list(row) == sorted(row)  # sorted keys, byte-stable
    write_prometheus(runs[0].metrics, tmp_path / "m.prom")
    assert (tmp_path / "m.prom").read_text() == prometheus_text(runs[0].metrics)


def test_prometheus_counters_match_report_exactly():
    from repro.data.traces import qos_poisson_trace, to_requests
    from repro.serving.qos import int_quantile, slo_report

    records = qos_poisson_trace(
        build_library(), n_requests=240, mean_interarrival=250_000,
        seed=SEED, tightness=8_000_000,
    )
    qtrace, qos = to_requests(records, build_library())
    obs = Observability.enabled()
    lib = build_library()
    report = serve_trace(
        lib, qtrace, "slack-accumulate", window=400_000, policy="dp",
        n_drives=3, drive_costs=COSTS, qos=qos,
        context=lib.context.replace(obs=obs),
    )
    s = report.summary()
    m = obs.metrics
    assert m.counter("requests_arrived_total") == len(qtrace)
    assert m.counter("requests_served_total") == report.n_served
    assert m.counter("batches_total") == s["n_batches"]
    assert m.counter("cells_evaluated_total") == s["cells_evaluated"]
    assert m.counter("cells_reused_total") == s["cells_reused"]
    assert m.counter("mount_delay_total") == s["mount_time"]
    assert m.counter("cache_hits_total", cache="SolveCache") == s["cache"]["hits"]
    assert m.counter("cache_misses_total", cache="SolveCache") == s["cache"]["misses"]
    # deadline accounting: same integers the report and SLO summary carry
    assert m.counter("deadlines_total") == report.n_deadlines == s["n_deadlines"]
    assert m.counter("deadline_misses_total") == report.n_missed == s["n_missed"]
    # the sojourn histogram IS the report's distribution
    sojourns = m.samples("sojourn")
    assert len(sojourns) == report.n_served
    assert sum(sojourns) == report.total_sojourn
    # recorded in event order; the report re-sorts rows — same multiset
    assert sorted(sojourns) == sorted(r.sojourn for r in report.served)
    # scraped quantiles == the SLO report's exact nearest-rank quantiles
    slo = slo_report(report)
    assert m.quantile("sojourn", 1, 2) == slo.overall.p50_sojourn
    assert m.quantile("sojourn", 99, 100) == slo.overall.p99_sojourn
    assert m.quantile("sojourn", 99, 100) == int_quantile(sojourns, 99, 100)
    assert slo.overall.n_missed == m.counter("deadline_misses_total")


def test_chrome_trace_has_one_lane_per_drive():
    obs = Observability.enabled()
    _pool_run(obs)
    doc = chrome_trace(obs.tracer)
    events = doc["traceEvents"]
    threads = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"drive0", "drive1", "drive2", "queue"} <= threads
    procs = {
        e["args"]["name"] for e in events
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert procs == {"shard0"}  # standalone run: one process
    batches = [e for e in events if e["ph"] == "X" and e["name"] == "batch"]
    assert batches and all(e["dur"] > 0 for e in batches)
    assert any(e["ph"] == "i" for e in events)  # instants export too


def test_chrome_trace_round_trips_as_json(tmp_path):
    obs = Observability.enabled()
    _pool_run(obs, trace=build_trace(40))
    p = tmp_path / "trace.chrome.json"
    write_chrome_trace(obs.tracer, p)
    doc = json.loads(p.read_text())
    assert doc == chrome_trace(obs.tracer)


# ---------------------------------------------------------------------------
# fleet: differential pin + per-shard spans
# ---------------------------------------------------------------------------
def _fleet_run(obs=None):
    from repro.core import FleetOptions
    from repro.fleet import demo_fleet, fleet_catalog, serve_fleet_trace

    libs, rmap = demo_fleet(SEED, n_shards=3, replicas=2)
    trace = poisson_trace(
        fleet_catalog(libs, rmap), n_requests=120, mean_interarrival=30_000,
        seed=SEED,
    )
    libs, rmap = demo_fleet(SEED, n_shards=3, replicas=2)
    ctx = ExecutionContext(
        fleet=FleetOptions(n_shards=3, placement="replica-affinity", replicas=2),
        obs=obs,
    )
    return serve_fleet_trace(
        libs, trace, "accumulate", replica_map=rmap,
        outages=(ShardOutage(at=1_500_000, shard=1),), window=400_000,
        n_drives=2, drive_costs=COSTS, retry=RetryPolicy(on_exhausted="drop"),
        context=ctx,
    )


def test_fleet_instrumented_run_reproduces_pin():
    sha, total, n_served, n_failed, n_rerouted = FLEET_PIN
    bare = _fleet_run()
    assert (_served_sha(bare.merged), bare.total_sojourn) == (sha, total)
    obs = Observability.enabled()
    fr = _fleet_run(obs)
    assert (_served_sha(fr.merged), fr.total_sojourn) == (sha, total)
    assert (fr.n_served, fr.n_failed, fr.n_rerouted) == (
        n_served, n_failed, n_rerouted,
    )
    assert _timeline(fr.merged) == _timeline(bare.merged)
    m = obs.metrics
    # routing counters reconcile with the report's routes, exactly
    routed = sum(v for _, v in m.counters_named("fleet_routed_total"))
    rerouted = sum(v for _, v in m.counters_named("fleet_rerouted_total"))
    assert routed == fr.n_served + fr.n_failed  # every arrival routed once
    assert routed + rerouted == sum(fr.routes.values())
    assert rerouted == fr.n_rerouted
    assert m.counter("fleet_outages_total") == 1
    # per-shard rollup gauges match the per-shard reports
    for i, shard in enumerate(fr.shards):
        assert m.gauge_value("shard_served", shard=str(i)) == shard.n_served
    # spans cover every shard; each shard's drives get their own lanes
    shards_seen = {sp.shard for sp in obs.tracer.spans}
    assert shards_seen == {0, 1, 2}
    doc = chrome_trace(obs.tracer)
    procs = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert procs == {"shard0", "shard1", "shard2"}
    tracks = {sp.track for sp in obs.tracer.spans}
    assert {"drive0", "drive1", "queue", "router"} <= tracks


# ---------------------------------------------------------------------------
# kernel profiling
# ---------------------------------------------------------------------------
def test_kernel_profile_cold_vs_warm_and_waste():
    prof = KernelProfile(wall=False)
    sig = (4, 8, 2, "int32", True, 0, False, None)
    prof.record(signature=sig, n_instances=2, R_pad=4, S_pad=8, B_pad=2,
                real_cells=100, interpret=True, h2d_bytes=136, d2h_bytes=1064)
    prof.record(signature=sig, n_instances=1, R_pad=4, S_pad=8, B_pad=2,
                real_cells=40, interpret=True, h2d_bytes=136, d2h_bytes=1064)
    first, second = prof.launches
    assert first.cold and not second.cold  # same signature: compiled once
    assert first.padded_cells == 2 * 4 * 4 * 8 == 256
    assert first.waste == (156, 256)  # exact fraction, no floats
    assert (first.h2d_bytes, first.d2h_bytes) == (136, 1064)
    s = prof.summary()
    assert s["n_launches"] == 2 and s["n_cold"] == 1
    assert s["real_cells"] == 140 and s["padded_cells"] == 512
    assert s["wasted_cells"] == 512 - 140
    assert (s["h2d_bytes"], s["d2h_bytes"]) == (2 * 136, 2 * 1064)


def test_kernel_profile_captures_device_launches():
    obs = Observability.enabled()
    lib = build_library()
    report = serve_trace(
        lib, build_trace(40), "batched", window=400_000, policy="dp",
        n_drives=2, drive_costs=COSTS,
        context=lib.context.replace(backend="pallas-interpret", obs=obs),
    )
    assert report.n_served == 40
    prof = obs.kernel
    assert len(prof.launches) > 0
    for rec in prof.launches:
        assert rec.padded_cells >= rec.real_cells > 0
        wasted, padded = rec.waste  # exact fraction (wasted, padded)
        assert 0 <= wasted < padded
        assert rec.interpret
        # int32 packed arrays in; the device walk's detours, detour counts,
        # roots and step counts out (and both planes, the int32 T and the
        # int16 C, when a warm start captures them)
        B, R = rec.B_pad, rec.R_pad
        assert rec.h2d_bytes == 4 * (4 * B * R + B)
        assert rec.d2h_bytes - 4 * (2 * B * R + 3 * B) in (0, 6 * rec.padded_cells)
        assert rec.walk_steps == B * (R - 1)
    assert prof.summary()["n_instances"] >= len(prof.launches)
    # a cold launch (first of its bucket signature) pays compilation; re-use
    # of the same bucket is marked warm
    assert any(rec.cold for rec in prof.launches)
