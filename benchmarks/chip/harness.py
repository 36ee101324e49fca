"""One cell of the chip benchmark: set-up, the measured window, the check.

``run.py`` parses the command line and calls :func:`main`.  A cell is an
entry of ``BENCHMARK.json``'s ``workloads``: a configuration file
(``configs/<name>.json``) and a traffic file (``traffic/<name>.json``), found
by name.  Every metric is a reader of its own, ``metrics/<name>.py``, whose
``read(run)`` takes the finished :class:`Run` and returns a number, or
``None`` where it finds nothing to read.

A decision is what a recall job waits for: one cartridge's batch goes
through ``repro.core.solve_batch([inst], policy, context)`` on the
``"pallas"`` backend (solver registry, bucketed launch, wavefront, copy of
the argmin plane, traceback), and then through
``repro.core.verify.verify_schedule``.  It is complete when its verified
``(cost, detours)`` is on the host.

Set-up draws the cell's tapes from the seed, compiles and runs each of their
launch shapes once on a tape of the same shape that the window does not
send, and then the window runs for ``--seconds``.  After it, a sample of the
window's decisions, drawn from the seed and holding the largest, is solved
again by the plain reference (``ltsp_reference.py``, NumPy on the host, in
worker processes) and compared exactly.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import importlib.util
import json
import multiprocessing
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

import layers
import ltsp_reference
import tapes
import trace_reduce
from roofline_bytes import peaks

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: fixed paths inside the checkout: the compile cache keys on its path
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: how long after the window a sampled decision may still be answered
GRACE_S = 60.0
#: a run prints the tracebacks of at most this many failed decisions
MAX_TRACEBACKS = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    run_seconds: float
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if not cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    [cell] = cells
    return make_cell(bench, workload, cell["config"], cell["traffic"], cell["chips"], root)


def make_cell(bench: dict, name: str, config: str, traffic: str, chips: int = 1,
              root: Path = ROOT) -> Cell:
    """A cell of configuration ``config`` under traffic ``traffic``, found by
    name; it need not be an entry of ``bench["workloads"]``."""
    [conf] = [c for c in bench["configs"] if c["name"] == config]
    cfg = json.loads((root / conf["file"]).read_text())
    if cfg["cartridges_per_call"] != 1:
        # the program pads a bucket's batch to a power of two with no memory
        # check, so the harness sends one cartridge per solve_batch call
        raise SystemExit(f"{config}: cartridges_per_call must be 1")

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name,
        chips,
        bench["run_seconds"],
        cfg,
        json.loads((HERE / "traffic" / f"{traffic}.json").read_text()),
        [m for m in bench["end_to_end"] if applies(m)],
        [m for m in bench["per_layer"] if applies(m)],
    )


@dataclasses.dataclass
class Decision:
    """One cartridge's decision; times in seconds after the window opened."""

    index: int
    tape: tapes.Tape
    span: int | None
    due: float
    start: float | None = None
    done: float | None = None
    cost: int | None = None
    detours: list | None = None
    error: str | None = None

    @property
    def size(self) -> int:
        return self.tape.n_req**2 * (self.tape.n + 1)


@dataclasses.dataclass
class Run:
    """What a metric reader sees of a finished run."""

    cell: Cell
    setup_s: float
    decisions: list[Decision]  # every decision of the window
    closed_at: float  # window length on the host clock
    trace: trace_reduce.Reduced | None = None
    launches: list = dataclasses.field(default_factory=list)
    peaks: dict | None = None

    def latencies_s(self) -> list[float]:
        """Due to verified schedule; one still waiting at the close enters
        with the time it has waited so far."""
        return [
            (d.done if d.done is not None else self.closed_at) - d.due
            for d in self.decisions
        ]

    def completed(self) -> list[Decision]:
        return [d for d in self.decisions if d.done is not None and d.error is None]


class CompileLog:
    """Counts backend compiles and persistent-cache hits by phase."""

    def __init__(self):
        self.phase = "setup"
        self.counts: collections.Counter = collections.Counter()

    def on_duration(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE:
            self.counts[self.phase, "compiles"] += 1
            self.counts[self.phase, "compile_s"] += duration

    def on_event(self, event: str, **_):
        if event == CACHE_HIT:
            self.counts[self.phase, "cache_hits"] += 1
        elif event == CACHE_MISS:
            self.counts[self.phase, "cache_misses"] += 1


def load_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _sample(decisions: list[Decision], check: dict, seed: int) -> list[Decision]:
    """Decisions to compare: the largest, and others drawn from the seed,
    one per distinct tape."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    seen, pool = set(), []
    for d in sorted(decisions, key=lambda d: (-d.size, d.index)):
        if id(d.tape) not in seen:
            seen.add(id(d.tape))
            pool.append(d)
    picked = pool[: check["largest"]]
    rest = pool[check["largest"]:]
    k = min(check["random"], len(rest))
    picked += [rest[i] for i in sorted(rng.choice(len(rest), size=k, replace=False))]
    return picked


def _references(jobs: list[tuple], workers: int) -> list[tuple]:
    """Reference ``(cost, detours)`` for each job, in worker processes that
    import only NumPy (``workers=0``: in this process)."""
    if workers == 0:
        return [ltsp_reference.solve(*j) for j in jobs]
    ctx = multiprocessing.get_context("spawn")
    with concurrent.futures.ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        futures = [pool.submit(ltsp_reference.solve, *j) for j in jobs]
        return [f.result() for f in futures]


def _wait_until(clock, t: float) -> None:
    """Sleep until ``clock() >= t``, spinning for the last half millisecond."""
    while True:
        left = t - clock()
        if left <= 0:
            return
        if left > 1e-3:
            time.sleep(left - 5e-4)


class Session:
    """A cell's set-up and window: the tapes drawn from the seed, the
    program's context, and every launch shape of those tapes compiled and run
    once on a tape the window does not send."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 *, backend: str = "pallas"):
        import jax

        import repro.serving.sim  # noqa: F401 - verify_schedule's replay, imported in set-up
        from repro.core import ExecutionContext, make_instance, solve_batch
        from repro.core.verify import verify_schedule
        from repro.obs import KernelProfile, Observability

        self.cell = cell
        self._solve_batch, self._verify = solve_batch, verify_schedule
        self._annotate = (jax.profiler.TraceAnnotation if trace
                          else (lambda _: contextlib.nullcontext()))
        self.plan = tapes.plan(cell.config, cell.traffic, seed, seconds)
        self._insts = {
            id(t): make_instance(t.left, t.size, t.mult, t.m, t.u_turn)
            for t in self.plan.tapes
        }
        self.profile = KernelProfile(wall=False) if trace else None
        self._ctx = ExecutionContext(
            backend=backend,
            obs=Observability(kernel=self.profile) if trace else None,
        )
        self._n_tracebacks = 0
        warm: dict = {}
        for t in self.plan.tapes:
            warm.setdefault((tapes.bucket(t), self.span_of(t)), t)
        for t in warm.values():
            # same files and request count, multiplicities reversed: the same
            # launch shape on a tape the window does not send
            inst = make_instance(t.left, t.size, t.mult[::-1], t.m, t.u_turn)
            solve_batch([inst], cell.config["policy"], context=self._ctx)
        self.shapes = sorted(warm, key=str)
        self.n_setup_launches = len(self.profile.launches) if trace else 0

    def span_of(self, t: tapes.Tape) -> int | None:
        return ltsp_reference.span_limit(self.cell.config["span_rule"], t.n_req)

    def decide(self, d: Decision, t0: float) -> None:
        """One decision through the program; times relative to ``t0``."""
        clock, annotate = time.perf_counter, self._annotate
        inst = self._insts[id(d.tape)]
        d.start = clock() - t0
        try:
            with annotate("solve_batch"):
                [res] = self._solve_batch([inst], self.cell.config["policy"],
                                          context=self._ctx)
            d.cost, d.detours = res.cost, list(res.detours)
            with annotate("verify_schedule"):
                self._verify(inst, res.detours, res.cost)
        except Exception as err:  # noqa: BLE001 - a failed decision is counted; the run goes on
            d.error = f"{type(err).__name__}: {err}"
            if self._n_tracebacks < MAX_TRACEBACKS:
                self._n_tracebacks += 1
                traceback.print_exc(file=sys.stderr)
        d.done = clock() - t0

    def window(self, seconds: float) -> tuple[list[Decision], float, list[float], float]:
        """Run the window: ``(decisions, closed_at, lateness, t0)``.  A closed
        loop starts decisions until ``seconds`` have passed; an open loop
        serves the cartridges due before ``seconds`` in order, and starts none
        after it.  ``lateness`` holds, for each arrival that found the caller
        idle, how late the caller took it up."""
        clock, plan = time.perf_counter, self.plan
        decisions: list[Decision] = []
        lateness: list[float] = []
        t0 = clock()
        with self._annotate("window"):
            if plan.due_s is None:  # closed loop: back to back
                k = 0
                while clock() - t0 < seconds:
                    t = plan.tapes[k % len(plan.tapes)]
                    d = Decision(k, t, self.span_of(t), clock() - t0)
                    decisions.append(d)
                    self.decide(d, t0)
                    k += 1
            else:  # open loop: due times fixed in set-up, served FIFO
                decisions = [
                    Decision(k, t, self.span_of(t), due)
                    for k, (t, due) in enumerate(zip(plan.tapes, plan.due_s))
                    if due < seconds
                ]
                for d in decisions:
                    if clock() - t0 >= seconds:
                        break
                    if clock() - t0 < d.due:
                        with self._annotate("wait_arrival"):
                            _wait_until(clock, t0 + d.due)
                        lateness.append(clock() - t0 - d.due)
                    self.decide(d, t0)
        return decisions, clock() - t0, lateness, t0


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_process: float,
    *,
    backend: str = "pallas",
    workers: int | None = None,
    out=sys.stdout,
) -> dict:
    """Run one cell and return the result line's object (``checks`` last)."""
    import jax

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    try:
        session = Session(cell, seed, seconds, trace, backend=backend)
        setup_s = time.perf_counter() - t_process
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        log.phase = "window"
        decisions, closed_at, lateness, t0 = session.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        log.phase = "after"
        devices = jax.devices()[: cell.chips]
        stats = [dev.memory_stats() or {} for dev in devices]
        memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

        # ---------------- metrics -----------------------------------------
        run = Run(cell, setup_s, decisions, closed_at)
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        }
        breakdown = None
        if trace:
            run.trace = trace_reduce.load(trace_reduce.find_xplane(str(TRACE_DIR)))
            run.launches = session.profile.launches[session.n_setup_launches:]
            run.peaks = peaks(devices[0].device_kind)
            layers.require_wavefront(run)
            device["busy_s"], device["window_s"], breakdown = _device_time(run)
        metrics = {}
        for m in cell.per_layer if trace else cell.end_to_end:
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        # ---------------- the check ---------------------------------------
        sample = _sample(decisions, cell.traffic["check"], seed)
        t_grace = time.perf_counter()
        for d in sample:
            if d.done is None and time.perf_counter() - t_grace < GRACE_S:
                session.decide(d, t0)  # due in the window, answered after it
        jobs = [
            (d.tape.left, d.tape.right, d.tape.mult, d.tape.m, d.tape.u_turn, d.span)
            for d in sample
        ]
        if workers is None:
            workers = min(len(jobs), max(1, (os.cpu_count() or 2) // 2), 8)
        refs = _references(jobs, workers)
        answered = [(d, r) for d, r in zip(sample, refs) if d.cost is not None]
        failed = sum(d.error is not None for d in decisions)
        checks = {
            "empty_sample": {"value": int(not sample), "limit": 0},
            "unanswered": {"value": len(sample) - len(answered), "limit": 0},
            "failed_decisions": {"value": failed, "limit": 0},
            "cost_mismatches": {
                "value": sum(d.cost != r[0] for d, r in answered), "limit": 0},
            "detour_mismatches": {
                "value": sum(sorted(d.detours) != r[1] for d, r in answered), "limit": 0},
        }
        correct = all(c["value"] <= c["limit"] for c in checks.values())

        # ---------------- report ------------------------------------------
        c = log.counts
        print(f"set-up: {setup_s:.3f} s; {len(session.shapes)} launch shapes "
              f"(bucket, span) {session.shapes}; compiles {c['setup', 'compiles']} "
              f"({c['setup', 'compile_s']:.3f} s), persistent cache hits "
              f"{c['setup', 'cache_hits']}, misses {c['setup', 'cache_misses']}",
              file=out)
        print(f"window: {len(decisions)} decisions in {closed_at:.3f} s; "
              f"compiles in the window: {c['window', 'compiles']}", file=out)
        if session.plan.due_s is not None:
            late = lateness or [0.0]
            print(f"generator lateness: {len(lateness)} idle arrivals, mean "
                  f"{1e3 * float(np.mean(late)):.4f} ms, max "
                  f"{1e3 * max(late):.4f} ms", file=out)
        print(f"checked {len(answered)} of {len(decisions)} decisions against "
              f"the reference", file=out)
        result = {
            "correct": bool(correct),
            "attempted": len(decisions),
            "failed": failed,
            "metrics": metrics,
            "device": device,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = {k: [v["value"], v["limit"]] for k, v in checks.items()}
        for k, v in checks.items():
            print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
        return result
    finally:
        jax.monitoring.unregister_event_duration_listener(log.on_duration)
        jax.monitoring.unregister_event_listener(log.on_event)


def _device_time(run: Run) -> tuple[float, float, dict]:
    """Busy and window seconds from the trace, and the breakdown: the device
    operations that took most time (a loop's event left out for its
    body's), and the longest idle gaps named by the
    host span they fall in."""
    tr = run.trace
    w = tr.window()
    chips = list(tr.device_ops.values())
    busy = sum(trace_reduce.union_ns(ops, w.start, w.end) for ops in chips)
    busy_s = busy / max(1, len(chips)) / 1e9
    by_op: collections.Counter = collections.Counter()
    for ops in chips:
        for op in trace_reduce.leaves(ops):
            by_op[trace_reduce.short_name(op)] += max(0.0, min(op.end, w.end) - max(op.start, w.start))
    gaps = []
    for ops in chips[:1]:
        for s, e in trace_reduce.idle_gaps(ops, w.start, w.end):
            gaps.append((trace_reduce.label_at(tr.host_spans, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    breakdown = {
        "device_ops": [[n, v / 1e9] for n, v in by_op.most_common(10)],
        "idle_gaps": [[n, v] for n, v in gaps[:10]],
    }
    return busy_s, (w.end - w.start) / 1e9, breakdown


def main(argv: list[str], t_process: float) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)

    # the program keeps its compile cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 3
    from repro.kernels.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_process)
    print(json.dumps(result))
    return 0
