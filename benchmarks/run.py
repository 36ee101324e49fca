# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

Paper artefacts reproduced (on the synthetic IN2P3-calibrated dataset):

  * ``bench_performance_profiles``  — Figures 14/15/16: performance profiles
    of all registered policies at U in {0, seg/2, seg}.
  * ``bench_time_to_solution``      — §5.3 running-time table.
  * ``bench_kernel_wavefront``      — wavefront DP table builds (the NumPy
    reference + the single-trace Pallas wavefront in interpret mode).
  * ``bench_solve_batch``           — padded multi-instance device launch vs
    per-instance python solving (parity-checked).
  * ``bench_hetero_batch``          — heterogeneous (mixed-size) batch: the
    seed's single maximally-padded launch vs the size-bucketed planner
    (bit-identical results, throughput A/B).
  * ``bench_policy_backends``       — per-policy, per-backend wall time and
    solve throughput matrix.
  * ``bench_tape_restore``          — system table: LTSP-scheduled checkpoint
    restore vs positional sweep (mean shard service time + solve-cache
    hit/miss counters).
  * ``bench_online_serving``        — online queue service: arrival-rate sweep
    of mean/p50/p95/p99 request sojourn per admission policy (fifo /
    accumulate / preempt) on a seeded trace, every emitted schedule re-scored
    by the discrete-event simulator oracle; asserts accumulate-then-solve
    beats per-request FIFO under load.  Plus the drive-pool sweep:
    drive-count x admission-policy (fifo-global / per-drive-accumulate /
    batched) with a nonzero mount/unmount/load-seek cost model, showing how
    mount contention degrades sojourn as the pool shrinks below
    one-drive-per-cartridge.  Plus the QoS sweep: deadline-tightness x
    admission miss-rate curves on a deadline/class-annotated trace
    (``repro.data.traces.qos_poisson_trace``) — asserts the deadline-aware
    admissions (``edf-global`` / ``slack-accumulate``) achieve strictly
    fewer deadline misses than ``fifo-global`` at every swept tightness
    (exact virtual-time ints) — and the mount-scheduler sweep
    (greedy / lru / lookahead) on the constrained pool.
  * ``bench_overload_serving``     — load-adaptive solver selection: arrival-
    rate sweep (light -> overloaded) under a priced ``ComputeBudget``, fixed
    dp/logdp1/nfgs arms vs the ``cost-model`` selector; asserts adaptation
    never misses more deadlines than the best fixed policy at any swept
    rate (exact virtual-time ints) and that the adaptive arm actually
    switches policy across the sweep.
  * ``bench_fleet_serving``        — fleet federation: shard-count x
    placement-strategy sweep on a replicated multi-library archive with one
    injected whole-shard outage; asserts ``replica-affinity`` routing
    strictly beats oblivious ``static-hash`` on deadline misses (served
    misses + dropped requests, exact virtual-time ints) at every swept
    cell.

All scheduling goes through the solver registry (``repro.core.solver``) under
an ``ExecutionContext``; every reported cost is re-validated against the
exact trajectory simulator.

Run: ``PYTHONPATH=src python -m benchmarks.run [--full]``

Recorded trajectory: ``--record [PATH]`` additionally writes a
machine-readable snapshot (default ``BENCH_pr2.json``) of every bench that
ran; ``--baseline PATH`` compares the fresh snapshot against a checked-in one
and exits nonzero if the interpret-backend bucketed solve throughput regressed
more than ``REGRESSION_TOLERANCE`` (runner-calibrated: measured as the speedup
over the padded arm of the same run) — CI runs the smoke profile of this as
the perf gate, so the perf trajectory of the repo is diffable PR over PR.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

RESULTS = pathlib.Path("results")

#: allowed fractional drop in recorded throughput before --baseline fails.
REGRESSION_TOLERANCE = 0.25

#: benches append {name: row} snapshots here; --record serialises it.
RECORD: dict = {}


#: set by ``--obs``: a repro.obs.MetricsRegistry every timed serving cell
#: feeds; ``--record`` then lands its snapshot as ``RECORD["obs_metrics"]``.
OBS_METRICS = None


def _emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def _timed_serve(label: str, run):
    """Run one timed serving cell: ``(report, summary, wall_s)``.

    The serving benches (online / overload / fleet) each repeated the same
    time-it / summarise block per swept cell; this is that block, shared.
    With ``--obs`` the cell also lands in the metrics registry as exact-int
    counters and a wall-time histogram (integer microseconds — the registry
    rejects floats by design).
    """
    t0 = time.perf_counter()
    report = run()
    dt = time.perf_counter() - t0
    s = report.summary()
    if OBS_METRICS is not None:
        OBS_METRICS.inc("bench_cells_total", bench=label)
        OBS_METRICS.inc(
            "bench_requests_served_total", int(report.n_served), bench=label
        )
        OBS_METRICS.observe("bench_wall_us", int(dt * 1e6), bench=label)
    return report, s, dt


def _timed_solve(solver, inst):
    """``(cost, detours, seconds)`` timing only schedule *construction*.

    Heuristic solvers score their detours with the exact simulator inside
    ``solve()``; the paper's running-time tables exclude evaluation, so time
    the raw detour computation and score outside the clock (DP solvers get
    their cost from the recurrence itself, i.e. for free).
    """
    from repro.core import evaluate_detours
    from repro.core.solver import HeuristicSolver

    if isinstance(solver, HeuristicSolver):
        t0 = time.perf_counter()
        detours = solver.fn(inst)
        dt = time.perf_counter() - t0
        return evaluate_detours(inst, detours), detours, dt
    t0 = time.perf_counter()
    res = solver.solve(inst)
    dt = time.perf_counter() - t0
    return res.cost, res.detours, dt


# ---------------------------------------------------------------------------
def bench_performance_profiles(full: bool = False):
    """Figures 14-16: fraction of instances within tau of optimal."""
    from repro.core import evaluate_detours, get_solver, list_solvers, lower_bound_gap
    from repro.data import BENCH_PROFILE, PAPER_PROFILE, generate_dataset, u_turn_values

    profile = PAPER_PROFILE if full else BENCH_PROFILE
    ds0 = generate_dataset(profile)
    u_vals = u_turn_values(ds0)
    taus = [0.001, 0.01, 0.025, 0.05, 0.10, 0.25]
    policies = list_solvers()
    out_rows = []
    for u_name, U in u_vals.items():
        import dataclasses

        ds = [dataclasses.replace(i, u_turn=U) for i in ds0]
        costs: dict[str, list[float]] = {a: [] for a in policies}
        gaps: dict[str, list[float]] = {a: [] for a in policies}
        t_algo: dict[str, float] = {a: 0.0 for a in policies}
        for inst in ds:
            per = {}
            for name in policies:
                cost, detours, dt = _timed_solve(get_solver(name), inst)
                t_algo[name] += dt
                assert cost == evaluate_detours(inst, detours), name
                per[name] = cost
                gaps[name].append(lower_bound_gap(inst, cost))
            opt = per["dp"]
            for name, c in per.items():
                costs[name].append(c / opt if opt else 1.0)
        for name in policies:
            ratios = np.array(costs[name])
            fracs = [(ratios <= 1 + tau).mean() for tau in taus]
            mean_gap = float(np.mean(gaps[name]))
            row = {
                "figure": f"perf_profile_U_{u_name}",
                "algorithm": name,
                "mean_ratio": float(ratios.mean()),
                "p95_ratio": float(np.quantile(ratios, 0.95)),
                "mean_lb_gap": mean_gap,
                **{f"within_{tau}": float(fr) for tau, fr in zip(taus, fracs)},
                "total_time_s": t_algo[name],
            }
            out_rows.append(row)
            _emit(
                f"profile/{u_name}/{name}",
                1e6 * t_algo[name] / len(ds),
                f"mean_ratio={ratios.mean():.4f};within_2.5%={fracs[2]:.2f};"
                f"lb_gap={mean_gap:.4f}",
            )
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "performance_profiles.json").write_text(json.dumps(out_rows, indent=1))
    return out_rows


#: What the paper's §5.3 running-time table establishes (qualitatively — the
#: absolute seconds are theirs, measured on their machine/dataset, and are
#: not restated here to avoid fabricating numbers): the list heuristics are
#: effectively instant, the restricted DPs (SIMPLEDP, LOGDP) stay within
#: interactive running times at full IN2P3 scale, and the exact DP is orders
#: of magnitude slower — minutes-plus per large tape — which is exactly why
#: the low-cost variants exist.  ``check_section_5_3`` verifies the measured
#: medians reproduce this class ordering.
PAPER_5_3_REFERENCE = {
    "source": "arXiv:2112.09384 §5.3 running-time comparison (IN2P3 dataset)",
    "classes": [
        {"name": "heuristics", "policies": ["nodetour", "gs", "fgs", "nfgs",
                                            "lognfgs5"]},
        {"name": "restricted-dp", "policies": ["simpledp", "logdp1", "logdp5"]},
        {"name": "exact-dp", "policies": ["dp"]},
    ],
    "expected": "median(heuristics) <= median(restricted-dp) << median(exact-dp)",
}

#: per-policy wall-time budget for the paper-scale (``--full``) §5.3 table;
#: a policy stops taking new (larger) instances once it has spent this much,
#: and the skipped strata are recorded as such — the exact DP needs hours on
#: the top strata of the 169-tape profile, which a snapshot run can't afford.
FULL_TIME_BUDGET_S = 300.0


def check_section_5_3(rows: list[dict]) -> dict:
    """Compare measured medians against the paper's §5.3 class ordering."""
    med = {r["algorithm"]: r["median_s"] for r in rows if r["median_s"] is not None}
    cls = {
        c["name"]: [med[p] for p in c["policies"] if p in med]
        for c in PAPER_5_3_REFERENCE["classes"]
    }
    cls_med = {k: float(np.median(v)) for k, v in cls.items() if v}
    if all(k in cls_med for k in ("heuristics", "restricted-dp", "exact-dp")):
        ordered = (
            cls_med["heuristics"]
            <= cls_med["restricted-dp"]
            <= cls_med["exact-dp"]
        )
    else:
        ordered = None  # a class has no completed strata: unknown, not "true"
    return {
        "reference": PAPER_5_3_REFERENCE,
        "class_median_s": cls_med,
        "ordering_consistent_with_paper": ordered,
        "dp_vs_heuristic_ratio": (
            cls_med["exact-dp"] / max(cls_med["heuristics"], 1e-9)
            if "exact-dp" in cls_med and "heuristics" in cls_med
            else None
        ),
    }


def bench_time_to_solution(full: bool = False):
    """§5.3 running-time comparison (median seconds per instance).

    Smoke mode keeps the historical CI behaviour: the first 20 bench-profile
    instances, every policy.  ``--full`` is the paper-scale artefact: a
    stratified sample of the 169-tape IN2P3-calibrated profile (one instance
    per ``n_req`` quantile) with a per-policy wall-time budget
    (:data:`FULL_TIME_BUDGET_S`) — policies run their strata smallest-first
    and stop when the budget is spent, so the exact DP reports honest medians
    over the strata it completed instead of hanging the run for hours.  The
    snapshot's summary block (``section_5_3``) compares the measured class
    ordering against the paper's table.
    """
    from repro.core import get_solver, list_solvers
    from repro.data import BENCH_PROFILE, PAPER_PROFILE, generate_dataset

    if full:
        ds_all = sorted(generate_dataset(PAPER_PROFILE), key=lambda i: i.n_req)
        qs = [0.0, 0.25, 0.5, 0.75, 0.9, 1.0]
        idx = sorted({int(q * (len(ds_all) - 1)) for q in qs})
        ds = [ds_all[i] for i in idx]
        budget = FULL_TIME_BUDGET_S
    else:
        ds = generate_dataset(BENCH_PROFILE)[:20]
        budget = float("inf")
    rows = []
    for name in list_solvers():
        ts: list[float] = []
        per_inst: list[dict] = []
        spent = 0.0
        prev: tuple[float, int, int] | None = None  # (seconds, n_req, n)
        for inst in ds:  # ascending n_req in full mode: small strata first
            if spent > budget:
                per_inst.append({"n_req": inst.n_req, "seconds": None,
                                 "skipped": "budget"})
                continue
            if prev is not None:
                # DP-family work scales ~ R^2 * S; refuse to *start* a stratum
                # the extrapolated cost of which blows the budget
                dt0, R0, n0 = prev
                predicted = dt0 * (inst.n_req / R0) ** 2 * (inst.n / max(n0, 1))
                if predicted > 1.0 and spent + predicted > budget:
                    per_inst.append({"n_req": inst.n_req, "seconds": None,
                                     "skipped": "budget-predicted"})
                    continue
            _, _, dt = _timed_solve(get_solver(name), inst)
            ts.append(dt)
            spent += dt
            prev = (dt, inst.n_req, inst.n)
            per_inst.append({"n_req": inst.n_req, "seconds": dt})
        med = float(np.median(ts)) if ts else None
        row = {"algorithm": name, "median_s": med,
               "max_s": float(max(ts)) if ts else None,
               "n_completed": len(ts), "n_instances": len(ds)}
        if full:
            row["per_instance"] = per_inst
        rows.append(row)
        _emit(
            f"time_to_solution/{name}",
            (med or 0.0) * 1e6,
            f"max_s={row['max_s']:.3f};completed={len(ts)}/{len(ds)}"
            if ts else "completed=0",
        )
    out: dict = {"rows": rows, "profile": "paper" if full else "bench"}
    if full:
        out["section_5_3"] = check_section_5_3(rows)
        ratio = out["section_5_3"]["dp_vs_heuristic_ratio"]
        _emit(
            "time_to_solution/section_5_3",
            0.0,
            f"ordering_consistent={out['section_5_3']['ordering_consistent_with_paper']};"
            f"dp_vs_heuristic_ratio={f'{ratio:.3g}' if ratio is not None else 'n/a'}",
        )
    (RESULTS / "time_to_solution.json").write_text(json.dumps(out, indent=1))
    RECORD["time_to_solution"] = out
    return rows


def _small_bench_instance(rng, R):
    from repro.core import make_instance

    sizes = rng.integers(1, 9, size=R)
    gaps = rng.integers(0, 6, size=R + 1)
    left, pos = [], int(gaps[0])
    for i in range(R):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    return make_instance(left, sizes, rng.integers(1, 4, size=R), m=pos, u_turn=3)


def bench_kernel_wavefront(full: bool = False):
    """Wavefront DP table builds: the NumPy reference and the single-trace
    Pallas wavefront (interpret mode is correctness-only on CPU, so its time
    measures one full table build, not TPU speed)."""
    import jax

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables
    from repro.kernels.ltsp_dp.ops import prepare_batch
    from repro.kernels.ltsp_dp.ref import ltsp_tables_ref

    rng = np.random.default_rng(0)
    R = 24 if not full else 48
    inst = _small_bench_instance(rng, R)
    packed = prepare_batch([inst])
    S = packed[-1]
    host = [np.asarray(v[0]) for v in packed[:-1]]

    t0 = time.perf_counter()
    n_rep = 3
    for _ in range(n_rep):
        ltsp_tables_ref(*host, S)
    dt = (time.perf_counter() - t0) / n_rep
    cells = R * R * S / 2
    _emit("kernel/wavefront_ref", dt * 1e6, f"R={R};S={S};cells_per_s={cells/dt:.3g}")

    pf = lambda: ltsp_dp_tables(*packed[:-1], S=S, interpret=True)
    T, _ = pf()  # compile (single trace: one retrace total, not R)
    t0 = time.perf_counter()
    T, C = pf()
    jax.block_until_ready((T, C))
    dt_p = time.perf_counter() - t0
    _emit(
        "kernel/wavefront_pallas_interpret",
        dt_p * 1e6,
        f"R={R};S={S};cells_per_s={cells/dt_p:.3g}",
    )
    row = {"R": R, "S": S, "seconds_ref": dt, "seconds_pallas": dt_p,
           "cells_per_s_ref": cells / dt}
    RECORD["kernel_wavefront"] = row
    return row


def bench_solve_batch(full: bool = False):
    """Bucketed multi-instance device launches vs per-instance python DP."""
    from repro.core import ExecutionContext, solve, solve_batch
    from repro.kernels.ltsp_dp.ops import plan_buckets, rescale_instance

    rng = np.random.default_rng(11)
    B = 8 if not full else 16
    insts = [_small_bench_instance(rng, int(rng.integers(6, 14))) for _ in range(B)]
    n_launches = len(plan_buckets([rescale_instance(i)[0] for i in insts]))
    dev_ctx = ExecutionContext(backend="pallas-interpret")

    t0 = time.perf_counter()
    py = [solve(i, policy="dp") for i in insts]
    dt_py = time.perf_counter() - t0

    solve_batch(insts, policy="dp", context=dev_ctx)  # compile
    t0 = time.perf_counter()
    dev = solve_batch(insts, policy="dp", context=dev_ctx)
    dt_dev = time.perf_counter() - t0

    assert [r.cost for r in py] == [r.cost for r in dev], "batch parity violated"
    _emit("solver/batch_python", dt_py * 1e6 / B, f"B={B}")
    _emit(
        "solver/batch_pallas_interpret",
        dt_dev * 1e6 / B,
        f"B={B};launches={n_launches}",
    )
    row = {"B": B, "launches": n_launches,
           "seconds_python": dt_py, "seconds_device": dt_dev}
    RECORD["solve_batch"] = row
    return row


def _hetero_instances(rng, full: bool = False):
    """Mixed-size cartridge batch: mostly small tapes plus a few wide ones
    (the IN2P3 shape — a global pad wastes most of its lanes)."""
    n_small = 8 if not full else 16
    n_wide = 4 if not full else 8
    insts = [_small_bench_instance(rng, int(rng.integers(3, 8)))
             for _ in range(n_small)]
    for _ in range(n_wide):
        insts.append(_small_bench_instance(rng, int(rng.integers(18, 27))))
    # bump a couple of multiplicities so the wide tapes cross the 128-lane
    # S boundary and land in a different (R, S) bucket
    import dataclasses
    for i in range(n_small, n_small + 2):
        mult = insts[i].mult.copy()
        mult[::2] += 9
        insts[i] = dataclasses.replace(insts[i], mult=mult)
    order = rng.permutation(len(insts))
    return [insts[i] for i in order]


def _median_time(fn, n_rep: int = 3) -> float:
    ts = []
    for _ in range(n_rep):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_hetero_batch(full: bool = False):
    """Heterogeneous batch: seed-style global padding vs the bucket planner.

    Both paths are the same interpret-mode wavefront; only the launch shapes
    differ.  Results must be bit-identical to per-instance device solving
    (cost *and* detours) — the planner is a pure scheduling optimisation.
    """
    from repro.core import dp_schedule, evaluate_detours
    from repro.kernels.ltsp_dp.ops import (
        ltsp_solve_batch, ltsp_solve_instance, plan_buckets, rescale_instance,
    )

    rng = np.random.default_rng(20260731)
    insts = _hetero_instances(rng, full)
    B = len(insts)
    buckets = plan_buckets([rescale_instance(i)[0] for i in insts])

    padded = ltsp_solve_batch(insts, interpret=True, bucketed=False)  # compile
    # compile (per bucket)
    bucketed = ltsp_solve_batch(insts, interpret=True, bucketed=True)
    assert padded == bucketed, "bucketing changed results"
    for inst, (cost, dets) in zip(insts, bucketed):
        assert (cost, dets) == ltsp_solve_instance(inst, interpret=True), (
            "batch != per-instance"
        )
        assert cost == dp_schedule(inst)[0] == evaluate_detours(inst, dets)

    dt_pad = _median_time(
        lambda: ltsp_solve_batch(insts, interpret=True, bucketed=False)
    )
    dt_buck = _median_time(
        lambda: ltsp_solve_batch(insts, interpret=True, bucketed=True)
    )
    speedup = dt_pad / dt_buck
    _emit("solver/hetero_padded", dt_pad * 1e6 / B, f"B={B};R_max={max(i.n_req for i in insts)}")
    _emit(
        "solver/hetero_bucketed",
        dt_buck * 1e6 / B,
        f"B={B};buckets={len(buckets)};speedup={speedup:.2f}x",
    )
    row = {
        "backend": "pallas-interpret",
        "B": B,
        "profile": "full" if full else "smoke",
        "buckets": [[r, s, len(idx)] for (r, s), idx in sorted(buckets.items())],
        "padded": {"seconds": dt_pad, "instances_per_s": B / dt_pad},
        "bucketed": {"seconds": dt_buck, "instances_per_s": B / dt_buck},
        "speedup": speedup,
        "parity": True,
    }
    RECORD["hetero_batch"] = row
    return row


def bench_policy_backends(full: bool = False):
    """Per-policy, per-backend wall time + solve throughput matrix.

    Python rows run the full bench dataset slice; device rows run the
    heterogeneous small-tape set (interpret mode emulates the kernel on CPU,
    so paper-scale instances would measure the emulator, not the policy).
    """
    from repro.core import ExecutionContext, evaluate_detours, get_solver
    from repro.core.solver import list_solvers
    from repro.data import BENCH_PROFILE, generate_dataset

    ds_py = generate_dataset(BENCH_PROFILE)[: 12 if not full else 30]
    rng = np.random.default_rng(5)
    ds_dev = _hetero_instances(rng)[:6]
    rows = []
    for name in list_solvers():
        solver = get_solver(name)
        for backend in solver.backends:
            if backend == "pallas":  # compiled TPU: not available in CI
                continue
            ctx = ExecutionContext(backend=backend)
            ds = ds_py if backend == "python" else ds_dev
            if backend != "python":
                solver.solve_batch(ds, ctx)  # compile outside the clock
            t0 = time.perf_counter()
            results = solver.solve_batch(ds, ctx)
            dt = time.perf_counter() - t0
            for inst, res in zip(ds, results):
                assert res.cost == evaluate_detours(inst, res.detours), name
            rows.append({
                "policy": name,
                "backend": backend,
                "n_instances": len(ds),
                "seconds_total": dt,
                "seconds_per_instance": dt / len(ds),
                "solves_per_s": len(ds) / dt,
            })
            _emit(
                f"policy_backend/{name}/{backend}",
                dt * 1e6 / len(ds),
                f"n={len(ds)};solves_per_s={len(ds) / dt:.3g}",
            )
    RECORD["policy_backends"] = rows
    return rows


def bench_tape_restore(full: bool = False):
    """System table: checkpoint-restore mean service time by scheduler.

    The library context carries a solve-memo cache; each policy is planned
    twice and the warm re-plan (what a recovering fleet's next cold start
    pays) plus the cache hit/miss counters land in the summary.
    """
    from repro.core import ExecutionContext, SolveCache
    from repro.distributed.checkpoint import plan_restore
    from repro.storage.tape import TapeLibrary

    rng = np.random.default_rng(7)
    lib = TapeLibrary(
        capacity_per_tape=2 * 10**9, u_turn=10_000_000,
        context=ExecutionContext(cache=SolveCache()),
    )
    shards = []
    for i in range(60):
        name = f"ckpt/shard{i:03d}"
        lib.store(name, int(rng.integers(5_000_000, 120_000_000)))
        shards.append(name)
    consumers = {s: int(rng.integers(1, 9)) for s in shards}
    rows = []
    base = None
    for policy in ("nodetour", "gs", "fgs", "nfgs", "simpledp", "logdp1", "dp"):
        t0 = time.perf_counter()
        plans = plan_restore(lib, shards, consumers, policy=policy)
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        replans = plan_restore(lib, shards, consumers, policy=policy)
        dt_warm = time.perf_counter() - t0
        assert [p.total_cost for p in plans] == [p.total_cost for p in replans]
        mean = sum(p.total_cost for p in plans) / sum(consumers.values())
        base = base or mean
        rows.append({
            "policy": policy, "mean_service": mean,
            "plan_s": dt, "replan_s": dt_warm,
        })
        _emit(
            f"tape_restore/{policy}",
            dt * 1e6,
            f"mean_service={mean:.3g};vs_nodetour={mean/base:.3f};"
            f"replan_us={dt_warm*1e6:.0f}",
        )
    stats = lib.cache.stats()
    _emit(
        "tape_restore/cache",
        0.0,
        f"hits={stats['hits']};misses={stats['misses']};entries={stats['entries']}",
    )
    (RESULTS / "tape_restore.json").write_text(
        json.dumps({"rows": rows, "cache": stats}, indent=1)
    )
    RECORD["tape_restore"] = {"rows": rows, "cache": stats}
    return rows


def bench_online_serving(full: bool = False):
    """Online tape-serving tables: admission x arrival rate, then the
    drive-pool sweep (drive count x admission x mount cost model).

    A seeded Poisson-like trace (>= 200 requests, >= 4 cartridges) is served
    through the queue service at several mean inter-arrival times; each cell
    reports the exact per-request sojourn distribution (the service time
    users experience) and the number of LTSP solves.  The discrete-event
    simulator independently re-scores every emitted schedule
    (``all_verified``), and the accumulate-then-solve admission must beat
    per-request FIFO at every swept rate — the online claim of the paper's
    objective, asserted on virtual time (no wall clocks).

    The warm-vs-cold sweep then re-serves each rate with ``warm_start``
    on and off: schedules must be bit-identical (warm start only changes
    how much DP work a re-solve performs), ``preempt`` must evaluate
    strictly fewer cells warm at every rate, and the loaded regime must
    show >= 30% fewer per-tick DP cells — the exact integer cell counts
    land in the record and are gated by ``--baseline``.

    The drive-pool sweep then prices the robotic-arm layer: ``n_drives`` in
    {1, 2, n_tapes} under a nonzero mount/unmount/load-seek model for each
    cross-cartridge admission (``fifo-global`` / ``per-drive-accumulate`` /
    ``batched``); ``batched`` must schedule bit-identically to
    ``per-drive-accumulate`` (it only changes how solves are batched onto
    the device), and the dedicated pool must serve no worse than the
    single-drive pool under every batching admission.

    The QoS sweep replays one deadline/class-annotated trace per swept
    tightness (same arrival process at every tightness — only the deadline
    pressure changes) through ``fifo-global`` and the deadline-aware
    admissions, recording per-admission miss-rate curves and per-class SLO
    summaries; the deadline-aware admissions must achieve *strictly fewer*
    misses than ``fifo-global`` at every tightness, asserted on exact
    integer virtual time.  The mount-scheduler sweep then runs the
    constrained pool under each registered eviction policy.

    The availability sweep prices the fault layer: recorded drive hard-
    failures (0/1/2 of a 3-drive pool, failure instants derived from the
    no-fault run so the first failure is guaranteed to abort live work)
    crossed with the retry policy (``FAIL_STOP`` vs retry+failover),
    reporting completion rate and p99 sojourn per cell; retry+failover must
    complete strictly more requests than fail-stop at every nonzero failure
    count, asserted on exact request counts.
    """
    from repro.data.traces import DEFAULT_QOS_CLASSES, qos_poisson_trace, to_requests
    from repro.serving.drives import DriveCosts
    from repro.serving.qos import slo_report
    from repro.serving.queue import (
        LEGACY_ADMISSIONS,
        POOL_ADMISSIONS,
        QOS_ADMISSIONS,
        WINDOWED_ADMISSIONS,
        serve_trace,
    )
    from repro.serving.sim import demo_library, poisson_trace

    seed = 20260731
    n_requests = 240 if not full else 600
    n_files = 48 if not full else 96

    def build_library():
        return demo_library(seed, n_files=n_files)

    n_tapes = len(build_library().tapes)
    assert n_tapes >= 4, "sweep needs a multi-cartridge library"
    rows = []
    window = 400_000
    for rate in (100_000, 400_000, 1_600_000):
        trace = poisson_trace(
            build_library(), n_requests=n_requests, mean_interarrival=rate, seed=seed
        )
        per_admission: dict[str, float] = {}
        for admission in LEGACY_ADMISSIONS:
            lib = build_library()
            # verify=True inside summary(): the oracle raised on any lie
            report, s, dt = _timed_serve("online", lambda: serve_trace(
                lib,
                trace,
                admission,
                window=window if admission == "accumulate" else 0,
                policy="dp",
                context=lib.context,
            ))
            assert s["n_served"] == n_requests
            per_admission[admission] = s["mean_sojourn"]
            rows.append({"rate": rate, "wall_s": dt, **s})
            _emit(
                f"online/{admission}/rate_{rate}",
                dt * 1e6,
                f"mean_sojourn={s['mean_sojourn']:.4g};"
                f"p50={s['p50_sojourn']:.4g};p95={s['p95_sojourn']:.4g};"
                f"p99={s['p99_sojourn']:.4g};batches={s['n_batches']};"
                f"preempts={s['n_preemptions']};"
                f"cells={s['cells_evaluated']};reused={s['cells_reused']};"
                f"cache_hits={s.get('cache', {}).get('hits', 0)}",
            )
        assert per_admission["accumulate"] < per_admission["fifo"], (
            f"accumulate-then-solve must beat FIFO at rate {rate}"
        )

    # -- warm-vs-cold sweep: per-tick DP work saved by incremental re-solve --
    # Both arms run the same solve_warm plumbing (so counters compare like
    # for like); only warm_start differs.  Schedules must be bit-identical
    # at every swept rate — warm start is a work optimisation, never a
    # scheduling change — and the cells-evaluated reduction is asserted
    # where re-solving dominates: `preempt` re-solves the surviving multiset
    # on every arrival, so reuse must strictly win at every rate and cut
    # >= 30% of the per-tick DP cells in the most-loaded regime.
    def _schedule_keys(s):
        return {
            k: v for k, v in s.items()
            if k not in ("warm_start", "cells_evaluated", "cells_reused",
                         "cells_per_batch", "cache")
        }

    warm_rows = []
    warm_cells: dict[tuple[str, int], dict] = {}
    rates = (100_000, 400_000, 1_600_000)
    loaded_rate = min(rates)  # smallest inter-arrival gap = highest load
    for rate in rates:
        trace = poisson_trace(
            build_library(), n_requests=n_requests, mean_interarrival=rate, seed=seed
        )
        for admission in ("accumulate", "preempt"):
            per_mode = {}
            for warm_start in (True, False):
                lib = build_library()
                report, s, dt = _timed_serve("online/warm", lambda: serve_trace(
                    lib, trace, admission,
                    window=window if admission == "accumulate" else 0,
                    policy="dp", context=lib.context, warm_start=warm_start,
                ))
                assert s["n_served"] == n_requests and s["all_verified"]
                per_mode[warm_start] = s
                warm_rows.append({"rate": rate, "wall_s": dt, **s})
            warm_s, cold_s = per_mode[True], per_mode[False]
            assert _schedule_keys(warm_s) == _schedule_keys(cold_s), (
                f"warm start changed a schedule: {admission} at rate {rate}"
            )
            assert cold_s["cells_reused"] == 0, "cold runs must not reuse"
            assert warm_s["cells_evaluated"] <= cold_s["cells_evaluated"]
            if admission == "preempt":
                # recorded assertion: strictly fewer cells at EVERY rate
                assert warm_s["cells_evaluated"] < cold_s["cells_evaluated"], (
                    f"warm start must strictly reduce DP work at rate {rate}"
                )
            reduction = (
                1.0 - warm_s["cells_evaluated"] / cold_s["cells_evaluated"]
                if cold_s["cells_evaluated"] else 0.0
            )
            warm_cells[(admission, rate)] = {
                "admission": admission,
                "rate": rate,
                "warm_cells": warm_s["cells_evaluated"],
                "cold_cells": cold_s["cells_evaluated"],
                "cells_reused": warm_s["cells_reused"],
                "n_batches": warm_s["n_batches"],
                "warm_cells_per_batch": warm_s["cells_per_batch"],
                "cold_cells_per_batch": cold_s["cells_per_batch"],
                "reduction": reduction,
            }
            _emit(
                f"online/warm/{admission}/rate_{rate}",
                0.0,
                f"cells_warm={warm_s['cells_evaluated']};"
                f"cells_cold={cold_s['cells_evaluated']};"
                f"reused={warm_s['cells_reused']};"
                f"reduction={reduction:.1%};batches={warm_s['n_batches']}",
            )
    headline = warm_cells[("preempt", loaded_rate)]
    assert headline["reduction"] >= 0.30, (
        f"warm start must cut >= 30% of per-tick DP cells in the loaded "
        f"regime (rate={loaded_rate}); measured {headline['reduction']:.1%}"
    )

    # -- drive-pool sweep: contention under an explicit mount cost model -----
    costs = DriveCosts(mount=150_000, unmount=60_000, load_seek=30_000)
    rate = 100_000  # the loaded regime, where drive contention binds
    trace = poisson_trace(
        build_library(), n_requests=n_requests, mean_interarrival=rate, seed=seed
    )
    pool_rows = []
    per_cell: dict[tuple[str, int], float] = {}
    for admission in POOL_ADMISSIONS:
        for n_drives in (1, 2, n_tapes):
            lib = build_library()
            report, s, dt = _timed_serve("online/pool", lambda: serve_trace(
                lib,
                trace,
                admission,
                window=window,
                policy="dp",
                n_drives=n_drives,
                drive_costs=costs,
                context=lib.context,
            ))
            assert s["n_served"] == n_requests and s["all_verified"]
            per_cell[(admission, n_drives)] = s["mean_sojourn"]
            pool_rows.append({"rate": rate, "wall_s": dt, **s})
            _emit(
                f"online/pool/{admission}/drives_{n_drives}",
                dt * 1e6,
                f"mean_sojourn={s['mean_sojourn']:.4g};"
                f"p50={s['p50_sojourn']:.4g};p95={s['p95_sojourn']:.4g};"
                f"p99={s['p99_sojourn']:.4g};batches={s['n_batches']};"
                f"mounts={s['mounts']};unmounts={s['unmounts']}",
            )
    for n_drives in (1, 2, n_tapes):
        # batched == per-drive-accumulate scheduling (one launch per tick is
        # a solve-batching change, not a scheduling change)
        assert per_cell[("batched", n_drives)] == per_cell[
            ("per-drive-accumulate", n_drives)
        ], n_drives
    for admission in ("per-drive-accumulate", "batched"):
        assert per_cell[(admission, n_tapes)] <= per_cell[(admission, 1)], (
            f"{admission}: a dedicated pool must serve no worse than one drive"
        )

    # -- QoS sweep: deadline tightness x admission, miss-rate curves ---------
    qos_rate = 250_000
    qos_admissions = ("fifo-global",) + QOS_ADMISSIONS + ("per-drive-accumulate",)
    tightness_sweep = (2_000_000, 8_000_000, 32_000_000)
    qos_rows = []
    for tightness in tightness_sweep:
        records = qos_poisson_trace(
            build_library(), n_requests=n_requests, mean_interarrival=qos_rate,
            seed=seed, tightness=tightness,
        )
        qtrace, qos = to_requests(records, build_library())
        missed: dict[str, int] = {}
        for admission in qos_admissions:
            lib = build_library()
            report, s, dt = _timed_serve("online/qos", lambda: serve_trace(
                lib,
                qtrace,
                admission,
                window=window if admission in WINDOWED_ADMISSIONS else 0,
                policy="dp",
                qos=qos,
                context=lib.context,
            ))
            assert s["n_served"] == n_requests and s["all_verified"]
            missed[admission] = report.n_missed  # exact virtual-time int
            qos_rows.append({
                "tightness": tightness, "wall_s": dt, **s,
                "slo": slo_report(report).summary(),
            })
            _emit(
                f"online/qos/{admission}/tight_{tightness}",
                dt * 1e6,
                f"missed={s['n_missed']}/{s['n_deadlines']};"
                f"miss_rate={s['miss_rate']:.3f};"
                f"p50={s['p50_sojourn']:.4g};p99={s['p99_sojourn']:.4g}",
            )
        for admission in QOS_ADMISSIONS:
            assert missed[admission] < missed["fifo-global"], (
                f"{admission} must achieve strictly fewer deadline misses "
                f"than fifo-global at tightness {tightness} "
                f"({missed[admission]} vs {missed['fifo-global']})"
            )

    # -- mount-scheduler sweep on the constrained pool -----------------------
    records = qos_poisson_trace(
        build_library(), n_requests=n_requests, mean_interarrival=qos_rate,
        seed=seed, tightness=8_000_000,
    )
    qtrace, qos = to_requests(records, build_library())
    sched_rows = []
    for admission in ("per-drive-accumulate", "slack-accumulate"):
        for sched in ("greedy", "lru", "lookahead"):
            lib = build_library()
            report, s, dt = _timed_serve("online/sched", lambda: serve_trace(
                lib, qtrace, admission, window=window, policy="dp",
                n_drives=2, drive_costs=costs, qos=qos,
                mount_scheduler=sched, context=lib.context,
            ))
            assert s["n_served"] == n_requests and s["all_verified"]
            sched_rows.append({"wall_s": dt, **s})
            _emit(
                f"online/sched/{admission}/{sched}",
                dt * 1e6,
                f"mean_sojourn={s['mean_sojourn']:.4g};"
                f"missed={s['n_missed']}/{s['n_deadlines']};"
                f"mounts={s['mounts']};mount_time={s['mount_time']}",
            )

    # -- availability sweep: recorded drive failures x retry policy ----------
    from repro.serving.drives import FAIL_STOP, RetryPolicy
    from repro.serving.faults import DriveFailure, FaultPlan

    avail_drives = 3
    avail_rate = 100_000
    trace = poisson_trace(
        build_library(), n_requests=n_requests, mean_interarrival=avail_rate,
        seed=seed,
    )
    lib = build_library()
    base = serve_trace(
        lib, trace, "per-drive-accumulate", window=window, policy="dp",
        n_drives=avail_drives, drive_costs=costs, context=lib.context,
    )
    # failure instants come from the no-fault run: one virtual tick after a
    # mid-trace batch starts service every request aboard is still pending,
    # and the pre-failure prefix is shared by construction, so the first
    # failure is guaranteed to abort live work in both policy arms
    mid = sorted(
        (b for b in base.batches if b.n_requests >= 2),
        key=lambda b: b.dispatched,
    )
    mid = mid[len(mid) // 2:]
    first = mid[0]
    second = next(
        b for b in mid + list(base.batches) if b.drive != first.drive
    )
    fail_points = (
        DriveFailure(at=first.dispatched + first.mount_delay + 1,
                     drive=first.drive),
        DriveFailure(at=second.dispatched + second.mount_delay + 1,
                     drive=second.drive),
    )
    retry_arms = {
        "fail-stop": FAIL_STOP,
        "retry-failover": RetryPolicy(on_exhausted="drop"),
    }
    avail_rows = []
    n_completed: dict[tuple[str, int], int] = {}
    for n_failures in (0, 1, 2):
        plan = FaultPlan(drive_failures=fail_points[:n_failures])
        for arm, retry in retry_arms.items():
            lib = build_library()
            report, s, dt = _timed_serve("online/avail", lambda: serve_trace(
                lib, trace, "per-drive-accumulate", window=window,
                policy="dp", n_drives=avail_drives, drive_costs=costs,
                context=lib.context, faults=plan or None, retry=retry,
            ))
            assert report.n_served + report.n_failed == n_requests, (
                "requests must be conserved: served or typed-failed"
            )
            n_completed[(arm, n_failures)] = report.n_served
            avail_rows.append({
                "arm": arm, "n_failures": n_failures, "wall_s": dt, **s,
            })
            _emit(
                f"online/avail/{arm}/failures_{n_failures}",
                dt * 1e6,
                f"completed={report.n_served}/{n_requests};"
                f"rate={report.completion_rate:.3f};"
                f"p99={s['p99_sojourn']:.4g};"
                f"requeued={s.get('faults', {}).get('requeued', 0)}",
            )
    assert (
        n_completed[("fail-stop", 0)]
        == n_completed[("retry-failover", 0)]
        == n_requests
    ), "with no failures both arms must complete everything"
    for n_failures in (1, 2):
        assert (
            n_completed[("retry-failover", n_failures)]
            > n_completed[("fail-stop", n_failures)]
        ), (
            f"retry+failover must complete strictly more requests than "
            f"fail-stop at {n_failures} drive failure(s): "
            f"{n_completed[('retry-failover', n_failures)]} vs "
            f"{n_completed[('fail-stop', n_failures)]}"
        )

    (RESULTS / "online_serving.json").write_text(
        json.dumps(
            rows + warm_rows + pool_rows + qos_rows + sched_rows + avail_rows,
            indent=1,
        )
    )
    RECORD["online_serving"] = {
        "seed": seed,
        "n_requests": n_requests,
        "n_tapes": n_tapes,
        "window": window,
        "rows": rows,
        "warm_sweep": {
            "rates": list(rates),
            "loaded_rate": loaded_rate,
            "headline": headline,
            "cells": list(warm_cells.values()),
            "rows": warm_rows,
        },
        "drive_sweep": {
            "costs": dataclasses.asdict(costs),
            "rate": rate,
            "rows": pool_rows,
        },
        "qos_sweep": {
            "rate": qos_rate,
            "tightness": list(tightness_sweep),
            "classes": [list(c) for c in DEFAULT_QOS_CLASSES],
            "rows": qos_rows,
        },
        "scheduler_sweep": {
            "costs": dataclasses.asdict(costs),
            "n_drives": 2,
            "tightness": 8_000_000,
            "rows": sched_rows,
        },
        "availability_sweep": {
            "costs": dataclasses.asdict(costs),
            "n_drives": avail_drives,
            "rate": avail_rate,
            "fail_points": [
                {"at": f.at, "drive": f.drive} for f in fail_points
            ],
            "completed": {
                f"{arm}/{n}": v for (arm, n), v in sorted(n_completed.items())
            },
            "rows": avail_rows,
        },
    }
    return rows + pool_rows + qos_rows + sched_rows + avail_rows


def bench_overload_serving(full: bool = False):
    """Overload sweep: load-adaptive solver selection vs every fixed policy.

    One seeded deadline-annotated trace per swept mean inter-arrival time
    (light -> overloaded) is served on a constrained 2-drive pool with a
    nonzero :class:`~repro.serving.drives.DriveCosts` model and a *priced*
    :class:`~repro.core.ComputeBudget`: every DP cell evaluated by a solve
    costs ``solve_time_num`` virtual-time units, so the exact DP's optimality
    is no longer free — under load its solve latency eats the very slack it
    optimises.  Four arms run on identical traces: three fixed policies
    (``dp`` / ``logdp1`` / ``nfgs``, pinned via the ``fixed`` selector so
    per-batch policy attribution lands in the record) and the ``cost-model``
    adaptive selector, which predicts per-policy solve cost from queue depth
    and the recorded per-tick timings and picks the strongest tier that fits
    ``per_tick``.

    Recorded assertion (exact integer virtual time, machine-independent):
    at *every* swept rate the adaptive arm misses no more deadlines than the
    best fixed policy at that rate — adaptation never costs you vs the best
    static choice, even though which fixed policy is best flips across the
    sweep (``dp`` wins light, ``nfgs`` wins loaded).  The adaptive arm must
    also actually adapt: its per-batch policy mix spans >= 2 policies across
    the sweep.  Solves run cold (``warm_start=False``): overload pressure
    comes from full re-solves, and pricing identical cold solves keeps the
    fixed arms like-for-like.  The workload is pinned (``--full`` does not
    widen it): the never-worse bound is a *recorded* property of this seeded
    trace + budget — the cost model carries no optimality guarantee, so the
    assertion documents a calibrated operating point, not a theorem over
    arbitrary workloads.
    """
    from repro.data.traces import qos_poisson_trace, to_requests
    from repro.core import ComputeBudget
    from repro.serving.drives import DriveCosts
    from repro.serving.queue import serve_trace
    from repro.serving.sim import demo_library

    del full  # recorded assertion — workload pinned to the calibrated trace
    seed = 20260731
    n_requests = 240
    n_files = 48

    def build_library():
        return demo_library(seed, n_files=n_files)

    window = 400_000
    tightness = 8_000_000
    costs = DriveCosts(mount=150_000, unmount=60_000, load_seek=30_000)
    rates = (400_000, 200_000, 60_000, 25_000)  # mean inter-arrival: light -> overloaded
    fixed_arms = ("dp", "logdp1", "nfgs")
    # calibrated on the seeded trace: at 10_000 units/cell the exact DP's
    # solve delay dominates under load; per_tick=120 cells is the knee where
    # the cost model starts demoting it.  hysteresis=1 because each tick
    # re-solves tiny instances from scratch — switching latency, not
    # flapping, is what hurts in the overloaded regime.
    budget = ComputeBudget(solve_time_num=10_000, per_tick=120, hysteresis=1)

    overload_rows = []
    headline = []
    policies_used: set[str] = set()
    for rate in rates:
        recs = qos_poisson_trace(
            build_library(), n_requests=n_requests,
            mean_interarrival=rate, seed=seed, tightness=tightness,
        )
        qtrace, qos = to_requests(recs, build_library())
        missed: dict[str, int] = {}
        for arm, policy, selector in (
            [(p, p, "fixed") for p in fixed_arms]
            + [("adaptive", "dp", "cost-model")]
        ):
            lib = build_library()
            ctx = lib.context.replace(budget=budget)
            report, s, dt = _timed_serve("overload", lambda: serve_trace(
                lib, qtrace, "slack-accumulate", window=window, qos=qos,
                policy=policy, selector=selector, n_drives=2,
                drive_costs=costs, context=ctx, warm_start=False,
            ))
            assert s["n_served"] == n_requests
            missed[arm] = report.n_missed
            if arm == "adaptive":
                policies_used.update(report.policy_mix)
            overload_rows.append({"rate": rate, "arm": arm, "wall_s": dt, **s})
            _emit(
                f"overload/{arm}/rate_{rate}",
                dt * 1e6,
                f"missed={report.n_missed}/{s['n_deadlines']};"
                f"p99={s['p99_sojourn']:.4g};"
                f"solve_delay={s['total_solve_delay']};"
                f"mix={'+'.join(f'{k}:{v}' for k, v in sorted(s['policy_mix'].items()))}",
            )
        best_fixed = min(missed[p] for p in fixed_arms)
        headline.append({
            "rate": rate,
            "adaptive_missed": missed["adaptive"],
            "best_fixed_missed": best_fixed,
            "fixed_missed": {p: missed[p] for p in fixed_arms},
        })
        assert missed["adaptive"] <= best_fixed, (
            f"adaptive selection must never miss more deadlines than the "
            f"best fixed policy: {missed['adaptive']} vs {best_fixed} "
            f"(fixed arms { {p: missed[p] for p in fixed_arms} }) at rate {rate}"
        )
    assert len(policies_used) >= 2, (
        f"the adaptive arm never switched policy across the sweep "
        f"(used {sorted(policies_used)}); the budget no longer exercises it"
    )

    (RESULTS / "overload_serving.json").write_text(
        json.dumps(overload_rows, indent=1)
    )
    RECORD["overload_serving"] = {
        "seed": seed,
        "n_requests": n_requests,
        "window": window,
        "tightness": tightness,
        "rates": list(rates),
        "budget": dataclasses.asdict(budget),
        "costs": dataclasses.asdict(costs),
        "selector": "cost-model",
        "fixed_arms": list(fixed_arms),
        "adaptive_policies_used": sorted(policies_used),
        "headline": headline,
        "rows": overload_rows,
    }
    return overload_rows


def bench_fleet_serving(full: bool = False):
    """Fleet federation sweep: placement strategies under a shard outage.

    A seeded ``replicas``-way replicated archive (every logical file lives
    on that many shards, :func:`~repro.fleet.demo_fleet`) serves one
    deadline-annotated federation-wide trace per swept arrival rate, for
    each swept shard count, while a
    :class:`~repro.serving.ShardOutage` darkens one whole shard mid-run
    (every drive on it fails at the same virtual instant).  Three routing
    arms run on identical traces: ``static-hash`` (oblivious content-hash
    placement — keeps routing into the dead shard), ``least-loaded``
    (queue-depth routing over live shard state), and ``replica-affinity``
    (queue depth x drive health x remount cost).  Retries are exhausted to
    ``drop`` so a stranded request becomes a recorded failure, not a crash.

    Recorded assertion (exact integer virtual time, machine-independent):
    at *every* swept (shard count, rate) cell, ``replica-affinity``'s
    deadline misses are strictly fewer than ``static-hash``'s, where a
    dropped deadline-carrying request counts as a miss (``n_missed`` among
    served + ``n_failed``).  The workload is pinned (``--full`` does not
    widen it): the strict bound is a *recorded* property of this seeded
    trace + outage, a calibrated operating point rather than a theorem
    over arbitrary workloads.
    """
    from repro.data.traces import qos_poisson_trace, to_requests
    from repro.fleet import demo_fleet, fleet_catalog, serve_fleet_trace
    from repro.serving import DriveCosts, RetryPolicy, ShardOutage

    del full  # recorded assertion — workload pinned to the calibrated sweep
    seed = 20260731
    n_requests = 180
    replicas = 2
    window = 400_000
    tightness = 8_000_000
    costs = DriveCosts(mount=150_000, unmount=60_000, load_seek=30_000)
    shard_counts = (2, 3)
    rates = (60_000, 30_000, 20_000)  # mean inter-arrival: light -> loaded
    outage_at, outage_shard = 1_500_000, 1
    placements = ("static-hash", "least-loaded", "replica-affinity")

    fleet_rows = []
    headline = []
    for n_shards in shard_counts:
        outages = (ShardOutage(at=outage_at, shard=outage_shard),)

        def build_fleet():
            return demo_fleet(seed, n_shards=n_shards, replicas=replicas)

        for rate in rates:
            libs, rmap = build_fleet()
            recs = qos_poisson_trace(
                fleet_catalog(libs, rmap), n_requests=n_requests,
                mean_interarrival=rate, seed=seed, tightness=tightness,
            )
            qtrace, qos = to_requests(recs, fleet_catalog(libs, rmap))
            misses: dict[str, int] = {}
            for pl in placements:
                libs, rmap = build_fleet()  # fresh shards per arm
                fr, s, dt = _timed_serve("fleet", lambda: serve_fleet_trace(
                    libs, qtrace, "slack-accumulate", placement=pl,
                    replica_map=rmap, outages=outages, window=window,
                    n_drives=2, drive_costs=costs, qos=qos,
                    retry=RetryPolicy(on_exhausted="drop"),
                ))
                # a dropped deadline-carrying request is a missed deadline
                misses[pl] = fr.n_missed + fr.n_failed
                fleet_rows.append({
                    "n_shards": n_shards, "rate": rate, "placement": pl,
                    "wall_s": dt, "deadline_misses": misses[pl], **s,
                })
                _emit(
                    f"fleet/{pl}/shards_{n_shards}/rate_{rate}",
                    dt * 1e6,
                    f"served={fr.n_served}/{n_requests};"
                    f"failed={fr.n_failed};missed={fr.n_missed};"
                    f"rerouted={fr.n_rerouted};"
                    f"routes={'/'.join(str(fr.routes[i]) for i in range(n_shards))}",
                )
            headline.append({
                "n_shards": n_shards,
                "rate": rate,
                "affinity_misses": misses["replica-affinity"],
                "static_misses": misses["static-hash"],
                "misses": dict(misses),
            })
            assert misses["replica-affinity"] < misses["static-hash"], (
                f"replica-affinity must strictly beat static-hash on "
                f"deadline misses under a shard outage: "
                f"{misses['replica-affinity']} vs {misses['static-hash']} "
                f"(all arms {misses}) at {n_shards} shards, rate {rate}"
            )

    (RESULTS / "fleet_serving.json").write_text(json.dumps(fleet_rows, indent=1))
    RECORD["fleet_serving"] = {
        "seed": seed,
        "n_requests": n_requests,
        "replicas": replicas,
        "window": window,
        "tightness": tightness,
        "shard_counts": list(shard_counts),
        "rates": list(rates),
        "costs": dataclasses.asdict(costs),
        "outage": {"at": outage_at, "shard": outage_shard},
        "placements": list(placements),
        "headline": headline,
        "rows": fleet_rows,
    }
    return fleet_rows


def check_baseline(record: dict, baseline_path: pathlib.Path) -> int:
    """Compare a fresh record against a checked-in baseline snapshot.

    Gate: the interpret-backend bucketed ``solve_batch`` throughput on the
    heterogeneous profile must not regress more than
    :data:`REGRESSION_TOLERANCE` against the baseline — measured as the
    *speedup over the padded launch from the same run*, so the padded arm
    calibrates away the runner's absolute speed (a checked-in baseline is
    recorded on a different machine than CI; absolute wall time would gate
    hardware, not code).  The absolute numbers are printed alongside for the
    trajectory.

    Second gate, on the serving loop's per-tick solve work: the warm-start
    sweep's headline cell counts are *exact integers on virtual time* —
    deterministic given the seeded trace, so machine-independent.  The
    warm-start reduction in the loaded regime must stay >= 30%, and the
    per-tick warm cell count must not creep above the baseline by more than
    :data:`REGRESSION_TOLERANCE` (a creep means reuse quietly degraded even
    if the ratio still clears the floor).  Returns a shell exit code.
    """
    baseline = json.loads(baseline_path.read_text())
    try:
        base, new = baseline["hetero_batch"], record["hetero_batch"]
        base_speedup, new_speedup = base["speedup"], new["speedup"]
        base_tp = base["bucketed"]["instances_per_s"]
        new_tp = new["bucketed"]["instances_per_s"]
    except KeyError as e:
        print(f"baseline check: missing hetero_batch record ({e})")
        return 2
    if base.get("profile") != new.get("profile"):
        print(
            f"baseline check: profile mismatch — baseline is "
            f"{base.get('profile')!r}, fresh run is {new.get('profile')!r}; "
            f"re-record the baseline with the matching profile"
        )
        return 2
    floor = (1.0 - REGRESSION_TOLERANCE) * base_speedup
    verdict = "OK" if new_speedup >= floor else "REGRESSED"
    print(
        f"baseline check [{verdict}]: bucketed-vs-padded interpret speedup "
        f"{new_speedup:.2f}x vs baseline {base_speedup:.2f}x "
        f"(floor {floor:.2f}x, tolerance {REGRESSION_TOLERANCE:.0%}); "
        f"absolute bucketed throughput {new_tp:.3g} inst/s "
        f"(baseline {base_tp:.3g}, different machine)"
    )
    if new_tp < (1.0 - REGRESSION_TOLERANCE) * base_tp:
        # a uniform slowdown of the shared kernel keeps the speedup ratio
        # flat, and a cross-machine baseline makes absolute wall time an
        # unreliable hard gate — so surface it loudly without failing.
        print(
            "baseline check WARNING: absolute bucketed throughput is >25% "
            "below the baseline; if this runner is comparable hardware, the "
            "shared wavefront path may have uniformly regressed (invisible "
            "to the speedup-ratio gate)."
        )

    # -- per-tick solve-work gate (exact virtual-time cell counts) -----------
    try:
        base_head = baseline["online_serving"]["warm_sweep"]["headline"]
        new_head = record["online_serving"]["warm_sweep"]["headline"]
    except KeyError as e:
        print(f"baseline check: missing warm_sweep record ({e})")
        return 2
    cells_ceiling = (1.0 + REGRESSION_TOLERANCE) * base_head["warm_cells_per_batch"]
    warm_ok = (
        new_head["reduction"] >= 0.30
        and new_head["warm_cells_per_batch"] <= cells_ceiling
    )
    print(
        f"baseline check [{'OK' if warm_ok else 'REGRESSED'}]: warm-start "
        f"per-tick DP work ({new_head['admission']} at rate "
        f"{new_head['rate']}): {new_head['warm_cells_per_batch']:.1f} "
        f"cells/batch vs baseline {base_head['warm_cells_per_batch']:.1f} "
        f"(ceiling {cells_ceiling:.1f}); reduction vs cold "
        f"{new_head['reduction']:.1%} (floor 30%, baseline "
        f"{base_head['reduction']:.1%})"
    )

    # -- adaptation-never-worse gate (exact virtual-time deadline misses) ----
    # Self-contained on the fresh record: the overload sweep's headline is
    # deterministic given the seeded trace, so the gate re-checks the
    # recorded assertion without needing the (possibly older) baseline to
    # carry the section.  A baseline that *does* carry it while the fresh
    # run doesn't means the bench silently stopped running — fail loudly.
    overload_ok = True
    new_over = record.get("overload_serving")
    base_over = baseline.get("overload_serving")
    if new_over is None and base_over is not None:
        print("baseline check: missing overload_serving record (bench not run?)")
        return 2
    if new_over is not None:
        worse = [
            h for h in new_over["headline"]
            if h["adaptive_missed"] > h["best_fixed_missed"]
        ]
        overload_ok = not worse and len(new_over["adaptive_policies_used"]) >= 2
        print(
            f"baseline check [{'OK' if overload_ok else 'REGRESSED'}]: "
            f"adaptive selection vs best fixed policy at rates "
            f"{new_over['rates']}: "
            + "; ".join(
                f"{h['adaptive_missed']}<={h['best_fixed_missed']}"
                for h in new_over["headline"]
            )
            + f" missed deadlines; policies used "
            f"{new_over['adaptive_policies_used']}"
        )

    # -- fleet replica-routing gate (exact virtual-time deadline misses) -----
    # Same self-contained shape as the overload gate: the fleet sweep's
    # headline is deterministic given the seeded trace + outage, so re-check
    # the recorded strict bound on the fresh record; a baseline carrying the
    # section while the fresh run lacks it means the bench silently stopped
    # running — fail loudly.
    fleet_ok = True
    new_fleet = record.get("fleet_serving")
    base_fleet = baseline.get("fleet_serving")
    if new_fleet is None and base_fleet is not None:
        print("baseline check: missing fleet_serving record (bench not run?)")
        return 2
    if new_fleet is not None:
        worse = [
            h for h in new_fleet["headline"]
            if h["affinity_misses"] >= h["static_misses"]
        ]
        fleet_ok = not worse
        print(
            f"baseline check [{'OK' if fleet_ok else 'REGRESSED'}]: "
            f"replica-affinity vs static-hash deadline misses under a shard "
            f"outage at (shards, rate) cells: "
            + "; ".join(
                f"({h['n_shards']},{h['rate']}):"
                f"{h['affinity_misses']}<{h['static_misses']}"
                for h in new_fleet["headline"]
            )
        )

    return 0 if (
        new_speedup >= floor and warm_ok and overload_ok and fleet_ok
    ) else 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale dataset (slow)")
    ap.add_argument(
        "--only", default=None, metavar="BENCH[,BENCH...]",
        help="run a subset of {profiles,time,kernel,batch,hetero,policies,"
             "restore,online,overload,fleet} (comma-separated)",
    )
    ap.add_argument(
        "--record", nargs="?", const="BENCH_pr2.json", default=None,
        metavar="PATH",
        help="write a machine-readable snapshot of every bench that ran "
             "(default PATH: BENCH_pr2.json)",
    )
    ap.add_argument(
        "--baseline", default=None, metavar="PATH",
        help="compare the fresh snapshot against a checked-in one and exit "
             "nonzero on >25%% interpret solve-throughput regression",
    )
    ap.add_argument(
        "--obs", action="store_true",
        help="feed every timed serving cell into a repro.obs "
             "MetricsRegistry; with --record the snapshot gains an "
             "'obs_metrics' block (off by default so recorded bytes are "
             "unchanged)",
    )
    args = ap.parse_args()
    if args.obs:
        from repro.obs import MetricsRegistry

        global OBS_METRICS
        OBS_METRICS = MetricsRegistry()
    benches = {
        "profiles": bench_performance_profiles,
        "time": bench_time_to_solution,
        "kernel": bench_kernel_wavefront,
        "batch": bench_solve_batch,
        "hetero": bench_hetero_batch,
        "policies": bench_policy_backends,
        "restore": bench_tape_restore,
        "online": bench_online_serving,
        "overload": bench_overload_serving,
        "fleet": bench_fleet_serving,
    }
    selected = list(benches) if args.only is None else args.only.split(",")
    unknown = [s for s in selected if s not in benches]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from {list(benches)}")
    RESULTS.mkdir(exist_ok=True)
    print("name,us_per_call,derived")
    for name in benches:
        if name in selected:
            benches[name](args.full)
    if OBS_METRICS is not None:
        # key order: after every bench block, so obs-off records keep their
        # exact bytes and obs-on records only append
        RECORD["obs_metrics"] = OBS_METRICS.snapshot()
    if args.record:
        snapshot = {
            "schema": "ltsp-bench/pr2",
            "profile": "full" if args.full else "smoke",
            **RECORD,
        }
        pathlib.Path(args.record).write_text(json.dumps(snapshot, indent=1) + "\n")
        print(f"recorded {sorted(RECORD)} -> {args.record}")
    if args.baseline:
        sys.exit(check_baseline(RECORD, pathlib.Path(args.baseline)))


if __name__ == "__main__":
    main()
