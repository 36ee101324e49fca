"""Production serving launcher: batched greedy decoding with sharded caches,
optionally warm-started from the tape-archive tier.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
      --batch 8 --new-tokens 32

``--restore-from-tape`` simulates the cold-start path: the checkpoint shards
are archived to the tape library and the restore reads are ordered by an LTSP
solver from the registry (``--tape-policy``, any of
``repro.core.list_solvers()``; ``--tape-backend`` builds the
:class:`~repro.core.ExecutionContext` the planner runs under), reporting the
mean shard arrival time the serving fleet would observe before weights are
resident.

Online tape serving (``--serve-tape-queue``)
--------------------------------------------
The tape tier also serves *online*: read requests arrive while drives are
busy, so batch composition — and, with a shared
:class:`~repro.serving.drives.DrivePool`, *which cartridge each drive mounts
next* — is a scheduling decision, not a given.  This mode drives
:mod:`repro.serving.queue`: per-cartridge request queues, ``--tape-drives``
drives shared across all cartridges (default: one per cartridge), an
explicit mount cost model (``--tape-mount-cost`` / ``--tape-unmount-cost`` /
``--tape-load-seek``), and a pluggable **admission policy**:

* ``fifo`` / ``fifo-global`` — per-request solving in global arrival order
  (every request pays a full seek from the load point; the baseline);
* ``accumulate`` / ``per-drive-accumulate`` — accumulate-then-solve: a free
  drive mounts the cartridge whose oldest request has waited
  ``--tape-window`` time units and serves its whole queue (``0`` = greedy
  batching on drive-free);
* ``preempt`` — greedy batching plus preemptive re-solve: an arrival mid-batch
  aborts the in-flight plan, keeps already-served completions, rewinds, and
  re-solves the survivors together with the newcomer;
* ``batched`` — cross-cartridge device batching: all mount-ready cartridges
  in an event tick are planned via a **single** ``solve_batch`` bucketed
  launch;
* ``edf-global`` / ``slack-accumulate`` — the deadline-aware (QoS)
  admissions: earliest-deadline-first per-request serving, and
  accumulate-then-solve whose hold window collapses as a queued request's
  slack burns down.  They need deadlines on the trace: pass
  ``--tape-tightness`` to annotate the generated trace
  (:func:`repro.data.traces.qos_poisson_trace`) or replay a recorded one.

**Recorded traces & SLOs** — ``--trace-file PATH`` replays a JSONL trace
(:mod:`repro.data.traces`: arrival, tape, file, multiplicity, deadline,
class) instead of generating one; ``--record-trace PATH`` writes the trace
that was served (round-trips bit-exactly).  ``--tape-scheduler`` picks the
drive-eviction policy (``greedy`` / ``lru`` / ``lookahead``,
:data:`repro.serving.drives.MOUNT_SCHEDULERS`).  With deadlines present the
table gains deadline-miss columns, and ``--slo-target RATE`` turns the run
into a check: exit status 1 unless some swept admission meets the target
miss rate.

**Load-adaptive solver selection** — ``--tape-selector`` (any of
``repro.core.list_selectors()``: ``fixed`` / ``depth-threshold`` /
``cost-model``) lets the server re-pick the solve policy *each tick* from
queue depth and recorded per-tick solve timings instead of pinning
``--tape-policy`` for the whole run: exact DP when queues are shallow,
restricted DP / heuristics as depth grows.  ``--tape-budget CELLS`` sets
the per-tick DP cell budget the ``cost-model`` selector fits under
(:class:`~repro.core.ComputeBudget`).  The table gains a ``policy_mix``
column showing how many batches each policy actually planned.

**Warm starts & persistent caching** — re-solving admissions warm-start
each cartridge's DP from the previous tick's table by default
(bit-identical schedules, fewer DP cells evaluated; disable with
``--no-tape-warm`` to A/B the work counters).  ``--tape-cache-file PATH``
swaps the in-process solve memo for a persistent
:class:`~repro.core.JsonlCacheBackend`: re-running the launcher against the
same path replays the journal into memo hits, the restart story for a
serving fleet.

**Observability** — ``--tape-trace-out PATH`` attaches the opt-in
:class:`~repro.obs.Observability` bundle and exports the run's
virtual-time span log as byte-deterministic JSONL at ``PATH`` plus a
Chrome ``trace_event`` file at ``PATH + ".chrome.json"`` (one Perfetto
track per drive/queue/router, one process per fleet shard);
``--tape-metrics-out PATH`` writes the exact-int counter/histogram
registry as a Prometheus text snapshot whose sojourn/miss totals match
the printed report exactly.  Both record exactly one run (single
admission / single placement).  Leaving them unset attaches nothing:
timelines, journals, and tables are bit-identical to an uninstrumented
run.

**Fault injection & crash recovery** — ``--tape-fault-profile light|heavy``
injects a seeded :class:`~repro.serving.faults.FaultPlan` (drive hard-
failures, transient mount faults; ``heavy`` adds media read errors and
solver faults) with a ``--tape-retries``-deep retry/backoff budget per
fault site; the table gains completed/failed/requeued columns.
``--tape-journal PATH`` writes a write-ahead event journal; pointed at a
(possibly torn) journal from a crashed run it recovers bit-identically and
completes the log (single-admission runs only).

Fleet federation (``--serve-tape-fleet``)
-----------------------------------------
``--serve-tape-fleet`` scales the queue simulation out to a *federation*
(:mod:`repro.fleet`): ``--fleet-shards`` per-library shards serve one
arrival stream in shared exact virtual time, each logical file stored on
``--fleet-replicas`` shards, and ``--fleet-placement`` picks the routing
strategy (``single`` / ``static-hash`` / ``least-loaded`` /
``replica-affinity``; ``all`` sweeps every strategy valid for the shard
count).  ``--fleet-outage-at T`` (with ``--fleet-outage-shard I``) injects
a :class:`~repro.serving.faults.ShardOutage` — shard ``I`` goes dark at
``T``, its orphaned requests re-route to surviving replicas — and the
printed table compares placements on served/failed/rerouted counts,
service times, and deadline misses.  The federation configuration rides
the :class:`~repro.core.ExecutionContext` as
:class:`~repro.core.FleetOptions`.

Every emitted schedule is validated by the **simulator oracle**
(:mod:`repro.serving.sim` via :func:`repro.core.verify.verify_schedule`): the
discrete-event replay independently recomputes the schedule's cost from the
materialised head trajectory and must match the solver-reported cost exactly
(integer arithmetic).  The printed table compares admission policies on one
seeded arrival trace: mean/p50/p95 service time (sojourn), batches,
preemptions, mounts, solve-cache hits, and exact DP cells
evaluated/reused.  ``--tape-admission all`` sweeps every policy.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, reduced
from ..core.context import ComputeBudget
from ..core.solver import (
    BACKENDS,
    DEFAULT_BACKEND,
    ExecutionContext,
    list_selectors,
    list_solvers,
)
from ..distributed.context import set_active_mesh
from ..distributed.sharding import cache_pspecs, param_pspecs, to_shardings
from ..kernels.compile_cache import enable_compile_cache
from ..models.model import init_cache, init_model
from ..serving.serve import make_serve_step
from .train import _auto_mesh


def _restore_from_tape(params, policy: str, backend: str) -> None:
    """Archive ``params`` to a simulated tape library and plan the restore.

    The library context owns a :class:`~repro.core.SolveCache`, so the
    re-plan a recovering serving fleet issues for the *same* archive (every
    cold start requests the identical shard multiset per cartridge) never
    re-solves a tape — the second pass below is all cache hits and its time
    is the pure memo-lookup cost.
    """
    from ..core.solver import SolveCache
    from ..distributed.checkpoint import archive_to_tape, plan_restore
    from ..storage.tape import TapeLibrary

    ctx = ExecutionContext(backend=backend, cache=SolveCache())
    lib = TapeLibrary(capacity_per_tape=4 * 10**6, u_turn=20_000, context=ctx)
    shards = archive_to_tape(lib, "serve-warmup", params, bytes_per_elem=1)
    consumers = {s: 2 for s in shards}  # every host group needs every shard
    t0 = time.time()
    try:
        plans = plan_restore(lib, shards, consumers, policy=policy)
    except ValueError as e:
        # unsupported policy/backend combo or the int32 device-DP magnitude
        # guard — cold-start planning must not kill the serving launcher
        print(f"tape restore [{policy}/{backend}] unavailable: {e}\n"
              f" -> falling back to backend='python'")
        backend = "python"
        ctx.cache.clear()  # drop the failed attempt's miss counts
        ctx = ctx.replace(backend=backend)
        plans = plan_restore(lib, shards, consumers, policy=policy, context=ctx)
    dt = time.time() - t0
    # warm re-plan: what the next cold start in the fleet pays
    t0 = time.time()
    plan_restore(lib, shards, consumers, policy=policy, context=ctx)
    dt_warm = time.time() - t0
    n_req = sum(consumers.values())
    mean = sum(p.total_cost for p in plans) / n_req
    last = max(max(p.service_time.values()) for p in plans)
    stats = ctx.cache.stats()
    print(
        f"tape restore [{policy}/{backend}]: {len(shards)} shards on "
        f"{len(lib.tapes)} tape(s), mean arrival {mean:.3g}, last {last:.3g} "
        f"(planned in {dt * 1e3:.0f} ms; re-plan {dt_warm * 1e3:.0f} ms, "
        f"cache {stats['hits']} hits / {stats['misses']} misses)"
    )


def _export_obs(obs, args) -> None:
    """Write the observability exporters a run's flags asked for.

    JSONL + Chrome trace to ``--tape-trace-out`` (the Chrome file rides
    next to the span log at ``PATH + ".chrome.json"``), Prometheus text
    to ``--tape-metrics-out``.  Shared by the queue and fleet modes.
    """
    from ..obs.export import write_chrome_trace, write_prometheus, write_spans_jsonl

    if args.tape_trace_out:
        n = write_spans_jsonl(obs.tracer, args.tape_trace_out)
        chrome = args.tape_trace_out + ".chrome.json"
        write_chrome_trace(obs.tracer, chrome)
        print(f"trace: {n} span(s) -> {args.tape_trace_out} (+ {chrome})")
    if args.tape_metrics_out:
        write_prometheus(obs.metrics, args.tape_metrics_out)
        print(f"metrics -> {args.tape_metrics_out}")


def _serve_tape_queue(args) -> int:
    """Drive the online tape-serving subsystem on one arrival trace.

    The trace is either replayed from a recorded JSONL file
    (``--trace-file``), generated with deadline/class annotations
    (``--tape-tightness``), or the plain seeded Poisson-like trace; each
    requested admission policy serves it on a shared drive pool under the
    chosen mount scheduler, and the per-policy service-time table is
    printed (with deadline-miss columns when the trace carries deadlines).
    Every dispatched schedule passes the simulator oracle (see the module
    docstring); the run is bit-deterministic given ``--tape-seed`` (or the
    trace file).  Returns a shell exit code: nonzero iff ``--slo-target``
    is set and no swept admission met it.
    """
    from ..data.traces import (
        qos_poisson_trace,
        read_trace,
        records_of,
        to_requests,
        write_trace,
    )
    from ..serving.drives import DriveCosts, RetryPolicy
    from ..serving.faults import recover_server, seeded_fault_plan
    from ..serving.queue import ADMISSIONS, WINDOWED_ADMISSIONS, serve_trace
    from ..serving.sim import demo_library, poisson_trace

    def build_library():
        return demo_library(args.tape_seed, n_files=args.tape_files)

    qos = {}
    if args.trace_file:
        if args.tape_tightness is not None:
            print("--trace-file replays recorded deadlines; it cannot be "
                  "combined with --tape-tightness")
            return 2
        records = read_trace(args.trace_file)
        trace, qos = to_requests(records, build_library())
        source = args.trace_file
    elif args.tape_tightness is not None:
        records = qos_poisson_trace(
            build_library(),
            n_requests=args.tape_requests,
            mean_interarrival=args.tape_rate,
            seed=args.tape_seed,
            tightness=args.tape_tightness,
        )
        trace, qos = to_requests(records, build_library())
        source = f"generated (tightness {args.tape_tightness})"
    else:
        trace = poisson_trace(
            build_library(),
            n_requests=args.tape_requests,
            mean_interarrival=args.tape_rate,
            seed=args.tape_seed,
        )
        records = None  # only materialised if the trace is being recorded
        source = "generated (best-effort)"
    if args.record_trace:
        if records is None:
            records = records_of(trace)
        write_trace(args.record_trace, records)
        print(f"recorded {len(records)} trace record(s) -> {args.record_trace}")
    admissions = (
        list(ADMISSIONS) if args.tape_admission == "all" else [args.tape_admission]
    )
    if args.tape_journal and len(admissions) != 1:
        print("--tape-journal records exactly one run; pick a single "
              "--tape-admission")
        return 2
    obs = None
    if args.tape_trace_out or args.tape_metrics_out:
        if len(admissions) != 1:
            print("--tape-trace-out/--tape-metrics-out record exactly one "
                  "run; pick a single --tape-admission")
            return 2
        from ..obs import Observability

        obs = Observability.enabled()
    costs = DriveCosts(
        mount=args.tape_mount_cost,
        unmount=args.tape_unmount_cost,
        load_seek=args.tape_load_seek,
    )
    n_drives = args.tape_drives  # None = one per cartridge (the PR-3 model)
    faults = None
    retry = None
    if args.tape_fault_profile != "off":
        pool_size = n_drives if n_drives else len(build_library().tapes)
        heavy = args.tape_fault_profile == "heavy"
        faults = seeded_fault_plan(
            build_library(), trace, seed=args.tape_seed, n_drives=pool_size,
            drive_failures=2 if heavy else 1,
            mount_faults=1,
            media_faults=1 if heavy else 0,
            solver_faults=2 if heavy else 0,
            backend=args.tape_backend,
        )
        # drop (typed FailedRequest rows) rather than raise: the table below
        # reports completion per admission instead of dying on the first run
        retry = RetryPolicy(max_attempts=args.tape_retries, on_exhausted="drop")
        print(
            f"fault profile {args.tape_fault_profile}: "
            f"{len(faults.drive_failures)} drive failure(s), "
            f"{len(faults.mount_faults)} mount fault(s), "
            f"{len(faults.media_faults)} media fault(s), "
            f"{len(faults.solver_faults)} solver fault(s); "
            f"{args.tape_retries} retr{'y' if args.tape_retries == 1 else 'ies'} "
            f"per fault site"
        )
    journal = None
    if args.tape_cache_file:
        from ..core.cache import JsonlCacheBackend

        journal = JsonlCacheBackend(args.tape_cache_file)
        print(
            f"persistent solve memo: {args.tape_cache_file} "
            f"({journal.loaded} journaled solve(s) replayed)"
        )
    print(
        f"online tape serving: {len(trace)} requests ({source}), "
        f"{len({r.tape_id for r in trace})} cartridge(s), "
        f"{n_drives if n_drives else 'dedicated'} drive(s), "
        f"scheduler {args.tape_scheduler}, policy {args.tape_policy}/"
        f"{args.tape_backend}, warm start "
        f"{'off' if args.no_tape_warm else 'on'}"
        + (f", selector {args.tape_selector}"
           f"{f' (budget {args.tape_budget} cells/tick)' if args.tape_budget else ''}"
           if args.tape_selector else "")
    )
    deadline_cols = ",missed,miss_rate" if qos else ""
    fault_cols = ",completed,failed,requeued" if faults is not None else ""
    selector_cols = ",policy_mix" if args.tape_selector else ""
    print("admission,window,mean_sojourn,p50_sojourn,p95_sojourn,batches,"
          f"preempts,mounts,cache_hits,cells,reused"
          f"{deadline_cols}{fault_cols}{selector_cols}")
    best_miss_rate = None
    for admission in admissions:
        lib = build_library()
        ctx = lib.context.replace(backend=args.tape_backend)
        if obs is not None:
            ctx = ctx.replace(obs=obs)
        if journal is not None:
            ctx = ctx.replace(cache=journal)
        if args.tape_budget is not None:
            ctx = ctx.replace(budget=ComputeBudget(per_tick=args.tape_budget))
        common = dict(
            window=args.tape_window if admission in WINDOWED_ADMISSIONS else 0,
            policy=args.tape_policy,
            selector=args.tape_selector,
            n_drives=n_drives,
            drive_costs=costs,
            qos=qos or None,
            mount_scheduler=args.tape_scheduler,
            context=ctx,
            warm_start=not args.no_tape_warm,
            faults=faults,
            retry=retry,
        )
        t0 = time.time()
        if args.tape_journal and os.path.exists(args.tape_journal) \
                and os.path.getsize(args.tape_journal) > 0:
            report = recover_server(
                lib, trace, args.tape_journal, admission=admission, **common
            )
            print(f"recovered from journal {args.tape_journal}")
        else:
            report = serve_trace(
                lib, trace, admission, journal=args.tape_journal, **common
            )
        dt = time.time() - t0
        s = report.summary()  # oracle runs per dispatch: a failure raised above
        extra = ""
        if qos:
            extra = f",{s['n_missed']}/{s['n_deadlines']},{s['miss_rate']:.3f}"
            best_miss_rate = (
                s["miss_rate"]
                if best_miss_rate is None
                else min(best_miss_rate, s["miss_rate"])
            )
        if faults is not None:
            extra += (
                f",{report.n_served}/{len(trace)},{report.n_failed},"
                f"{s['faults']['requeued']}"
            )
        if args.tape_selector:
            extra += "," + "+".join(
                f"{p}:{n}" for p, n in sorted(s["policy_mix"].items())
            )
        print(
            f"{admission},{s['window']},{s['mean_sojourn']:.4g},"
            f"{s['p50_sojourn']:.4g},{s['p95_sojourn']:.4g},{s['n_batches']},"
            f"{s['n_preemptions']},{s['mounts']},{s['cache']['hits']},"
            f"{s['cells_evaluated']},{s['cells_reused']}{extra} "
            f"({dt*1e3:.0f} ms wall)"
        )
    if journal is not None:
        journal.close()
    if obs is not None:
        _export_obs(obs, args)
    if args.slo_target is not None:
        if not any(s.deadline is not None for s in qos.values()):
            print("--slo-target needs a deadline-annotated trace "
                  "(--tape-tightness or --trace-file with deadlines)")
            return 2
        ok = best_miss_rate is not None and best_miss_rate <= args.slo_target
        print(
            f"SLO {'PASS' if ok else 'FAIL'}: best miss rate "
            f"{best_miss_rate:.3f} vs target {args.slo_target:.3f}"
        )
        return 0 if ok else 1
    return 0


def _serve_tape_fleet(args) -> int:
    """Drive the fleet federation on one federation-wide arrival trace.

    Builds a seeded ``--fleet-shards``-shard archive with
    ``--fleet-replicas``-way replication, generates one trace over the
    unified catalogue, and serves it under each requested placement
    strategy (fresh shard libraries per run, so runs never share state).
    The federation configuration rides the
    :class:`~repro.core.ExecutionContext` as
    :class:`~repro.core.FleetOptions` — ``serve_fleet_trace`` reads the
    placement from there.  Deterministic given ``--tape-seed``.
    """
    from ..core.context import FleetOptions
    from ..core.solver import SolveCache
    from ..data.traces import qos_poisson_trace, to_requests
    from ..fleet import demo_fleet, fleet_catalog, serve_fleet_trace
    from ..serving.drives import DriveCosts, RetryPolicy
    from ..serving.faults import ShardOutage
    from ..serving.queue import WINDOWED_ADMISSIONS
    from ..serving.sim import poisson_trace

    n_shards = args.fleet_shards
    if n_shards < 1:
        print("--fleet-shards must be >= 1")
        return 2
    if not (1 <= args.fleet_replicas <= n_shards):
        print("--fleet-replicas must be between 1 and --fleet-shards")
        return 2
    if args.fleet_placement == "all":
        placements = (
            ["single"]
            if n_shards == 1
            else ["static-hash", "least-loaded", "replica-affinity"]
        )
    else:
        placements = [args.fleet_placement]
    if "single" in placements and n_shards != 1:
        print("placement 'single' is the one-shard NoOp default; pick a "
              "routing strategy (or --fleet-shards 1)")
        return 2
    obs = None
    if args.tape_trace_out or args.tape_metrics_out:
        if len(placements) != 1:
            print("--tape-trace-out/--tape-metrics-out record exactly one "
                  "run; pick a single --fleet-placement")
            return 2
        from ..obs import Observability

        obs = Observability.enabled()

    def build_fleet():
        return demo_fleet(
            args.tape_seed,
            n_shards=n_shards,
            n_files=args.tape_files,
            replicas=args.fleet_replicas,
            with_cache=False,  # the run's shared memo lives on the context
        )

    libs, rmap = build_fleet()
    catalog = fleet_catalog(libs, rmap)
    qos = {}
    if args.tape_tightness is not None:
        records = qos_poisson_trace(
            catalog,
            n_requests=args.tape_requests,
            mean_interarrival=args.tape_rate,
            seed=args.tape_seed,
            tightness=args.tape_tightness,
        )
        trace, qos = to_requests(records)
    else:
        trace = poisson_trace(
            catalog,
            n_requests=args.tape_requests,
            mean_interarrival=args.tape_rate,
            seed=args.tape_seed,
        )
    outages = ()
    retry = None
    if args.fleet_outage_at is not None:
        if not (0 <= args.fleet_outage_shard < n_shards):
            print("--fleet-outage-shard must name a shard in the fleet")
            return 2
        outages = (ShardOutage(at=args.fleet_outage_at,
                               shard=args.fleet_outage_shard),)
        # drop (typed FailedRequest rows) rather than raise when a dark
        # shard strands replicas-of-one requests: the table compares
        # placements on completion instead of dying on the first run
        retry = RetryPolicy(on_exhausted="drop")
    admission = (
        "accumulate" if args.tape_admission == "all" else args.tape_admission
    )
    costs = DriveCosts(
        mount=args.tape_mount_cost,
        unmount=args.tape_unmount_cost,
        load_seek=args.tape_load_seek,
    )
    print(
        f"fleet serving: {n_shards} shard(s) x "
        f"{args.tape_drives if args.tape_drives else 'dedicated'} drive(s), "
        f"{args.fleet_replicas}-way replicas, {len(trace)} requests, "
        f"admission {admission}, policy {args.tape_policy}/{args.tape_backend}"
        + (f", outage: shard {args.fleet_outage_shard} at "
           f"{args.fleet_outage_at}" if outages else "")
    )
    deadline_cols = ",missed,miss_rate" if qos else ""
    print(f"placement,served,failed,rerouted,mean_sojourn,p95_sojourn,"
          f"mounts{deadline_cols}")
    for pl in placements:
        libs, rmap = build_fleet()
        ctx = ExecutionContext(
            backend=args.tape_backend,
            cache=SolveCache(),
            fleet=FleetOptions(
                n_shards=n_shards, placement=pl, replicas=args.fleet_replicas
            ),
            obs=obs,
        )
        t0 = time.time()
        fr = serve_fleet_trace(
            libs,
            trace,
            admission,
            replica_map=rmap,
            outages=outages,
            window=(
                args.tape_window if admission in WINDOWED_ADMISSIONS else 0
            ),
            policy=args.tape_policy,
            n_drives=args.tape_drives,
            drive_costs=costs,
            qos=qos or None,
            context=ctx,
            warm_start=not args.no_tape_warm,
            retry=retry,
        )
        dt = time.time() - t0
        s = fr.summary()
        extra = ""
        if qos:
            extra = f",{s['n_missed']}/{s['n_deadlines']},{s['miss_rate']:.3f}"
        print(
            f"{pl},{fr.n_served}/{len(trace)},{fr.n_failed},{fr.n_rerouted},"
            f"{s['mean_sojourn']:.4g},{s['p95_sojourn']:.4g},{s['mounts']}"
            f"{extra} ({dt*1e3:.0f} ms wall; routes "
            + "/".join(str(fr.routes[i]) for i in range(n_shards))
            + ")"
        )
    if obs is not None:
        _export_obs(obs, args)
    return 0


def main() -> None:
    from ..serving.drives import MOUNT_SCHEDULERS
    from ..serving.queue import ADMISSIONS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh", default="auto", choices=["auto", "pod", "multipod"])
    ap.add_argument("--restore-from-tape", action="store_true",
                    help="simulate an LTSP-scheduled checkpoint restore first")
    ap.add_argument("--tape-policy", default="dp", choices=list_solvers())
    ap.add_argument("--tape-backend", default=DEFAULT_BACKEND, choices=list(BACKENDS))
    ap.add_argument("--serve-tape-queue", action="store_true",
                    help="run the online tape-serving queue simulation "
                         "(admission-policy comparison) instead of model serving")
    ap.add_argument("--tape-admission", default="all",
                    choices=[*ADMISSIONS, "all"])
    ap.add_argument("--serve-tape-fleet", action="store_true",
                    help="run the sharded fleet-federation simulation "
                         "(placement-strategy comparison) instead of model "
                         "serving")
    ap.add_argument("--fleet-shards", type=int, default=3, metavar="N",
                    help="per-library shards in the federation")
    ap.add_argument("--fleet-placement", default="all",
                    choices=["single", "static-hash", "least-loaded",
                             "replica-affinity", "all"],
                    help="replica routing strategy ('all' sweeps every "
                         "strategy valid for the shard count)")
    ap.add_argument("--fleet-replicas", type=int, default=2, metavar="K",
                    help="shards each logical file is replicated on")
    ap.add_argument("--fleet-outage-at", type=int, default=None, metavar="T",
                    help="inject a ShardOutage (whole shard dark) at this "
                         "virtual time")
    ap.add_argument("--fleet-outage-shard", type=int, default=0, metavar="I",
                    help="shard the injected outage darkens")
    ap.add_argument("--tape-selector", default=None,
                    choices=list_selectors(),
                    help="load-adaptive solver selection: re-pick the solve "
                         "policy each tick from queue depth / recorded solve "
                         "timings (unset = pin --tape-policy, bit-identical "
                         "to previous behaviour)")
    ap.add_argument("--tape-budget", type=int, default=None, metavar="CELLS",
                    help="per-tick DP cell budget the 'cost-model' selector "
                         "fits under (repro.core.ComputeBudget.per_tick)")
    ap.add_argument("--tape-scheduler", default="greedy",
                    choices=sorted(MOUNT_SCHEDULERS),
                    help="drive-pool mount/eviction scheduler")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="replay a recorded JSONL trace (repro.data.traces) "
                         "instead of generating one")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="write the served trace as JSONL (round-trips "
                         "bit-exactly through --trace-file)")
    ap.add_argument("--tape-tightness", type=int, default=None,
                    help="annotate the generated trace with deadlines: "
                         "deadline = arrival + tightness * class slack "
                         "multiplier (enables the QoS admissions)")
    ap.add_argument("--slo-target", type=float, default=None, metavar="RATE",
                    help="deadline-miss-rate target; exit 1 unless some "
                         "swept admission meets it")
    ap.add_argument("--no-tape-warm", action="store_true",
                    help="disable warm-started re-solves (bit-identical "
                         "schedules either way; cold re-solves every tick)")
    ap.add_argument("--tape-cache-file", default=None, metavar="PATH",
                    help="persist the solve memo to a JSONL journal "
                         "(replayed on the next run against the same path)")
    ap.add_argument("--tape-fault-profile", default="off",
                    choices=["off", "light", "heavy"],
                    help="inject a seeded fault plan into the serving run: "
                         "'light' = 1 drive failure + 1 transient mount "
                         "fault, 'heavy' adds media + solver faults "
                         "(deterministic given --tape-seed)")
    ap.add_argument("--tape-retries", type=int, default=3, metavar="N",
                    help="retry budget per fault site (mount attempts, media "
                         "read attempts, solver attempts per backend tier); "
                         "exhausted budgets drop requests as typed failures")
    ap.add_argument("--tape-trace-out", default=None, metavar="PATH",
                    help="attach the observability tracer and export the "
                         "virtual-time span log as JSONL at PATH plus a "
                         "Chrome trace_event file at PATH + '.chrome.json' "
                         "(single-admission/-placement runs only)")
    ap.add_argument("--tape-metrics-out", default=None, metavar="PATH",
                    help="attach the observability metrics registry and "
                         "export a Prometheus text snapshot at PATH "
                         "(single-admission/-placement runs only)")
    ap.add_argument("--tape-journal", default=None, metavar="PATH",
                    help="write-ahead event journal; if PATH already holds a "
                         "(possibly torn) journal from a crashed run, the "
                         "run recovers from it bit-identically")
    ap.add_argument("--tape-window", type=int, default=400_000,
                    help="accumulate-then-solve re-plan window (virtual time)")
    ap.add_argument("--tape-drives", type=int, default=None,
                    help="shared drive-pool size (default: one per cartridge)")
    ap.add_argument("--tape-mount-cost", type=int, default=0,
                    help="cost of threading a cartridge into a drive")
    ap.add_argument("--tape-unmount-cost", type=int, default=0,
                    help="cost of ejecting the cartridge a drive holds")
    ap.add_argument("--tape-load-seek", type=int, default=0,
                    help="seek from thread point to load point after mounting")
    ap.add_argument("--tape-rate", type=int, default=250_000,
                    help="mean request inter-arrival time (virtual time)")
    ap.add_argument("--tape-requests", type=int, default=300)
    ap.add_argument("--tape-files", type=int, default=40)
    ap.add_argument("--tape-seed", type=int, default=20260731)
    args = ap.parse_args()
    enable_compile_cache()

    if args.serve_tape_queue:
        raise SystemExit(_serve_tape_queue(args))
    if args.serve_tape_fleet:
        raise SystemExit(_serve_tape_fleet(args))

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduced(cfg, periods=2)
        cfg = dataclasses.replace(cfg, vocab_size=min(cfg.vocab_size, 32768))

    mesh = _auto_mesh(args.mesh)
    set_active_mesh(mesh)
    max_len = args.prompt_len + args.new_tokens

    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.restore_from_tape:
        _restore_from_tape(params, args.tape_policy, args.tape_backend)
    params = jax.device_put(params, to_shardings(param_pspecs(params), mesh, params))
    cache = init_cache(cfg, args.batch, max_len=max_len)
    cache = jax.device_put(cache, to_shardings(cache_pspecs(cache, mesh), mesh))

    serve = jax.jit(make_serve_step(cfg))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    with mesh:
        for t in range(args.prompt_len - 1):  # teacher-forced prefill
            _, _, cache = serve(params, cache, prompts[:, t : t + 1], jnp.int32(t))
        tok = prompts[:, -1:]
        t0 = time.time()
        outs = []
        for t in range(args.new_tokens):
            tok, _, cache = serve(params, cache, tok, jnp.int32(args.prompt_len - 1 + t))
            outs.append(np.asarray(tok))
        jax.block_until_ready(tok)
    dt = time.time() - t0
    set_active_mesh(None)
    print(f"{cfg.arch_id}: {args.batch}x{args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch*args.new_tokens/dt:.0f} tok/s)")
    print("first sequence:", np.concatenate(outs, 1)[0][:16].tolist(), "...")


if __name__ == "__main__":
    main()
