"""Smoke test of the compiled LTSP wavefront and the serving loop on one TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py

It runs three phases through the entry points a user calls, each printing
its sizes, the cuts made, compile and steady wall times, and its checks:

* A: one paper-median tape (bucket R=256, S=4096, B=1) solved with
  ``solve(inst, "dp", context=ExecutionContext(backend="pallas"))``; the cost
  must equal the detours' evaluated cost, pass the schedule oracle, and be no
  worse than NFGS and GS.
* B: small seeded tapes from the same profile, several buckets with B > 1,
  once per U-turn value of paper section 5.3 and per device-capable policy,
  each set in one ``solve_batch`` on ``"pallas"``, plus the exact DP through
  ``ops.ltsp_solve_batch`` with a 16-row candidate tile, so the banded scan
  runs in more chunks; every ``(cost, detours)`` must equal the exact Python
  DP (``repro.core.dp``).
* C: ``serve_trace`` with the ``batched`` admission on a two-drive pool; the
  served timeline must equal the ``"python"`` run exactly, and the kernel
  profile must show device launches and none in interpret mode.

The last line of standard output is one JSON object naming the device.  Any
failed check exits non-zero before it.  With no TPU the script exits non-zero
at once and prints no result.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

BACKEND = "pallas"
#: phase A's bucket (R, S): the bucket of a tape at the paper's medians.
MEDIAN_BUCKET = (256, 4096)
#: phase B's tapes: n_req at most this, at most PER_BUCKET per bucket.
MAX_N_REQ = 40
PER_BUCKET = 2

#: tape grain for phases A and B: 2**15 units per 20 TB tape (~610 MB),
#: instead of the generator's 1 MB; see ``CUT_GRAIN``.
TAPE_CAPACITY = 2**15
CUT_GRAIN = (
    f"tape_capacity={TAPE_CAPACITY} units per 20 TB tape (~610 MB grain) "
    "instead of 1 MB: at 1 MB no PAPER_PROFILE tape passes the int32 guard"
)
#: the served-path workload of ``repro.launch.serve --serve-tape-queue
#: --tape-requests 150 --tape-drives 2 --tape-mount-cost 150000``, with the
#: accumulate window cut to 0; see ``CUT_WINDOW``.
SERVE = dict(seed=20260731, n_files=40, requests=150, rate=250_000, drives=2,
             mount=150_000, window=0)
CUT_WINDOW = (
    "window 0 (dispatch on drive-free) instead of the launcher's 400000: "
    "at 400000 a 35-request batch on the byte-grain demo cartridges fails "
    "the int32 guard"
)


def check(ok: bool, what: str) -> None:
    """Exit non-zero, naming the check, unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")
    print(f"    check passed: {what}")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _dataset(u_turn: int = 0):
    from repro.data.generator import PAPER_PROFILE, generate_dataset

    prof = dataclasses.replace(PAPER_PROFILE, tape_capacity=TAPE_CAPACITY)
    return generate_dataset(prof, u_turn=u_turn)


def _u_turns() -> dict[str, int]:
    from repro.data.generator import u_turn_values

    return u_turn_values(_dataset())


def phase_a() -> None:
    """One paper-median tape, B = 1, through ``solve``."""
    from repro.core import ExecutionContext, evaluate_detours, solve
    from repro.core.verify import verify_schedule
    from repro.kernels.ltsp_dp.ops import _int32_admits, bucket_shape, rescale_instance

    u = _u_turns()["full_seg"]
    tapes = _dataset(u)
    idx = next(
        i for i, t in enumerate(tapes)
        if bucket_shape(t) == MEDIAN_BUCKET
        and _int32_admits(rescale_instance(t)[0])
    )
    inst = tapes[idx]
    R, S = MEDIAN_BUCKET
    print(f"[A] paper median bucket: tape {idx} of PAPER_PROFILE, n_req="
          f"{inst.n_req}, n={inst.n}, U={u} -> B=1, R={R}, S={S} "
          f"(T, Tc and C {R * R * S * 4 / 2**30:.3g} GiB each)")
    print(f"[A] cuts: {CUT_GRAIN}; one tape (B=1)")
    ctx = ExecutionContext(backend=BACKEND)
    first, t_first = _timed(lambda: solve(inst, "dp", context=ctx))
    steady = []
    for _ in range(2):
        res, dt = _timed(lambda: solve(inst, "dp", context=ctx))
        steady.append(dt)
        check((res.cost, res.detours) == (first.cost, first.detours),
              "repeat solve returns the same (cost, detours)")
    print(f"[A] first solve (includes compile) {t_first:.3f} s; steady solves "
          f"{', '.join(f'{t:.3f}' for t in steady)} s; compile ~ "
          f"{t_first - min(steady):.3f} s")
    cost, dets = first.cost, first.detours
    print(f"[A] cost {cost}, {len(dets)} detours")
    check(evaluate_detours(inst, dets) == cost, "evaluate_detours == cost")
    check(verify_schedule(inst, dets, cost) == cost, "verify_schedule oracle")
    for h in ("nfgs", "gs"):
        hc = solve(inst, h).cost
        check(cost <= hc, f"dp cost {cost} <= {h} cost {hc}")


def _phase_b_tapes() -> list[int]:
    from repro.kernels.ltsp_dp.ops import bucket_shape

    by_bucket: dict[tuple[int, int], list[int]] = {}
    for i, t in enumerate(_dataset()):
        if t.n_req <= MAX_N_REQ:
            by_bucket.setdefault(bucket_shape(t), []).append(i)
    return sorted(i for ids in by_bucket.values() for i in ids[:PER_BUCKET])


def phase_b() -> None:
    """Small tapes, B > 1 buckets, every U and device policy vs core/dp.py."""
    from repro.core import ExecutionContext, get_solver, list_solvers, solve_batch
    from repro.kernels.ltsp_dp import ops
    from repro.kernels.ltsp_dp.ops import bucket_shape

    idxs = _phase_b_tapes()
    base = _dataset()
    shapes: dict[tuple[int, int], int] = {}
    for i in idxs:
        b = bucket_shape(base[i])
        shapes[b] = shapes.get(b, 0) + 1
    policies = [p for p in list_solvers() if get_solver(p).supports_device]
    runs = [(p, None) for p in policies] + [("dp", 16)]
    print(f"[B] {len(idxs)} tapes with n_req <= {MAX_N_REQ} (tapes {idxs}); "
          f"buckets (R, S): instances "
          f"{', '.join(f'{k}: {v}' for k, v in sorted(shapes.items()))}")
    print(f"[B] cuts: {CUT_GRAIN}; n_req <= {MAX_N_REQ}, at most {PER_BUCKET} "
          f"tapes per bucket, so the Python reference finishes in minutes")
    t_first = t_steady = 0.0
    n_checked = 0
    for name, u in _u_turns().items():
        tapes = _dataset(u)
        insts = [tapes[i] for i in idxs]
        refs: dict[str, list] = {}
        for policy, tile in runs:
            if tile is None:
                ctx = ExecutionContext(backend=BACKEND)
                run = lambda: [(r.cost, r.detours)
                               for r in solve_batch(insts, policy, context=ctx)]
            else:
                run = lambda: ops.ltsp_solve_batch(insts, interpret=False, cand_tile=tile)
            got, dt1 = _timed(run)
            again, dt2 = _timed(run)
            t_first += dt1
            t_steady += dt2
            if policy not in refs:
                refs[policy] = [(r.cost, r.detours) for r in solve_batch(insts, policy)]
            check(
                got == refs[policy] and got == again,
                f"U={name}({u}) {policy}"
                f"{'' if tile is None else f' cand_tile={tile}'}: "
                f"{len(insts)} (cost, detours) == core/dp.py",
            )
            n_checked += len(insts)
    print(f"[B] {n_checked} device solves equal the reference; device "
          f"solve_batch wall: first calls (include compiles) {t_first:.3f} s, "
          f"steady repeats {t_steady:.3f} s")


def _served(report) -> str:
    """The served timeline, minus what names or counts the backend's work."""
    batches = [
        dataclasses.replace(b, cells_evaluated=0) for b in report.batches
    ]
    served = [(r, r.sojourn) for r in report.served]
    return repr((served, batches, report.failed, report.n_preemptions,
                 report.horizon, report.pool_stats, report.cache_stats))


def phase_c() -> None:
    """``serve_trace`` with the batched admission on a drive pool."""
    from repro.obs import KernelProfile, Observability
    from repro.serving.drives import DriveCosts
    from repro.serving.queue import serve_trace
    from repro.serving.sim import demo_library, poisson_trace

    c = SERVE
    trace = poisson_trace(demo_library(c["seed"], n_files=c["n_files"]),
                          n_requests=c["requests"],
                          mean_interarrival=c["rate"], seed=c["seed"])
    print(f"[C] serve_trace admission=batched: {len(trace)} requests, "
          f"{c['n_files']} files, {c['drives']} drives, mount cost "
          f"{c['mount']}, window {c['window']}, seed {c['seed']}")
    print(f"[C] cuts: {CUT_WINDOW}")

    def run(backend, obs=None):
        lib = demo_library(c["seed"], n_files=c["n_files"])
        ctx = lib.context.replace(backend=backend, obs=obs)
        return serve_trace(lib, trace, "batched", window=c["window"],
                           n_drives=c["drives"],
                           drive_costs=DriveCosts(mount=c["mount"]),
                           context=ctx)

    obs = Observability(kernel=KernelProfile())
    dev, t_first = _timed(lambda: run(BACKEND, obs))
    _, t_steady = _timed(lambda: run(BACKEND, Observability(kernel=KernelProfile())))
    ref = run("python")
    launches = obs.kernel.launches
    shapes = sorted({(r.B_pad, r.R_pad, r.S_pad) for r in launches})
    print(f"[C] {len(dev.served)} served in {len(dev.batches)} batches; "
          f"{len(launches)} device launches, bucket shapes (B, R, S) {shapes}")
    print(f"[C] first run (includes compiles) {t_first:.3f} s; steady run "
          f"{t_steady:.3f} s")
    check(_served(dev) == _served(ref),
          "served timeline == python backend (every request's dispatch, "
          "completion and sojourn, every batch record; backend name and "
          "per-backend cell counts excluded)")
    check(len(launches) >= 1, f"{len(launches)} device launches >= 1")
    n_interp = sum(r.interpret for r in launches)
    check(n_interp == 0, f"{n_interp} interpret-mode launches == 0")


def main() -> int:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); this "
              "script runs only on a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    phase_a()
    phase_b()
    phase_c()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
