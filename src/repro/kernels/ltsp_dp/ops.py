"""Host-side drivers for the Pallas LTSP wavefront: adapters, rescaling,
traceback, and a size-bucketed batch planner.

The device path is a **complete solver**: :func:`ltsp_dp_tables` (one jitted
wavefront, see :mod:`.ltsp_dp`) returns the value table *and* per-cell argmin
planes; :func:`~.walk.traceback_device` replays the argmin planes on the
device to reconstruct the optimal detour list, exactly like the Python DP's
traceback, so only the detours and root values cross to the host.
:func:`traceback_detours` is the same walk on a host copy of a plane, the
reference the device walk is tested against.

The device DP computes in ``int32`` only.  It is bit-exact when every cell
a reader takes is below ``2**30 - 1`` and every term the kernel forms at the
lanes those cells depend on below ``2**31 - 1`` (:func:`_int32_admits`): the
kernel clips its tables and saturates its sums instead of wrapping, so a
candidate sum may pass int32 and still lose the minimum exactly (see
:mod:`.ltsp_dp`, *Saturating sums*).  Before the :func:`_check_int32_safe`
guard runs, :func:`rescale_instance` shifts each instance to its leftmost
requested byte and divides all coordinates (and the U-turn penalty) by their
gcd — every DP term is a coordinate *difference*, so the whole table scales
by exactly ``1/g`` and the argmin structure (ties included) is untouched.
Real cartridge layouts share the tape's block granularity, so byte
coordinates far beyond int32 rescale into range.  The guard refuses the
genuinely coprime byte-scale layouts before anything is launched; the exact
``backend="python"`` solves them at any magnitude.

``disjoint=True`` routes SIMPLEDP through the same kernel: the candidate band
is clipped to root-level cells (no detour may start inside another), which
collapses the 3-D table to SIMPLEDP's 2-D recursion — same mechanism as the
LOGDP ``span`` clip, bit-identical to :func:`repro.core.dp.simpledp_schedule`
(cost *and* traceback).

Batching and the bucket planner
-------------------------------
Instances are right-padded with zero-width, zero-multiplicity phantom files at
the rightmost coordinate.  A phantom file's ``skip`` transition is free and
never loses to a detour (detours only add nonnegative terms there, and skip
wins ties), so neither the root value nor the traceback changes — several
tapes' instances solve in one device launch.

A single launch must share one ``(B, R, S)`` shape, so the seed driver padded
*every* instance to the global ``(R_max, S_max)`` — maximally wasteful on the
heterogeneous cartridge batches the IN2P3 logs actually produce.
:func:`plan_buckets` instead groups instances into a small set of shape
buckets and :func:`ltsp_solve_batch` launches one tight wavefront per bucket.

Bucket-rounding policy (applies to every padded dimension):

* ``R`` (requested files) rounds up to the next power of two;
* ``S`` (skip counts, ``n + 1``) rounds up to the next power-of-two multiple
  of 128 (the TPU lane width): 128, 256, 512, …;
* ``B`` (instances per launch) rounds up to the next power of two, padding
  with all-phantom rows that are never traced back.

Powers-of-two rounding bounds the set of distinct launch shapes
logarithmically, so repeated heterogeneous batches re-hit the ``jit`` cache
instead of retracing the wavefront for every novel ``(B, R, S)``; within a
bucket, padding waste is at most 2x per dimension instead of unbounded.
``ltsp_solve_batch([])`` returns ``[]`` and single-instance batches skip the
planner entirely (one tight launch, no grouping pass).
"""

from __future__ import annotations

import contextlib
import math

import jax.numpy as jnp
import numpy as np

from ...core.instance import Instance, virtual_lb
from ...core.warm import DenseStore, WarmState, WarmStats, align_warm, warm_from_instance
from .ltsp_dp import DEFAULT_CAND_TILE, INT32_CAP, band_copy_bytes, ltsp_dp_tables
from .walk import traceback_device

__all__ = [
    "prepare_batch",
    "plan_buckets",
    "bucket_shape",
    "rescale_instance",
    "traceback_detours",
    "ltsp_solve_instance",
    "ltsp_solve_batch",
    "ltsp_solve_instance_warm",
    "ltsp_solve_batch_warm",
]


#: the context every phase opens when no profile is attached: one shared
#: object, so an unprofiled solve builds no span
_NO_SPAN = contextlib.nullcontext()


def _span(profile, name: str, **args):
    """``profile.span(name, **args)``, or :data:`_NO_SPAN` without a profile."""
    return _NO_SPAN if profile is None else profile.span(name, **args)


def _pad_s(S: int) -> int:
    return int(math.ceil(S / 128) * 128)


def _pow2(v: int) -> int:
    """Smallest power of two >= v (v >= 1)."""
    return 1 << max(0, int(v) - 1).bit_length()


def bucket_shape(inst: Instance) -> tuple[int, int]:
    """``(R_pad, S_pad)`` shape bucket for one instance.

    See the module docstring for the rounding policy: ``R`` to the next power
    of two, ``S = n + 1`` to the next power-of-two multiple of 128.
    """
    return _pow2(inst.n_req), 128 * _pow2(-(-(inst.n + 1) // 128))


def plan_buckets(instances: list[Instance]) -> dict[tuple[int, int], list[int]]:
    """Group instance indices by shape bucket (insertion-ordered)."""
    buckets: dict[tuple[int, int], list[int]] = {}
    for i, inst in enumerate(instances):
        buckets.setdefault(bucket_shape(inst), []).append(i)
    return buckets


def prepare_batch(
    instances: list[Instance],
    R_pad: int | None = None,
    S_pad: int | None = None,
    B_pad: int | None = None,
):
    """Pack instances into padded ``[B, R]`` int32 arrays + shared ``S``.

    ``R_pad``/``S_pad``/``B_pad`` override the default tight padding (the
    batch maxima) — the bucket planner passes its power-of-two bucket shape so
    repeated launches share compiled programs.  File padding appends phantom
    files (zero width, zero multiplicity) at each instance's rightmost
    coordinate; batch padding appends all-phantom rows; see the module
    docstring for why both are result-preserving.
    """
    if not instances:
        raise ValueError("prepare_batch needs at least one instance")
    B = len(instances) if B_pad is None else max(B_pad, len(instances))
    R = max(i.n_req for i in instances) if R_pad is None else R_pad
    S = _pad_s(max(i.n for i in instances) + 1 if S_pad is None else S_pad)
    if R < max(i.n_req for i in instances):
        raise ValueError("R_pad smaller than the widest instance")
    if S_pad is not None and S_pad < max(i.n for i in instances) + 1:
        raise ValueError("S_pad smaller than the largest request count + 1")
    left = np.zeros((B, R), dtype=np.int64)
    right = np.zeros((B, R), dtype=np.int64)
    x = np.zeros((B, R), dtype=np.int64)
    u = np.zeros((B,), dtype=np.int64)
    for i, inst in enumerate(instances):
        r = inst.n_req
        left[i, :r] = inst.left
        right[i, :r] = inst.right
        left[i, r:] = inst.right[-1]
        right[i, r:] = inst.right[-1]
        x[i, :r] = inst.mult
        u[i] = inst.u_turn
    nl = np.concatenate(
        [np.zeros((B, 1), np.int64), np.cumsum(x, axis=1)[:, :-1]], axis=1
    )
    return (
        jnp.asarray(left, jnp.int32),
        jnp.asarray(right, jnp.int32),
        jnp.asarray(x, jnp.int32),
        jnp.asarray(nl, jnp.int32),
        jnp.asarray(u, jnp.int32),
        S,
    )


def rescale_instance(inst: Instance) -> tuple[Instance, int]:
    """Shift + gcd-reduce an instance for the int32 device table.

    Returns ``(scaled, g)`` with coordinates ``(coord - left[0]) // g`` where
    ``g = gcd`` of all shifted coordinates and the U-turn penalty.  Every DP
    term (base, skip, detour) is a linear combination of coordinate
    *differences* and ``U`` with scale-free integer coefficients, so the full
    table of ``scaled`` is exactly ``1/g`` times the original's and its argmin
    planes — the traceback — are identical.  Reconstruct original table values
    as ``g * T_scaled``.

    The scaled instance's ``m`` is set to its rightmost coordinate (the head
    start position never enters the device table — only *VirtualLB*, which the
    host computes from the original instance), which tightens the
    :func:`_check_int32_safe` bound to the requested span instead of the
    absolute tape length.
    """
    base = int(inst.left[0])
    g = 0
    for v in inst.left.tolist():
        g = math.gcd(g, v - base)
    for v in inst.right.tolist():
        g = math.gcd(g, v - base)
    g = math.gcd(g, inst.u_turn) or 1
    left = (inst.left - base) // g
    right = (inst.right - base) // g
    scaled = Instance(
        left=left,
        right=right,
        mult=inst.mult,
        m=int(right[-1]),
        u_turn=inst.u_turn // g,
    )
    return scaled, g


def _cell_bound(inst: Instance) -> int:
    """Bound on every cell value a reader takes: ``4 n m``.

    The readers are the traceback, from the root ``(0, R - 1, 0)`` down, and
    a warm start's :class:`~repro.core.warm.DenseStore`, which admits
    ``(a, b, s)`` when ``s + x_{a+1} + ... + x_b <= n``; the root's cone
    keeps that, and every cell reads only cells that keep it.
    ``T = min(skip, detours) <= skip``, so such a cell is at most its
    all-skip chain down to the base cell ``(a, a)``.  Skipping file ``k``
    adds ``2 (r_k - r_{k-1}) (s_k + n_l(a)) + 2 (l_k - r_{k-1}) x_k <=
    2 (r_k - r_{k-1}) (s_k + x_k + n_l(a))`` and the base cell is ``2 (r_a -
    l_a) (s_a + n_l(a))``, where ``s_k + x_k`` and ``s_a`` are at most ``n``
    by the condition above and ``n_l(a) <= n``.  So the chain telescopes to
    at most ``2 (n + n_l(a)) (r_b - l_a) <= 4 n m`` (``2 n m`` on the cells
    the root reaches, where ``s_a + n_l(a) <= n``).  Callers pass
    :func:`rescale_instance` output, so ``m`` is the gcd-reduced *requested
    span*.  Phantom padding files (zero width and multiplicity at the last
    coordinate) add nothing to a chain.
    """
    return 4 * inst.n * inst.m


def _term_bound(inst: Instance) -> int:
    """Bound on every term and product the kernel forms at a lane a reader
    depends on: ``4 n (m + U)``.

    A lane ``s`` reads only lanes ``>= s`` (the skip reads ``s + x_b``, the
    candidates the same ``s``), and every cell a reader takes (see
    :func:`_cell_bound`) has ``s <= n``; so the lanes ``s > n`` of a padded
    launch feed none of them, and their terms may wrap.  The largest term is
    a candidate's linear part ``2 (r_b - r_{c-1}) (s + n_l(a)) + 2 U (s +
    n_l(c))``, formed as ``2 (r_b - r_{c-1} + U) s + 2 ((r_b - r_{c-1})
    n_l(a) + U n_l(c))`` with ``s`` and every ``n_l`` at most ``n``; the base
    and skip terms are ``2 d (s + n_l)`` and ``2 d x_b`` with ``d <= m``.
    """
    return 4 * inst.n * (inst.m + inst.u_turn)


def _failed_bounds(inst: Instance) -> str:
    """The bounds of ``inst`` that reach their limit, named with their
    values; empty when the instance is admitted.  Cells must stay below the
    value the kernel's int32 tables clip to, terms below int32's largest
    value (``ltsp_dp`` docstring, *Saturating sums*)."""
    bounds = (("cell-value bound 4nm", _cell_bound(inst), INT32_CAP),
              ("term bound 4n (m + U)", _term_bound(inst), 2**31 - 1))
    return "; ".join(f"{name} = {v}, not below {lim}"
                     for name, v, lim in bounds if v >= lim)


def _int32_admits(inst: Instance) -> bool:
    """Whether the int32 wavefront solves ``inst`` exactly."""
    return not _failed_bounds(inst)


def _check_int32_safe(instances: list[Instance]) -> None:
    """Magnitude guard for the int32 table: raising means the instance
    genuinely overflows even at tape-block granularity (after gcd/shift
    rescaling)."""
    for inst in instances:
        failed = _failed_bounds(inst)
        if failed:
            raise ValueError(
                f"instance too large for the int32 device DP even after gcd "
                f"rescaling (m={inst.m}, n={inst.n}, R={inst.n_req}): "
                f"{failed}; rescale coordinates to a coarser grain, or solve "
                f"it exactly with backend='python'"
            )


def traceback_detours(choice: np.ndarray, mult: np.ndarray) -> list[tuple[int, int]]:
    """Replay an argmin plane ``choice[R, R, S]`` into the detour list.

    Iterative pre-order walk from the root cell ``(0, R-1, 0)``: ``-1`` means
    "skip b" (descend to ``(a, b-1, s + x_b)``), ``c`` means detour ``(c, b)``
    (emit it, descend into its inner structure ``(c, b, s)``, then resume with
    ``(a, c-1, s)``).  Matches the exact Python DP's emission order.  The
    solver runs the same walk on the device (:func:`~.walk.traceback_device`);
    this host copy is the reference the tests hold it to.
    """
    R = choice.shape[0]
    x = [int(v) for v in mult]
    detours: list[tuple[int, int]] = []
    work: list[tuple[int, int, int]] = [(0, R - 1, 0)]
    while work:
        a, b, s = work.pop()
        while a < b:
            c = int(choice[a, b, s])
            if c == -1:
                s += x[b]
                b -= 1
                continue
            detours.append((c, b))
            work.append((a, c - 1, s))
            a = c
    return detours


# ---------------------------------------------------------------------------
# solver entry points
# ---------------------------------------------------------------------------
def ltsp_solve_instance(
    inst: Instance,
    span: int | None = None,
    *,
    interpret: bool,
    cand_tile: int = DEFAULT_CAND_TILE,
    disjoint: bool = False,
    profile=None,
) -> tuple[int, list[tuple[int, int]]]:
    """Device-solved ``(opt_cost, detours)`` for one instance (exact)."""
    return ltsp_solve_batch([inst], span=span, interpret=interpret,
                            cand_tile=cand_tile, disjoint=disjoint,
                            profile=profile)[0]


def _solve_packed(
    originals: list[Instance],
    scaled: list[Instance],
    gs: list[int],
    R_pad: int | None,
    S_pad: int | None,
    B_pad: int | None,
    span: int | None,
    interpret: bool,
    cand_tile: int,
    disjoint: bool = False,
    capture: bool = False,
    profile=None,
) -> tuple[list[tuple[int, list[tuple[int, int]]]], list[DenseStore | None]]:
    """One padded device launch; results refer to the *original* instances.

    ``capture=True`` additionally snapshots each instance's dense value and
    argmin planes into a :class:`~repro.core.warm.DenseStore` (kept in the
    launch's gcd-rescaled units together with ``g``, so lookups reconstruct
    original-unit values with python-int arithmetic).

    The detours come from :func:`~.walk.traceback_device` on both paths: the
    tables stay on the device unless ``capture`` needs them on the host.

    With a ``profile`` the launch is recorded, and each phase runs inside
    its span (see :class:`~repro.obs.KernelProfile`): ``ltsp.pack``,
    ``ltsp.dispatch`` (returns before the device finishes; a cold shape
    traces and compiles here), ``ltsp.device_wait``, ``ltsp.fetch_argmin``
    (the device walk and the copy of its outputs, and of both planes when
    captured), ``ltsp.fetch_root`` (the root values' copy, started with the
    walk's), ``ltsp.traceback`` (detour lists, costs, ``DenseStore``s) and
    ``ltsp.release``, where the last references to the tables and the packed
    inputs go, so that freeing them is timed too.
    """
    with _span(profile, "ltsp.pack"):
        left, right, x, nl, u, S = prepare_batch(
            scaled, R_pad=R_pad, S_pad=S_pad, B_pad=B_pad
        )
        if profile is not None:
            h2d = sum(a.nbytes for a in (left, right, x, nl, u))
    B, R = left.shape
    with _span(profile, "ltsp.dispatch", R=R, S=S, B=B, span=span):
        T, C = ltsp_dp_tables(
            left, right, x, nl, u, S=S, span=span, disjoint=disjoint,
            interpret=interpret, cand_tile=cand_tile,
        )
    with _span(profile, "ltsp.device_wait"):
        C.block_until_ready()
    with _span(profile, "ltsp.fetch_argmin"):
        walked = traceback_device(T, C, x)
        for a in walked:
            a.copy_to_host_async()
        dets_host, n_dets, steps = (np.asarray(walked[k]) for k in (0, 1, 3))
        C_host = np.asarray(C) if capture else None
        T_host = np.asarray(T) if capture else None
    with _span(profile, "ltsp.fetch_root"):
        T_root = np.asarray(walked[2])
    with _span(profile, "ltsp.traceback"):
        out = []
        stores: list[DenseStore | None] = []
        for i, (inst, g) in enumerate(zip(originals, gs)):
            dets = list(map(tuple, dets_host[i, : n_dets[i]].tolist()))
            # padding only ever skips, so emitted detours stay within the
            # real files; guard the invariant anyway.
            assert all(b < inst.n_req for _, b in dets)
            # the scaled table is exactly 1/g of the original's (see
            # rescale_instance); VirtualLB comes from the original coordinates.
            cost = g * int(T_root[i]) + virtual_lb(inst)
            out.append((cost, dets))
            if capture:
                prefix = np.cumsum(inst.mult).tolist()
                stores.append(
                    DenseStore(T_host[i].copy(), C_host[i].copy(), g, inst.n, prefix)
                )
            else:
                stores.append(None)
    if profile is not None:
        d2h = dets_host.nbytes + n_dets.nbytes + T_root.nbytes + steps.nbytes
        if capture:
            d2h += C_host.nbytes + T_host.nbytes
    with _span(profile, "ltsp.release"):
        del C_host, T_host, T, C, walked, left, right, x, nl, u
    if profile is not None:
        profile.record(
            signature=(R, S, B, interpret, span, disjoint, cand_tile),
            n_instances=len(scaled),
            R_pad=R,
            S_pad=S,
            B_pad=B,
            real_cells=sum(s.n_req * s.n_req * (s.n + 1) for s in scaled),
            interpret=interpret,
            h2d_bytes=h2d,
            d2h_bytes=d2h,
            walk_steps=int(steps.sum()),
            operand_bytes=band_copy_bytes(
                B, R, S, np.dtype(np.int32).itemsize, span, disjoint, cand_tile
            ),
        )
    return out, stores


def ltsp_solve_batch(
    instances: list[Instance],
    span: int | None = None,
    *,
    interpret: bool,
    bucketed: bool = True,
    cand_tile: int = DEFAULT_CAND_TILE,
    disjoint: bool = False,
    capture: bool = False,
    profile=None,
) -> list[tuple[int, list[tuple[int, int]]]]:
    """Solve several instances in a few size-bucketed device launches.

    Returns one ``(opt_cost, detours)`` per instance, in order.  ``opt_cost``
    is ``g * T[0, R_pad-1, 0] + VirtualLB`` taken from the gcd-rescaled int32
    device table — exact under the :func:`_check_int32_safe` bounds; detour
    indices refer to each instance's own (unpadded) requested files.

    ``bucketed=True`` (default) launches one wavefront per
    :func:`plan_buckets` shape bucket — tight shapes for heterogeneous
    batches, jit-cache-friendly powers-of-two padding.  ``bucketed=False``
    reproduces the seed behaviour (every instance padded to the global batch
    maxima, one launch) and exists for A/B benchmarking.

    ``interpret`` has no default: ``False`` runs the compiled Mosaic kernel,
    ``True`` the Pallas interpreter.

    An instance that fails the guard raises before anything is launched.

    ``capture=True`` changes the return to ``(results, stores)`` where
    ``stores[i]`` is a :class:`~repro.core.warm.DenseStore` snapshot of
    instance ``i``'s dense value/argmin planes — the raw material for
    warm-starting the next solve of a perturbed sibling (see
    :func:`ltsp_solve_batch_warm`).

    ``profile`` takes an optional :class:`~repro.obs.KernelProfile`: every
    device launch records its padded bucket shape, the exact
    real-vs-padded DP cell counts, whether its jit signature was cold, and
    the exact bytes it moved each way; every phase opens a profiler span
    (``ltsp.rescale`` and ``ltsp.guard`` here, the launch's own in
    :func:`_solve_packed`) — host-side accounting, results unchanged.
    """
    if not instances:
        return ([], []) if capture else []
    with _span(profile, "ltsp.rescale"):
        pairs = [rescale_instance(inst) for inst in instances]
        scaled = [p[0] for p in pairs]
        gs = [p[1] for p in pairs]
    with _span(profile, "ltsp.guard"):
        _check_int32_safe(scaled)

    stores: list[DenseStore | None] = [None] * len(instances)

    def solve(idxs, R_pad, S_pad, B_pad):
        out, subs = _solve_packed(
            [instances[i] for i in idxs],
            [scaled[i] for i in idxs],
            [gs[i] for i in idxs],
            R_pad, S_pad, B_pad, span,
            interpret, cand_tile,
            disjoint=disjoint, capture=capture, profile=profile,
        )
        for i, st in zip(idxs, subs):
            stores[i] = st
        return out

    def done(results):
        return (results, stores) if capture else results

    if not bucketed:  # seed behaviour: one launch padded to the batch maxima
        return done(solve(range(len(instances)), None, None, None))
    if len(instances) == 1:  # fast path: no planner, one tight launch
        R_pad, S_pad = bucket_shape(scaled[0])
        return done(solve([0], R_pad, S_pad, None))
    results: list[tuple[int, list[tuple[int, int]]] | None] = [None] * len(instances)
    for (R_pad, S_pad), idxs in plan_buckets(scaled).items():
        for idx, res in zip(idxs, solve(idxs, R_pad, S_pad, _pow2(len(idxs)))):
            results[idx] = res
    return done(results)  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# warm-start entry points
# ---------------------------------------------------------------------------
def ltsp_solve_instance_warm(
    inst: Instance,
    span: int | None = None,
    warm: WarmState | None = None,
    *,
    interpret: bool,
    cand_tile: int = DEFAULT_CAND_TILE,
    profile=None,
) -> tuple[int, list[tuple[int, int]], WarmState | None, WarmStats]:
    """Warm-startable single-instance solve (see :func:`ltsp_solve_batch_warm`)."""
    results, warms, stats = ltsp_solve_batch_warm(
        [inst], [warm], span=span, interpret=interpret,
        cand_tile=cand_tile, profile=profile,
    )
    (cost, dets) = results[0]
    return cost, dets, warms[0], stats[0]


def ltsp_solve_batch_warm(
    instances: list[Instance],
    warms: list[WarmState | None] | None = None,
    span: int | None = None,
    *,
    interpret: bool,
    bucketed: bool = True,
    cand_tile: int = DEFAULT_CAND_TILE,
    profile=None,
) -> tuple[
    list[tuple[int, list[tuple[int, int]]]],
    list[WarmState | None],
    list[WarmStats],
]:
    """Warm-startable batch solve, bit-identical to :func:`ltsp_solve_batch`.

    Instances whose :class:`~repro.core.warm.WarmState` aligns (same U-turn
    penalty and span, at least one matching file run — see
    :func:`repro.core.warm.align_warm`) re-evaluate **only the invalidated
    cells on the host**, in exact python ints, reading every still-valid cell
    out of the warm store: a device relaunch would recompute the whole dense
    table, which is precisely the work warm-starting exists to avoid, and
    the host incremental path is bit-identical to the device wavefront (the
    python and device backends are pinned bit-identical by the kernel parity
    tests, and warm-vs-cold identity is asserted differentially on top).
    Everything else takes the normal bucketed device launches with
    ``capture=True``, so each cold solve yields a dense
    :class:`~repro.core.warm.DenseStore` warm state for the next tick.

    The int32 guard runs for *every* instance first — including warm-aligned
    ones, which would otherwise bypass it — so the error behaviour matches
    the cold path exactly.  Returns ``(results, new_warm_states, stats)``, all parallel to
    ``instances``.
    """
    if not instances:
        return [], [], []
    if warms is None:
        warms = [None] * len(instances)
    # same guard discipline as the cold path (before any solving: a batch
    # never fails mid-flight)
    _check_int32_safe([rescale_instance(inst)[0] for inst in instances])

    from ...core.dp import dp_schedule_warm

    results: list[tuple[int, list[tuple[int, int]]] | None] = [None] * len(instances)
    new_warms: list[WarmState | None] = [None] * len(instances)
    stats: list[WarmStats | None] = [None] * len(instances)
    cold: list[int] = []
    for i, (inst, warm) in enumerate(zip(instances, warms)):
        if align_warm(warm, inst, span) is not None:
            cost, dets, new_warm, st = dp_schedule_warm(inst, span=span, warm=warm)
            results[i], new_warms[i], stats[i] = (cost, dets), new_warm, st
        else:
            cold.append(i)
    if cold:
        solved, stores = ltsp_solve_batch(
            [instances[i] for i in cold], span=span, interpret=interpret,
            bucketed=bucketed, cand_tile=cand_tile, capture=True, profile=profile,
        )
        for i, res, store in zip(cold, solved, stores):
            results[i] = res
            new_warms[i] = (
                warm_from_instance(instances[i], span, store)
                if store is not None else None
            )
            # honest device work accounting: the wavefront evaluates every
            # dense cell of the padded launch shape
            stats[i] = WarmStats(cells_evaluated=len(store) if store else 0)
    return results, new_warms, stats  # type: ignore[return-value]
