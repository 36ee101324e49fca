"""Size-bucketed batch planner, coordinate rescaling, and the solve memo
cache: bucketed ``solve_batch`` must be bit-identical to per-instance solving
(cost *and* detours), empty/single batches take their fast paths, gcd
rescaling widens the int32 device envelope, and cache hits never alias."""

import numpy as np
import pytest

from repro.core import (
    ExecutionContext,
    SolveCache,
    dp_schedule,
    evaluate_detours,
    make_instance,
    solve,
    solve_batch,
)

from repro.kernels.ltsp_dp.ops import (
    bucket_shape,
    ltsp_solve_batch,
    ltsp_solve_instance,
    plan_buckets,
    prepare_batch,
    rescale_instance,
)

DEV = ExecutionContext(backend="pallas-interpret")


def _hetero_instance(rng):
    """Mixed-size instance: n_req from 2..20, multiplicities up to 8."""
    R = int(rng.integers(2, 21))
    sizes = rng.integers(1, 50, size=R)
    gaps = rng.integers(0, 40, size=R + 1)
    left, pos = [], int(gaps[0])
    for i in range(R):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    mult = rng.integers(1, 8, size=R)
    u = int(rng.integers(0, 40)) if rng.random() < 0.7 else 0
    return make_instance(left, sizes, mult, m=pos, u_turn=u)


# ---------------------------------------------------------------------------
# bucketed batching: bit-identical to per-instance solving
# ---------------------------------------------------------------------------
def test_bucketed_batch_bit_identical_to_per_instance_50_instances():
    """>= 50 random heterogeneous instances in one bucketed batch call:
    (cost, detours) must be *bit-identical* to solving each instance alone on
    the same backend, and every cost must equal the exact python optimum."""
    rng = np.random.default_rng(20260801)
    insts = [_hetero_instance(rng) for _ in range(52)]
    assert len({i.n_req for i in insts}) > 5  # genuinely heterogeneous
    assert sum(i.u_turn > 0 for i in insts) >= 10

    batched = ltsp_solve_batch(insts, interpret=True)
    assert len(plan_buckets([rescale_instance(i)[0] for i in insts])) >= 2
    for trial, (inst, (cost, dets)) in enumerate(zip(insts, batched)):
        solo = ltsp_solve_instance(inst, interpret=True)
        assert (cost, dets) == solo, trial
        assert cost == dp_schedule(inst)[0], trial
        assert evaluate_detours(inst, dets) == cost, trial


def test_bucketed_matches_seed_style_padded_launch(rng):
    insts = [_hetero_instance(rng) for _ in range(8)]
    assert ltsp_solve_batch(insts, interpret=True, bucketed=True) == (
        ltsp_solve_batch(insts, interpret=True, bucketed=False)
    )


def test_solver_engine_batch_goes_through_buckets(rng):
    insts = [_hetero_instance(rng) for _ in range(7)]
    dev = solve_batch(insts, policy="dp", context=DEV)
    for inst, res in zip(insts, dev):
        assert res.cost == dp_schedule(inst)[0]
        assert evaluate_detours(inst, res.detours) == res.cost


# ---------------------------------------------------------------------------
# fast paths: empty and single-instance batches
# ---------------------------------------------------------------------------
def test_empty_batch_returns_empty():
    assert ltsp_solve_batch([], interpret=True) == []
    assert solve_batch([], policy="dp", context=DEV) == []
    assert solve_batch([], policy="gs") == []


def test_prepare_batch_empty_raises_cleanly():
    with pytest.raises(ValueError, match="at least one instance"):
        prepare_batch([])


def test_single_instance_batch_matches_solve(rng):
    inst = _hetero_instance(rng)
    [res] = solve_batch([inst], policy="dp", context=DEV)
    alone = solve(inst, policy="dp", context=DEV)
    assert (res.cost, res.detours) == (alone.cost, alone.detours)


# ---------------------------------------------------------------------------
# bucket rounding policy
# ---------------------------------------------------------------------------
def test_bucket_shape_rounding(rng):
    for inst in (make_instance([0], [5], [1]), make_instance([0, 9], [5, 5], [1, 1])):
        R_pad, S_pad = bucket_shape(inst)
        assert R_pad >= inst.n_req and (R_pad & (R_pad - 1)) == 0
        assert S_pad >= inst.n + 1 and S_pad % 128 == 0
        assert ((S_pad // 128) & (S_pad // 128 - 1)) == 0
    big = make_instance([0, 10], [5, 5], [100, 100])  # n = 200 -> S bucket 256
    assert bucket_shape(big)[1] == 256


# ---------------------------------------------------------------------------
# coordinate rescaling: gcd + shift widens the int32 device envelope
# ---------------------------------------------------------------------------
def test_rescale_accepts_tape_block_granularity_coordinates():
    """Byte-scale coordinates on a block grid used to trip the int32 guard;
    gcd rescaling must now solve them exactly on the device backend."""
    inst = make_instance([0, 2 * 10**9], [10**6, 10**6], [3, 3], u_turn=10**7)
    scaled, g = rescale_instance(inst)
    assert g == 10**6 and scaled.m == scaled.right[-1]
    res = solve(inst, policy="dp", context=DEV)
    py = solve(inst, policy="dp")
    assert (res.cost, res.detours) == (py.cost, py.detours)
    assert evaluate_detours(inst, res.detours) == res.cost


def test_rescale_shift_handles_far_offset_layouts():
    """Files far from tape start but close together: the shift (not the gcd)
    does the work, because DP terms only ever see coordinate differences."""
    base = 17 * 10**12 + 5  # odd offset, gcd with coords is 1 without shift
    inst = make_instance([base, base + 40], [10, 20], [2, 3], u_turn=8)
    scaled, g = rescale_instance(inst)
    assert int(scaled.left[0]) == 0 and scaled.m <= 70
    res = solve(inst, policy="dp", context=DEV)
    assert res.cost == dp_schedule(inst)[0]


#: a byte-scale coprime layout, which gcd/shift rescaling cannot bring into
#: int32, and one beyond 2**53
COPRIME = make_instance(
    [0, 2 * 10**9 + 1], [10**6 + 1, 10**6 + 3], [3, 3], u_turn=10**7 + 1
)
PAST_2_53 = make_instance(
    [0, 2 * 10**15 + 1], [10**6 + 1, 10**6 + 3], [3, 3], u_turn=10**7 + 1
)


@pytest.mark.parametrize("policy", ["dp", "simpledp"])
@pytest.mark.parametrize("backend", ["pallas", "pallas-interpret"])
def test_guard_still_rejects_unrescalable_instances(backend, policy, monkeypatch):
    """Coprime huge coordinates cannot be gcd-reduced: the guard raises
    before any launch and names the python backend, which solves them
    exactly at any magnitude."""
    from repro.kernels.ltsp_dp import ops

    def no_launch(*args, **kwargs):
        raise AssertionError("the guard let a launch through")

    monkeypatch.setattr(ops, "ltsp_dp_tables", no_launch)
    for bad in (COPRIME, PAST_2_53):
        with pytest.raises(ValueError, match="int32") as ei:
            solve_batch([_hetero_instance(np.random.default_rng(0)), bad],
                        policy=policy, context=ExecutionContext(backend=backend))
        assert "backend='python'" in str(ei.value)
        py = solve(bad, policy=policy)
        assert py.cost == evaluate_detours(bad, py.detours)


def test_rescale_is_exact_not_approximate(rng):
    """Scaled-table reconstruction g * T_root must be exact on instances
    whose gcd is > 1 by construction."""
    for _ in range(5):
        inst0 = _hetero_instance(rng)
        k = int(rng.integers(2, 9))
        inst = make_instance(
            left=np.asarray(inst0.left) * k,
            size=(np.asarray(inst0.right) - np.asarray(inst0.left)) * k,
            mult=inst0.mult,
            m=inst0.m * k,
            u_turn=inst0.u_turn * k,
        )
        assert rescale_instance(inst)[1] % k == 0
        assert solve(inst, policy="dp", context=DEV).cost == (
            dp_schedule(inst)[0]
        )


# ---------------------------------------------------------------------------
# partial batches: typed per-instance failures, cache never polluted
# ---------------------------------------------------------------------------
def test_partial_batch_solves_good_and_types_bad(rng):
    """``partial=True`` must solve the good instances bit-identically, park
    a typed :class:`FailedSolve` at each failing position, and never let a
    failure touch the cache (regression: an aborted whole-batch launch used
    to throw away the good instances' work)."""
    from repro.core.solver import FailedSolve

    good = [_hetero_instance(rng) for _ in range(3)]
    bad = COPRIME
    batch = [good[0], bad, good[1], good[2]]

    # strict device policy: the bad instance trips the int32 guard
    with pytest.raises(ValueError, match="int32"):
        solve_batch(batch, policy="dp", context=DEV)

    cache = SolveCache()
    ctx = DEV.replace(cache=cache)
    res = solve_batch(batch, policy="dp", context=ctx, partial=True)
    assert isinstance(res[1], FailedSolve)
    assert res[1].policy == "dp" and res[1].index == 1
    assert isinstance(res[1].error, ValueError)
    direct = [solve(i, policy="dp", context=DEV) for i in good]
    assert [(r.cost, r.detours) for r in (res[0], res[2], res[3])] == [
        (r.cost, r.detours) for r in direct
    ]
    # only the three good results were cached; the failure left no entry
    assert cache.stats()["entries"] == 3
    assert cache.get(bad, "dp", "pallas-interpret") is None
    # re-running serves the good ones from the memo, re-fails the bad one
    again = solve_batch(batch, policy="dp", context=ctx, partial=True)
    assert cache.stats()["hits"] == 3
    assert isinstance(again[1], FailedSolve)


def test_partial_without_cache_and_all_good(rng):
    """``partial=True`` on an all-good batch is bit-identical to the strict
    path, with or without a memo on the context."""
    insts = [_hetero_instance(rng) for _ in range(4)]
    strict = solve_batch(insts, policy="dp", context=DEV)
    relaxed = solve_batch(insts, policy="dp", context=DEV, partial=True)
    assert [(r.cost, r.detours) for r in strict] == [
        (r.cost, r.detours) for r in relaxed
    ]


# ---------------------------------------------------------------------------
# solve memo cache
# ---------------------------------------------------------------------------
def test_cache_hit_is_equal_and_counted(rng):
    cache = SolveCache()
    inst = _hetero_instance(rng)
    r1 = solve(inst, policy="dp", context=DEV.replace(cache=cache))
    r2 = solve(inst, policy="dp", context=DEV.replace(cache=cache))
    assert cache.stats() == {"hits": 1, "misses": 1, "entries": 1, "warm_entries": 0}
    assert (r1.cost, r1.detours) == (r2.cost, r2.detours)


def test_cache_hit_never_aliases(rng):
    """Mutating a returned schedule or the instance after a hit must not
    corrupt the cached entry or serve a stale result."""
    cache = SolveCache()
    inst = _hetero_instance(rng)
    first = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    hit = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    assert hit.detours is not first.detours
    hit.detours.append((999, 999))  # vandalise the returned copy
    clean = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    assert clean.detours == first.detours

    # mutate the instance in place: the content-derived key must miss, and
    # the fresh solve must reflect the new instance, not the cached one
    misses_before = cache.misses
    inst.mult[0] += 3
    fresh = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    assert cache.misses == misses_before + 1
    assert fresh.cost == dp_schedule(inst)[0]
    assert fresh.cost == evaluate_detours(inst, fresh.detours)


def test_cache_batch_only_solves_misses(rng):
    cache = SolveCache()
    insts = [_hetero_instance(rng) for _ in range(5)]
    a = solve_batch(insts, policy="dp", context=ExecutionContext(cache=cache))
    extra = _hetero_instance(rng)
    b = solve_batch(insts + [extra], policy="dp", context=ExecutionContext(cache=cache))
    assert cache.hits == 5 and cache.misses == 6
    assert [r.cost for r in b[:5]] == [r.cost for r in a]
    assert b[5].cost == dp_schedule(extra)[0]


def test_cache_keys_separate_policies_and_backends(rng):
    cache = SolveCache()
    inst = _hetero_instance(rng)
    dp = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    sdp = solve(inst, policy="simpledp", context=ExecutionContext(cache=cache))
    assert cache.misses == 2  # different policies never share entries
    assert dp.cost <= sdp.cost
    dev = solve(inst, policy="dp", context=DEV.replace(cache=cache))
    assert cache.misses == 3 and dev.backend == "pallas-interpret"


def test_cache_eviction_is_bounded(rng):
    cache = SolveCache(maxsize=3)
    for _ in range(6):
        solve(_hetero_instance(rng), policy="gs", context=ExecutionContext(cache=cache))
    assert len(cache) == 3 and cache.misses == 6


def test_cache_lru_eviction_order(rng):
    """Least-recently-*used* goes first: a get() refreshes recency, so the
    untouched entry is the one evicted when the bound is crossed."""
    cache = SolveCache(maxsize=3)
    a, b, c, d = (_hetero_instance(rng) for _ in range(4))
    for inst in (a, b, c):
        solve(inst, policy="gs", context=ExecutionContext(cache=cache))
    solve(a, policy="gs", context=ExecutionContext(cache=cache))  # refresh a: LRU order is now b, c, a
    solve(d, policy="gs", context=ExecutionContext(cache=cache))  # evicts b
    assert len(cache) == 3
    assert cache.get(b, "gs", "python") is None  # evicted -> miss
    for inst in (a, c, d):  # everything else still resident
        assert cache.get(inst, "gs", "python") is not None
    # and the eviction is strictly in recency order: after the gets above the
    # stalest entry is a, so inserting a fresh one must evict a, not c or d
    e = _hetero_instance(rng)
    cache.get(c, "gs", "python")
    solve(e, policy="gs", context=ExecutionContext(cache=cache))
    assert cache.get(a, "gs", "python") is None
    assert cache.get(c, "gs", "python") is not None


def test_cache_key_isolation_is_total(rng):
    """Entries never leak across policy or backend for the same instance."""
    cache = SolveCache()
    inst = _hetero_instance(rng)
    combos = [("dp", "python"), ("dp", "pallas-interpret"), ("gs", "python"),
              ("simpledp", "python")]
    for policy, backend in combos:
        solve(inst, policy=policy, context=ExecutionContext(backend=backend, cache=cache))
    assert len(cache) == len(combos) and cache.misses == len(combos)
    for policy, backend in combos:
        hit = cache.get(inst, policy, backend)
        assert hit is not None
        assert (hit.policy, hit.backend) == (policy, backend)
    # unseen combination for the same instance: miss, never a cross-key hit
    assert cache.get(inst, "nodetour", "python") is None


def test_cache_hit_returns_equal_but_not_aliased_detours(rng):
    """Every hit materialises a fresh, equal detour list — never the stored
    tuple and never a previously returned list."""
    cache = SolveCache()
    inst = _hetero_instance(rng)
    first = solve(inst, policy="dp", context=ExecutionContext(cache=cache))
    h1 = cache.get(inst, "dp", "python")
    h2 = cache.get(inst, "dp", "python")
    assert h1.detours == h2.detours == first.detours
    assert h1.detours is not h2.detours
    assert h1.detours is not first.detours
    assert all(isinstance(d, tuple) for d in h1.detours)


def test_library_schedule_uses_cache(rng):
    from repro.storage.tape import TapeLibrary

    lib = TapeLibrary(capacity_per_tape=150_000, u_turn=700,
                      context=ExecutionContext(cache=SolveCache()))
    for i in range(9):
        lib.store(f"f{i}", 30_000)
    reqs = {f"f{i}": 1 + i % 2 for i in range(9)}
    p1 = lib.schedule(reqs, policy="dp")
    assert lib.cache.hits == 0 and lib.cache.misses > 0
    p2 = lib.schedule(reqs, policy="dp")
    assert lib.cache.hits == lib.cache.misses  # full re-plan from the memo
    assert [p.total_cost for p in p1] == [p.total_cost for p in p2]
    assert [p.order for p in p1] == [p.order for p in p2]
