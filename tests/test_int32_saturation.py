"""The int32 wavefront past int32 candidate sums: clipped tables,
saturating sums, and the guard on cell values and terms.

The kernel stores table values clipped to ``INT32_CAP`` and adds each
candidate's linear part saturating at ``2**31 - 1``; the guard of ``ops``
admits an instance when every cell a reader takes (``_cell_bound``) is below
``INT32_CAP`` and every term the kernel forms at the lanes those cells
depend on (``_term_bound``) below ``2**31 - 1``.  These instances have
candidate-sum bounds ``2n (8m + (2R + 2) U)`` of ``2**31`` or more, which
the guard refused before; on the Pallas interpreter they must equal the
exact Python DP in cost and detours, for DP, LOGDP and SIMPLEDP.  So must
every instance the old bound admitted.
"""

import numpy as np
import pytest

from repro.core import (
    ExecutionContext,
    dp_schedule,
    evaluate_detours,
    make_instance,
    simpledp_schedule,
    solve,
    solve_batch,
)
from repro.core.dp import logdp_schedule
from repro.kernels.ltsp_dp.ltsp_dp import INT32_CAP, ltsp_dp_tables
from repro.kernels.ltsp_dp.ops import (
    _cell_bound,
    _int32_admits,
    _term_bound,
    host_table,
    ltsp_solve_batch,
    prepare_batch,
    rescale_instance,
)

DEV = ExecutionContext(backend="pallas-interpret")
INT32_MAX = 2**31 - 1
ORACLES = {
    "dp": dp_schedule,
    "logdp1": logdp_schedule,
    "simpledp": simpledp_schedule,
}


def _read_cells(inst):
    """Every cell a reader takes, by the recurrence in plain Python
    integers: ``{(a, b, s): value}`` over the cells a warm start's
    ``DenseStore`` admits (``s + x_{a+1} + ... + x_b <= n``, the root's cone
    among them), and the largest candidate sum formed there."""
    L = [int(v) for v in inst.left]
    Rt = [int(v) for v in inst.right]
    x = [int(v) for v in inst.mult]
    U = int(inst.u_turn)
    R, n = len(x), sum(x)
    csum = np.concatenate([[0], np.cumsum(x)]).tolist()
    nl = csum[:-1]
    T = {}
    top_cand = 0
    for b in range(R):
        for s in range(n + 1):
            T[b, b, s] = 2 * (Rt[b] - L[b]) * (s + nl[b])
    for d in range(1, R):
        for a in range(R - d):
            b = a + d
            for s in range(n - csum[b + 1] + csum[a + 1] + 1):
                v = (T[a, b - 1, s + x[b]] + 2 * (Rt[b] - Rt[b - 1]) * (s + nl[a])
                     + 2 * (L[b] - Rt[b - 1]) * x[b])
                for c in range(a + 1, b + 1):
                    cand = (T[a, c - 1, s] + T[c, b, s]
                            + 2 * (Rt[b] - Rt[c - 1]) * (s + nl[a])
                            + 2 * U * (s + nl[c]))
                    top_cand = max(top_cand, cand)
                    v = min(v, cand)
                T[a, b, s] = v
    return T, top_cand


def _old_candidate_bound(inst):
    """The guard's bound before the kernel saturated its sums: every
    candidate sum below ``2n (8m + (2R + 2) U)``, admitted below ``2**31``."""
    return 2 * inst.n * (8 * inst.m + (2 * inst.n_req + 2) * inst.u_turn)


def _past_candidate_bound(seed, count):
    """Seeded tapes with coprime coordinates of a million or two units, up
    to 127 requests and a U-turn penalty near the span: candidate-sum bound
    at least ``2**31``, admitted by the int32 guard."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < count:
        R = int(rng.integers(3, 9))
        m = int(rng.integers(1_000_000, 2_000_000))
        edges = np.sort(rng.choice(np.arange(1, m), size=2 * R - 1, replace=False))
        edges = np.concatenate([[0], edges])
        left, right = edges[0::2], edges[1::2]
        mult = rng.multinomial(127 - R, rng.dirichlet(np.ones(R))) + 1
        inst = make_instance(left.tolist(), (right - left).tolist(), mult.tolist(),
                             m=int(right[-1]) + 7, u_turn=int(rng.integers(m // 2, m)))
        scaled, g = rescale_instance(inst)
        if g == 1 and _int32_admits(scaled):
            out.append(inst)
    return out


#: a tape whose candidate sums pass int32 in cells a warm start reads (found
#: by a seeded search)
SATURATING = make_instance(
    [0, 226961, 1204974], [51549, 39709, 532556], [118, 7, 2],
    m=1737530, u_turn=2472976,
)

#: 10 requests in 128 lanes on a span of 7e6 units: the far lanes' sums pass
#: the table's clip
FAR_LANES = make_instance(
    [0, 3_000_001, 5_000_003], [1_000_003, 999_999, 2_000_001], [4, 3, 3], u_turn=5,
)

#: 2 requests in 128 lanes on a coprime span of 1e7 units: the far lanes'
#: terms wrap int32, and the old bound admitted it
WRAPPING_LANES = make_instance([0, 10_000_000], [1, 1], [1, 1], u_turn=1)


@pytest.mark.parametrize("policy", sorted(ORACLES))
def test_past_the_candidate_bound_equals_the_python_dp(policy):
    insts = _past_candidate_bound(20261018, 4) + [SATURATING]
    for inst in insts:
        scaled, _ = rescale_instance(inst)
        # the guard refused these before: candidate sums may pass int32 ...
        assert _old_candidate_bound(scaled) >= 2**31
        # ... and admits them now
        assert _cell_bound(scaled) < INT32_CAP
        assert _term_bound(scaled) < INT32_MAX
    for inst, res in zip(insts, solve_batch(insts, policy=policy, context=DEV)):
        assert (res.cost, res.detours) == ORACLES[policy](inst)
        assert evaluate_detours(inst, res.detours) == res.cost


def test_the_cell_bound_holds_on_every_cell_a_reader_takes():
    for inst in _past_candidate_bound(7, 3) + [SATURATING]:
        scaled, _ = rescale_instance(inst)
        cells, top_cand = _read_cells(scaled)
        assert max(cells.values()) <= _cell_bound(scaled)
        n, csum = scaled.n, np.cumsum(scaled.mult).tolist()
        reached = [v for (a, b, s), v in cells.items() if s <= n - csum[b]]
        assert 2 * max(reached) <= _cell_bound(scaled)  # 2nm from the root


def _envelope(T, inst):
    """The values of a launch's table ``T[0]`` at the cells a reader takes
    (``s + x_{a+1} + ... + x_b <= n``), by ``(a, b, s)``."""
    x, n = [int(v) for v in inst.mult], inst.n
    return {(a, b, s): int(T[0, a, b, s])
            for a in range(len(x)) for b in range(a, len(x))
            for s in range(n - sum(x[a + 1:b + 1]) + 1)}


def test_sums_past_int32_saturate_and_every_stored_value_stays_in_range():
    scaled, g = rescale_instance(SATURATING)
    assert g == 1 and _int32_admits(scaled)
    cells, top_cand = _read_cells(scaled)
    # a wrapping kernel would read this candidate as a negative number
    assert top_cand > INT32_MAX
    # every cell a warm start reads is exact ...
    [(cost, dets)], [store] = ltsp_solve_batch([scaled], interpret=True, capture=True)
    assert (cost, dets) == dp_schedule(scaled)
    assert all(store.lookup(a, b, s)[0] == v for (a, b, s), v in cells.items())
    # ... and every value the tables store at a cell a reader takes is exact
    # and in [0, cap], also where the lanes past the requests pass the clip
    # (FAR_LANES) or wrap (WRAPPING_LANES)
    for inst in (scaled, rescale_instance(FAR_LANES)[0], rescale_instance(WRAPPING_LANES)[0]):
        assert _int32_admits(inst)
        left, right, x, nl, u, S = prepare_batch([inst], R_pad=4, S_pad=128)
        T, _ = ltsp_dp_tables(left, right, x, nl, u, S=S, interpret=True)
        read = _envelope(host_table(T), inst)
        assert read == _read_cells(inst)[0]
        assert 0 <= min(read.values()) and max(read.values()) <= INT32_CAP
    for inst in (FAR_LANES, WRAPPING_LANES):
        assert solve(inst, policy="dp", context=DEV).cost == dp_schedule(inst)[0]


def _old_admitted(seed, count):
    """Seeded tapes with coprime coordinates, few requests and spans of 1e5 to
    6.7e7 units, that the old candidate bound admitted; small ``n`` with a
    large span puts the terms of the padded lanes past int32."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < count:
        R = int(rng.integers(2, 7))
        m = int(10 ** rng.uniform(5, np.log10(6.7e7)))
        edges = np.sort(rng.choice(m, size=2 * R, replace=False))
        left, right = edges[0::2], edges[1::2]
        mult = rng.integers(1, 4, size=R)
        inst = make_instance(left.tolist(), (right - left).tolist(), mult.tolist(),
                             m=m + 1, u_turn=int(rng.integers(0, m // 4)))
        if _old_candidate_bound(rescale_instance(inst)[0]) < 2**31:
            out.append(inst)
    return out


@pytest.mark.parametrize("policy", sorted(ORACLES))
def test_what_the_old_candidate_bound_admitted_still_solves_in_int32(policy):
    insts = _old_admitted(20261019, 6) + [WRAPPING_LANES]
    scaled = [rescale_instance(inst)[0] for inst in insts]
    assert all(_int32_admits(s) for s in scaled)
    # terms past int32 at the padded lanes of a 128-lane launch
    assert sum(2 * (s.m + s.u_turn) * (127 + s.n) > INT32_MAX for s in scaled) >= 2
    # one launch of every instance at the batch's widest S, and one per bucket
    for bucketed in (False, True):
        ctx = DEV.replace(bucketed=bucketed)
        for inst, res in zip(insts, solve_batch(insts, policy=policy, context=ctx)):
            assert (res.cost, res.detours) == ORACLES[policy](inst)


def test_above_the_cell_bound_the_guard_raises_and_python_solves():
    bad = make_instance(
        [0, 2 * 10**9 + 1], [10**6 + 1, 10**6 + 3], [3, 3], u_turn=10**7 + 1
    )
    scaled, _ = rescale_instance(bad)
    assert _cell_bound(scaled) > INT32_MAX
    with pytest.raises(ValueError, match="cell-value bound 4nm") as ei:
        solve(bad, policy="dp", context=DEV)
    assert "term bound" in str(ei.value) and "backend='python'" in str(ei.value)
    res = solve(bad, policy="dp")
    assert (res.cost, res.detours) == dp_schedule(bad)
    assert evaluate_detours(bad, res.detours) == res.cost
