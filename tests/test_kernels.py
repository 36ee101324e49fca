"""Pallas LTSP-DP kernel: shape/dtype sweep vs the pure-jnp oracle and the
exact integer DP (f32 is exact for the small-integer instances used here)."""

import numpy as np
import pytest

from conftest import random_instance
from repro.core import dp_schedule, make_instance
from repro.kernels.ltsp_dp.ops import ltsp_dp_table, ltsp_opt_instance, prepare_arrays
from repro.kernels.ltsp_dp.ref import ltsp_dp_table_ref, ltsp_opt_ref


def _small_instance(rng, R):
    sizes = rng.integers(1, 9, size=R)
    gaps = rng.integers(0, 6, size=R + 1)
    left, pos = [], int(gaps[0])
    for i in range(R):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    mult = rng.integers(1, 4, size=R)
    return make_instance(left, sizes, mult, m=pos, u_turn=int(rng.integers(0, 5)))


@pytest.mark.parametrize("R", [2, 3, 5, 9, 14])
def test_kernel_matches_ref_exactly(R, rng):
    inst = _small_instance(rng, R)
    l, r, x, nl, S = prepare_arrays(inst)
    T_kernel = ltsp_dp_table(l, r, x, nl, float(inst.u_turn), S, interpret=True)
    T_ref = ltsp_dp_table_ref(l, r, x, nl, float(inst.u_turn), S)
    np.testing.assert_array_equal(np.asarray(T_kernel), np.asarray(T_ref))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_kernel_opt_equals_exact_dp(seed):
    rng = np.random.default_rng(seed)
    inst = _small_instance(rng, int(rng.integers(2, 10)))
    opt_exact, _ = dp_schedule(inst)
    assert ltsp_opt_instance(inst, interpret=True) == float(opt_exact)


def test_ref_opt_equals_exact_dp(rng):
    inst = _small_instance(rng, 7)
    l, r, x, nl, S = prepare_arrays(inst)
    v = ltsp_opt_ref(l, r, x, nl, float(inst.u_turn), float(inst.m), S)
    assert float(v) == float(dp_schedule(inst)[0])


@pytest.mark.parametrize("cand_tile", [8, 16, 32])
def test_kernel_banded_scan_matches_full_tile(rng, cand_tile):
    """The band scanned in chunks of ``cand_tile`` rows (several per band,
    the last one clamped to ``R - cand_tile``) must reproduce one chunk of
    all ``R`` rows bit-for-bit — values AND argmin planes (tie-breaks
    included)."""
    import jax.numpy as jnp

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables

    inst = _small_instance(rng, 40)
    l, r, x, nl, S = prepare_arrays(inst)
    u = jnp.asarray([float(inst.u_turn)], l.dtype)
    args = (l[None], r[None], x[None], nl[None], u)
    T_full, C_full = ltsp_dp_tables(*args, S=S, cand_tile=40, interpret=True)
    T_band, C_band = ltsp_dp_tables(*args, S=S, cand_tile=cand_tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(T_band), np.asarray(T_full))
    np.testing.assert_array_equal(np.asarray(C_band), np.asarray(C_full))


def test_kernel_s_padding_invariance(rng):
    """Padding the skip-count axis must not change reachable cells."""
    inst = _small_instance(rng, 6)
    l, r, x, nl, S = prepare_arrays(inst)
    T1 = ltsp_dp_table(l, r, x, nl, float(inst.u_turn), S, interpret=True)
    T2 = ltsp_dp_table(l, r, x, nl, float(inst.u_turn), S + 128, interpret=True)
    R = inst.n_req
    # reachable skip counts never exceed n; compare that slab
    n = inst.n
    np.testing.assert_array_equal(
        np.asarray(T1[..., : n + 1]), np.asarray(T2[..., : n + 1])
    )


@pytest.mark.parametrize("cand_tile", [8, 16, 32])
def test_banded_scan_unaligned_band_starts_match_python_dp(cand_tile):
    """R > cand_tile: chunk bases align down to 8 sublanes while the live
    band starts at c = a + 1 (and at b - span under LOGDP), mostly unaligned.
    The masked overhang must change neither the value nor the smallest-c
    tie-break: tables equal one chunk of all R rows, (cost, detours) the
    exact python DP."""
    import jax.numpy as jnp

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables
    from repro.kernels.ltsp_dp.ops import ltsp_solve_instance, prepare_batch

    rng = np.random.default_rng(20261016)
    inst = _small_instance(rng, 37)
    left, right, x, nl, u, S = prepare_batch([inst], dtype=jnp.int32)
    for span in (None, 11):
        kw = dict(S=S, span=span, interpret=True)
        T_one, C_one = ltsp_dp_tables(left, right, x, nl, u, cand_tile=37, **kw)
        T_band, C_band = ltsp_dp_tables(left, right, x, nl, u, cand_tile=cand_tile, **kw)
        np.testing.assert_array_equal(np.asarray(T_band), np.asarray(T_one))
        np.testing.assert_array_equal(np.asarray(C_band), np.asarray(C_one))
        assert ltsp_solve_instance(
            inst, span=span, cand_tile=cand_tile, interpret=True
        ) == dp_schedule(inst, span=span)


@pytest.mark.parametrize("R, cand_tile", [(37, 8), (32, 12)])
def test_compiled_banded_scan_refuses_unaligned_chunks(R, cand_tile):
    """The compiled banded scan tells Mosaic its chunk bases are multiples of
    8; a shape where that would not hold raises before anything is lowered."""
    import jax.numpy as jnp

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables

    vec = jnp.zeros((1, R), jnp.int32)
    with pytest.raises(ValueError, match="multiples of 8"):
        ltsp_dp_tables(
            vec, vec, vec, vec, jnp.zeros((1,), jnp.int32),
            S=128, interpret=False, cand_tile=cand_tile,
        )


def test_skip_shift_clamps_at_last_skip_count():
    """The skip term reads T[a, b-1, min(s + x_b, S-1)].  With multiplicities
    large next to S, s + x_b passes S - 1 on many cells, where a wrapped
    rotation would read T[a, b-1, s + x_b - S] instead: the kernel table must
    equal the reference's clamped one everywhere, unreachable cells included."""
    inst = make_instance(
        [0, 7, 15, 30, 41], [5, 6, 9, 8, 3], [40, 1, 50, 2, 30], m=50, u_turn=3
    )
    l, r, x, nl, S = prepare_arrays(inst)
    assert S == 128 and inst.n == 123
    T_kernel = np.asarray(ltsp_dp_table(l, r, x, nl, float(inst.u_turn), S, interpret=True))
    T_ref = np.asarray(ltsp_dp_table_ref(l, r, x, nl, float(inst.u_turn), S))
    np.testing.assert_array_equal(T_kernel, T_ref)
    # the clamp matters: somewhere the clamped and the wrapped reads differ
    s = np.arange(S)
    differs = False
    for b in range(1, inst.n_req):
        xb = int(inst.mult[b])
        for a in range(b):
            row = T_ref[a, b - 1]
            differs |= bool(
                np.any(row[np.minimum(s + xb, S - 1)] != row[(s + xb) % S])
            )
    assert differs


def _tables_np(left, right, x, nl, u, S, span=None, disjoint=False):
    """Plain NumPy int64 DP tables ``(T, C)`` of one instance over every cell
    and lane, with the kernel's rules: the skip reads lane ``min(s + x_b,
    S - 1)``; the skip wins ties, then the smallest detour start ``c``.  Also
    the number of skip-detour and detour-detour ties met on the way."""
    big = 2**31 - 1
    R, s = len(left), np.arange(S)
    T = np.zeros((R, R, S), np.int64)
    C = np.full((R, R, S), -1, np.int64)
    rr = np.arange(R)
    T[rr, rr] = 2 * (right - left)[:, None] * (s + nl[:, None])
    ties = np.zeros(2, np.int64)
    for d in range(1, R):
        a = np.arange(R - d)
        b = a + d
        idx = np.minimum(s[None] + x[b][:, None], S - 1)
        skip = (np.take_along_axis(T[a, b - 1], idx, 1)
                + 2 * (right[b] - right[b - 1])[:, None] * (s + nl[a][:, None])
                + (2 * (left[b] - right[b - 1]) * x[b])[:, None])
        c_min = a + 1
        if span is not None:
            c_min = np.maximum(c_min, b - span)
        if disjoint:
            c_min = np.where(a > 0, b + 1, c_min)
        det = np.full((R - d, S), big, np.int64)
        arg = np.zeros((R - d, S), np.int64)
        for k in range(1, d + 1):
            c = a + k
            cand = (T[a, c - 1] + T[c, b]
                    + 2 * (right[b] - right[c - 1])[:, None] * (s + nl[a][:, None])
                    + 2 * u * (s + nl[c][:, None]))
            cand = np.where((c >= c_min)[:, None], cand, big)
            ties[1] += np.sum((cand == det) & (cand < big))
            better = cand < det
            det = np.where(better, cand, det)
            arg = np.where(better, c[:, None], arg)
        ties[0] += np.sum(skip == det)
        T[a, b] = np.minimum(skip, det)
        C[a, b] = np.where(skip <= det, -1, arg)
    return T, C, ties


def _uniform_instance(n_req):
    """Equal files edge to edge, one request each, no U-turn penalty: skips
    tie with detours and detours with each other all over the table."""
    return make_instance(np.arange(n_req) * 2, np.full(n_req, 2),
                         np.ones(n_req, np.int64), m=2 * n_req, u_turn=0)


def _short_instance(rng, n_req):
    """``n_req`` files of one request each, so that ``n + 1 <= 128``."""
    inst = _small_instance(rng, n_req)
    return make_instance(inst.left, inst.size, np.ones(n_req, np.int64),
                         m=inst.m, u_turn=inst.u_turn)


@pytest.mark.parametrize(
    "R, n_req, B, span, disjoint, cand_tile, ties",
    [
        pytest.param(8, 8, 1, None, False, 32, False, id="dp-R8"),
        pytest.param(64, 61, 1, None, False, 16, False, id="dp-R64-H16"),
        pytest.param(64, 64, 1, None, False, 8, False, id="dp-R64-H8"),
        pytest.param(256, 120, 1, None, False, 32, False, id="dp-R256-H32-S128"),
        pytest.param(64, 64, 1, 1, False, 16, False, id="logdp-span1-R64"),
        pytest.param(64, 57, 1, 5, False, 16, False, id="logdp-span5-R64"),
        pytest.param(64, 64, 1, 40, False, 16, False, id="logdp-span40-R64"),
        pytest.param(256, 120, 1, 5, False, 32, False, id="logdp-span5-R256-S128"),
        pytest.param(64, 50, 1, None, True, 16, False, id="simpledp-disjoint-R64"),
        pytest.param(64, 64, 2, None, False, 16, False, id="dp-B2-R64-H16"),
        pytest.param(64, 64, 1, None, False, 16, True, id="ties-dp-R64-H16"),
        pytest.param(64, 64, 1, 5, False, 16, True, id="ties-logdp-span5-R64"),
    ],
)
def test_band_only_kernel_matches_references(R, n_req, B, span, disjoint, cand_tile, ties):
    """The band-only wavefront against whole tables computed plainly (every
    cell and lane of T and C, NumPy here; T by ``ref.py`` too for the
    unrestricted DP at R = 8, where its eager loops are quick) and against ``core/dp.py``'s root cost and detours.  The shapes make
    bands cross chunk boundaries, start unaligned, and meet the last chunk's
    clamp to ``R - H``; chunks come from ``cand_tile``, or from the span
    under LOGDP."""
    import jax.numpy as jnp

    from repro.core import simpledp_schedule
    from repro.core.instance import virtual_lb
    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables
    from repro.kernels.ltsp_dp.ops import prepare_batch, traceback_detours

    rng = np.random.default_rng(1000 * R + n_req + B)
    if ties:
        insts = [_uniform_instance(n_req)]
    elif n_req + 1 > 128 or R == 256:
        insts = [_short_instance(rng, n_req) for _ in range(B)]
    else:
        insts = [_small_instance(rng, n_req) for _ in range(B)]
    left, right, x, nl, u, S = prepare_batch(insts, dtype=jnp.int32, R_pad=R)
    T, C = ltsp_dp_tables(left, right, x, nl, u, S=S, span=span, disjoint=disjoint,
                          cand_tile=cand_tile, interpret=True)
    T, C = np.asarray(T), np.asarray(C)
    met = np.zeros(2, np.int64)
    for i, inst in enumerate(insts):
        host = [np.asarray(v[i], np.int64) for v in (left, right, x, nl)]
        T_np, C_np, t = _tables_np(*host, int(u[i]), S, span, disjoint)
        np.testing.assert_array_equal(T[i], T_np)
        np.testing.assert_array_equal(C[i], C_np)
        met += t
        if span is None and not disjoint and R <= 8:
            fl = [jnp.asarray(v, jnp.float32) for v in host[:2]]
            T_ref = ltsp_dp_table_ref(fl[0], fl[1], jnp.asarray(host[2], jnp.int32),
                                      jnp.asarray(host[3], jnp.float32),
                                      float(u[i]), S)
            np.testing.assert_array_equal(T[i], np.asarray(T_ref).astype(np.int64))
        want = simpledp_schedule(inst) if disjoint else dp_schedule(inst, span=span)
        mult = np.asarray(x[i], np.int64)
        got = (int(T[i, 0, R - 1, 0]) + virtual_lb(inst), traceback_detours(C[i], mult))
        assert got[0] == want[0]
        assert sorted(got[1]) == sorted(want[1])
    if ties:
        assert met[0] > 0 and met[1] > 0, met
