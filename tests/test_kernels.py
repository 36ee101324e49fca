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


@pytest.mark.parametrize("cand_tile", [2, 4, 8])
def test_kernel_banded_scan_matches_full_tile(rng, cand_tile):
    """The chunked banded candidate scan (cand_tile < R - 1) must reproduce
    the single-tile path bit-for-bit — values AND argmin planes (tie-breaks
    included), with and without a span restriction."""
    import jax.numpy as jnp

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables

    inst = _small_instance(rng, 11)
    l, r, x, nl, S = prepare_arrays(inst)
    u = jnp.asarray([float(inst.u_turn)], l.dtype)
    args = (l[None], r[None], x[None], nl[None], u)
    for span in (None, 3):
        T_full, C_full = ltsp_dp_tables(*args, S=S, span=span, interpret=True)
        T_band, C_band = ltsp_dp_tables(
            *args, S=S, span=span, cand_tile=cand_tile, interpret=True
        )
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


@pytest.mark.parametrize("cand_tile", [8, 16])
def test_banded_scan_unaligned_band_starts_match_python_dp(cand_tile):
    """R - 1 > cand_tile: chunk bases align down to 8 sublanes while the live
    band starts at c = a + 1 (and at b - span under LOGDP), mostly unaligned.
    The masked overhang must change neither the value nor the smallest-c
    tie-break: tables equal the single-tile path, (cost, detours) the exact
    python DP."""
    import jax.numpy as jnp

    from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables
    from repro.kernels.ltsp_dp.ops import ltsp_solve_instance, prepare_batch

    rng = np.random.default_rng(20261016)
    inst = _small_instance(rng, 37)
    left, right, x, nl, u, S = prepare_batch([inst], dtype=jnp.int32)
    for span in (None, 11):
        kw = dict(S=S, span=span, interpret=True)
        T_one, C_one = ltsp_dp_tables(left, right, x, nl, u, **kw)
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
