"""Pallas LTSP-DP kernel against the dense NumPy reference (:mod:`.ref`,
every cell and lane of ``T`` and ``C``) and the exact Python DP: shapes,
chunked bands, LOGDP spans, SIMPLEDP's clip, skip-count padding, and the
skip's clamp, all on the int32 path the solver runs."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dp_schedule, make_instance
from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables
from repro.kernels.ltsp_dp.ops import prepare_batch, traceback_detours
from repro.kernels.ltsp_dp.ref import ltsp_tables_ref, root_cost


def _small_instance(rng, R):
    sizes = rng.integers(1, 9, size=R)
    gaps = rng.integers(0, 6, size=R + 1)
    left, pos = [], int(gaps[0])
    for i in range(R):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    mult = rng.integers(1, 4, size=R)
    return make_instance(left, sizes, mult, m=pos, u_turn=int(rng.integers(0, 5)))


def _kernel_and_ref(inst, S_pad=None, **kw):
    """One instance's int32 kernel tables ``(T, C)`` and the reference's,
    as NumPy arrays over the same packed inputs."""
    left, right, x, nl, u, S = prepare_batch([inst], S_pad=S_pad)
    T, C = ltsp_dp_tables(left, right, x, nl, u, S=S, interpret=True, **kw)
    host = [np.asarray(v[0]) for v in (left, right, x, nl, u)]
    span, disjoint = kw.get("span"), kw.get("disjoint", False)
    T_ref, C_ref, _ = ltsp_tables_ref(*host, S, span, disjoint)
    return (np.asarray(T[0]), np.asarray(C[0])), (T_ref, C_ref)


def _assert_tables_equal(got, want):
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("R", [2, 3, 5, 9, 14])
def test_kernel_matches_ref_exactly(R, rng):
    inst = _small_instance(rng, R)
    _assert_tables_equal(*_kernel_and_ref(inst))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_kernel_opt_equals_exact_dp(seed):
    rng = np.random.default_rng(seed)
    inst = _small_instance(rng, int(rng.integers(2, 10)))
    kernel, ref = _kernel_and_ref(inst)
    _assert_tables_equal(kernel, ref)
    assert root_cost(kernel[0], inst) == dp_schedule(inst)[0]


def test_ref_opt_equals_exact_dp(rng):
    inst = _small_instance(rng, 7)
    left, right, x, nl, u, S = prepare_batch([inst])
    T, C, _ = ltsp_tables_ref(*(np.asarray(v[0]) for v in (left, right, x, nl, u)), S)
    cost, detours = dp_schedule(inst)
    assert root_cost(T, inst) == cost
    assert sorted(traceback_detours(C, inst.mult)) == sorted(detours)


def test_ltsp_dp_tables_refuses_float_input():
    """The wavefront has one numeric path: any dtype but int32 is refused
    when the call is traced, before anything runs."""
    vec = jnp.zeros((1, 8), jnp.float32)
    with pytest.raises(TypeError, match="int32"):
        ltsp_dp_tables(vec, vec, jnp.zeros((1, 8), jnp.int32), vec,
                       jnp.zeros((1,), jnp.float32), S=128, interpret=True)


@pytest.mark.parametrize("cand_tile", [8, 16, 32])
def test_kernel_banded_scan_matches_full_tile(rng, cand_tile):
    """The band scanned in chunks of ``cand_tile`` rows (several per band,
    the last one clamped to ``R - cand_tile``) must reproduce one chunk of
    all ``R`` rows bit-for-bit — values AND argmin planes (tie-breaks
    included)."""
    inst = _small_instance(rng, 40)
    args = prepare_batch([inst])
    S = args[-1]
    T_full, C_full = ltsp_dp_tables(*args[:-1], S=S, cand_tile=40, interpret=True)
    T_band, C_band = ltsp_dp_tables(*args[:-1], S=S, cand_tile=cand_tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(T_band), np.asarray(T_full))
    np.testing.assert_array_equal(np.asarray(C_band), np.asarray(C_full))


def test_kernel_s_padding_invariance(rng):
    """Padding the skip-count axis must not change reachable cells."""
    inst = _small_instance(rng, 6)
    (T1, C1), ref = _kernel_and_ref(inst)
    (T2, C2), _ = _kernel_and_ref(inst, S_pad=T1.shape[-1] + 128)
    _assert_tables_equal((T1, C1), ref)
    # reachable skip counts never exceed n; compare that slab
    n = inst.n
    np.testing.assert_array_equal(T1[..., : n + 1], T2[..., : n + 1])
    np.testing.assert_array_equal(C1[..., : n + 1], C2[..., : n + 1])


@pytest.mark.parametrize("cand_tile", [8, 16, 32])
def test_banded_scan_unaligned_band_starts_match_python_dp(cand_tile):
    """R > cand_tile: chunk bases align down to 8 sublanes while the live
    band starts at c = a + 1 (and at b - span under LOGDP), mostly unaligned.
    The masked overhang must change neither the value nor the smallest-c
    tie-break: tables equal one chunk of all R rows, (cost, detours) the
    exact python DP."""
    from repro.kernels.ltsp_dp.ops import ltsp_solve_instance

    rng = np.random.default_rng(20261016)
    inst = _small_instance(rng, 37)
    left, right, x, nl, u, S = prepare_batch([inst])
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
    vec = jnp.zeros((1, R), jnp.int32)
    with pytest.raises(ValueError, match="multiples of 8"):
        ltsp_dp_tables(
            vec, vec, vec, vec, jnp.zeros((1,), jnp.int32),
            S=128, interpret=False, cand_tile=cand_tile,
        )


def test_skip_shift_clamps_at_last_skip_count():
    """The skip term reads T[a, b-1, min(s + x_b, S-1)].  With multiplicities
    large next to S, s + x_b passes S - 1 on many cells, where a wrapped
    rotation would read T[a, b-1, s + x_b - S] instead: the kernel tables must
    equal the reference's clamped ones everywhere, unreachable cells included."""
    inst = make_instance(
        [0, 7, 15, 30, 41], [5, 6, 9, 8, 3], [40, 1, 50, 2, 30], m=50, u_turn=3
    )
    kernel, (T_ref, C_ref) = _kernel_and_ref(inst)
    S = T_ref.shape[-1]
    assert S == 128 and inst.n == 123
    _assert_tables_equal(kernel, (T_ref, C_ref))
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
    cell and lane of T and C, by ``ref.py``) and against ``core/dp.py``'s
    root cost and detours.  The shapes make bands cross chunk boundaries,
    start unaligned, and meet the last chunk's clamp to ``R - H``; chunks
    come from ``cand_tile``, or from the span under LOGDP."""
    from repro.core import simpledp_schedule

    rng = np.random.default_rng(1000 * R + n_req + B)
    if ties:
        insts = [_uniform_instance(n_req)]
    elif n_req + 1 > 128 or R == 256:
        insts = [_short_instance(rng, n_req) for _ in range(B)]
    else:
        insts = [_small_instance(rng, n_req) for _ in range(B)]
    left, right, x, nl, u, S = prepare_batch(insts, R_pad=R)
    T, C = ltsp_dp_tables(left, right, x, nl, u, S=S, span=span, disjoint=disjoint,
                          cand_tile=cand_tile, interpret=True)
    T, C = np.asarray(T), np.asarray(C)
    met = np.zeros(2, np.int64)
    for i, inst in enumerate(insts):
        host = [np.asarray(v[i], np.int64) for v in (left, right, x, nl)]
        T_np, C_np, t = ltsp_tables_ref(*host, int(u[i]), S, span, disjoint)
        np.testing.assert_array_equal(T[i], T_np)
        np.testing.assert_array_equal(C[i], C_np)
        met += t
        want = simpledp_schedule(inst) if disjoint else dp_schedule(inst, span=span)
        mult = np.asarray(x[i], np.int64)
        got = (root_cost(T[i], inst), traceback_detours(C[i], mult))
        assert got[0] == want[0]
        assert sorted(got[1]) == sorted(want[1])
    if ties:
        assert met[0] > 0 and met[1] > 0, met
