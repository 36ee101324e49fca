"""Pallas LTSP-DP kernel against the dense NumPy reference (:mod:`.ref`,
every cell and lane of ``T`` and ``C``) and the exact Python DP: shapes,
chunked bands, LOGDP spans, SIMPLEDP's clip, skip-count padding, the skip's
shift across the lane-tiled rows and its clamp, and the in-place writes of
one diagonal, all on the int32 path the solver runs."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dp_schedule, make_instance
from repro.kernels.ltsp_dp.ltsp_dp import ltsp_dp_tables, ltsp_dp_wavefront
from repro.kernels.ltsp_dp.ops import bucket_shape, host_table, prepare_batch, traceback_detours
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
    return (host_table(T)[0], host_table(C)[0]), (T_ref, C_ref)


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
    T, C = host_table(T), host_table(C)
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


@pytest.mark.parametrize(
    "mult, S",
    [
        # x_b of 60 and 100 cross a 128-lane boundary; 130 and 129 start
        # past one; s + x_b passes S - 1 on many lanes
        pytest.param([60, 130, 1, 40, 20], 256, id="S256"),
        pytest.param([200, 129, 3, 150, 27, 2], 512, id="S512"),
        pytest.param([100, 260, 7, 128, 1, 3], 512, id="S512-q2"),
    ],
)
def test_skip_shift_across_lane_tiles(mult, S):
    """A row of S skip counts is S/128 tiles of 128 lanes: the skip's read
    of ``s + x_b`` comes from tile row ``p + q`` or ``p + q + 1`` (``q =
    x_b // 128``) and lane ``(l + x_b) % 128``, clamped to lane ``S - 1``.
    Every cell and lane of the kernel tables equals the reference's."""
    R = len(mult)
    size = [5, 6, 9, 8, 3, 4][:R]
    left = np.cumsum([0] + [sz + 2 for sz in size[:-1]]).tolist()
    inst = make_instance(left, size, mult, m=left[-1] + size[-1], u_turn=3)
    assert inst.n + 1 <= S and 2 * inst.n >= S  # the smallest bucket of n
    kernel, ref = _kernel_and_ref(inst, S_pad=S)
    assert kernel[0].shape[-1] == S
    _assert_tables_equal(kernel, ref)
    xs = np.asarray(mult[1:])
    assert (xs >= 128).any() and ((xs % 128) > 0).any()


@pytest.mark.parametrize("d", [1, 6, 15])
def test_one_diagonal_is_written_in_place_and_nothing_else(d):
    """One launch at diagonal ``d`` over two instances, whose dead programs
    ``a >= R - d`` revisit the last live program's blocks before the second
    instance starts.  Diagonal ``d`` of ``T`` and of the 16-bit ``C``
    equals the reference; every other cell keeps the sentinel or the value
    it held, the upper triangle past ``d`` and the base diagonal included.
    The columns come from ``T`` itself, so what lies below its diagonal
    (here a sentinel, not 0) reaches no live candidate."""
    rng = np.random.default_rng(d)
    R, S = 16, 256
    insts = [_small_instance(rng, R) for _ in range(2)]
    left, right, x, nl, u, S = prepare_batch(insts, S_pad=S)
    refs = [ltsp_tables_ref(*(np.asarray(v[i]) for v in (left, right, x, nl, u)), S)
            for i in range(2)]
    T_ref = np.stack([r[0] for r in refs]).astype(np.int32)
    C_ref = np.stack([r[1] for r in refs]).astype(np.int16)
    a, b = np.meshgrid(np.arange(R), np.arange(R), indexing="ij")
    before = (b - a < d)[None, :, :, None]  # diagonals already computed
    T0 = np.where(before & (b >= a)[None, :, :, None], T_ref, 777_000).astype(np.int32)
    C0 = np.where(before & (b > a)[None, :, :, None], C_ref, 555).astype(np.int16)
    tiled = (2, R, R, S // 128, 128)
    T1, C1 = ltsp_dp_wavefront(
        *(jnp.asarray(t.reshape(tiled)) for t in (T0, C0)),
        left, right, x, nl, u, d,
        span=None, disjoint=False, interpret=True, cand_tile=8,
    )
    assert C1.dtype == jnp.int16
    T1, C1 = host_table(T1), host_table(C1)
    on = (b - a == d)[None, :, :, None]
    np.testing.assert_array_equal(T1, np.where(on, T_ref, T0))
    np.testing.assert_array_equal(C1, np.where(on, C_ref, C0))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_solve_batch_on_the_interpreter_equals_the_exact_dp(seed):
    """The whole device path on the interpreter, ``solve_batch(...,
    "pallas-interpret")``: rescale, both guards, one bucketed launch per
    shape bucket of one table ``T`` and a 16-bit ``C``, and the device walk.
    Seeded random tapes over several buckets; cost and detours equal
    ``core/dp.py``'s."""
    from conftest import random_instance

    from repro.core import ExecutionContext, solve_batch

    rng = np.random.default_rng(20261022 + seed)
    insts = [random_instance(rng, 2, 40) for _ in range(6)]
    assert len({bucket_shape(i) for i in insts}) > 1
    got = solve_batch(insts, "dp", context=ExecutionContext(backend="pallas-interpret"))
    assert [(r.cost, list(r.detours)) for r in got] == [
        (c, list(d)) for c, d in map(dp_schedule, insts)]
