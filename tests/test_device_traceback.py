"""The device traceback (:func:`repro.kernels.ltsp_dp.walk.traceback_device`)
replays the argmin plane exactly as the host reference
:func:`~repro.kernels.ltsp_dp.ops.traceback_detours` does: the same detours
in the same order, for every row of a launch, on planes from the python DP
and from the wavefront under each band layout, captured or not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dp_schedule, make_instance, simpledp_schedule
from repro.core.dp import dp_schedule_warm
from repro.kernels.ltsp_dp.ltsp_dp import ARGMIN_DTYPE, ltsp_dp_tables
from repro.kernels.ltsp_dp.ops import (
    bucket_shape,
    host_table,
    ltsp_solve_batch,
    prepare_batch,
    rescale_instance,
    traceback_detours,
)
from repro.kernels.ltsp_dp.walk import traceback_device


def _instance(n_req: int, seed: int):
    """Random instance of ``n_req`` files."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 50, size=n_req) + rng.integers(0, 2, size=n_req)
    gaps = rng.integers(0, 40, size=n_req + 1)
    left, pos = [], int(gaps[0]) + 1
    for i in range(n_req):
        left.append(pos)
        pos += int(sizes[i] + gaps[i + 1])
    mult = rng.integers(1, 8, size=n_req)
    return make_instance(left, sizes, mult, m=pos, u_turn=int(rng.integers(5, 30)) + 1)


def _check(T, C, x, expected):
    """The device walk of ``(T, C, x)`` against the host walk of each row,
    and the real rows' host walks against the policy's own ``expected``."""
    dets, n_dets, root, steps = jax.device_get(traceback_device(T, C, x))
    T, C, x = host_table(T), host_table(C), np.asarray(x)
    B, R = x.shape
    host = [traceback_detours(C[i], x[i]) for i in range(B)]
    assert host[: len(expected)] == expected
    assert all(h == [] for h in host[len(expected):])  # phantom rows only skip
    for i in range(B):
        assert [tuple(d) for d in dets[i, : n_dets[i]].tolist()] == host[i]
        assert (dets[i, n_dets[i]:] == -1).all()
    assert root.dtype == T.dtype and root.tolist() == T[:, 0, R - 1, 0].tolist()
    assert steps.tolist() == [R - 1] * B
    assert any(host)  # the cases are chosen to have detours


def _kernel_tables(insts, B_pad=None, span=None, disjoint=False):
    """One interpreted wavefront launch over ``insts``' shared bucket."""
    scaled = [rescale_instance(i)[0] for i in insts]
    R_pad = max(bucket_shape(s)[0] for s in scaled)
    S_pad = max(bucket_shape(s)[1] for s in scaled)
    left, right, x, nl, u, S = prepare_batch(
        scaled, R_pad=R_pad, S_pad=S_pad, B_pad=B_pad)
    T, C = ltsp_dp_tables(left, right, x, nl, u, S=S, span=span, disjoint=disjoint,
                          interpret=True)
    return T, C, x


def _core_dp_planes(*shapes):
    """Dense planes holding the python DP's recorded choices and root
    values, instances of ``(n_req, seed)`` in one ``[B, R, R, S]``, laid
    out as the wavefront lays its tables out, ``[B, R, R, S/128, 128]``."""
    insts = [_instance(n_req, seed) for n_req, seed in shapes]
    B, R, S = len(insts), max(i.n_req for i in insts), 128
    T = np.zeros((B, R, R, S), np.int32)
    C = np.full((B, R, R, S), -1, np.int32)
    x = np.zeros((B, R), np.int32)
    expected = []
    for i, inst in enumerate(insts):
        _, dets, warm, _ = dp_schedule_warm(inst)
        for (a, b, s), c in warm.store._choice.items():
            C[i, a, b, s] = c
        T[i, 0, R - 1, 0] = warm.store._memo[(0, inst.n_req - 1, 0)]
        x[i, : inst.n_req] = inst.mult
        expected.append(dets)
    tiled = (B, R, R, S // 128, 128)
    _check(jnp.asarray(T.reshape(tiled)), jnp.asarray(C.reshape(tiled)),
           jnp.asarray(x), expected)


def _phantom_rows():
    insts = [_instance(5, 11), _instance(12, 184), _instance(6, 13)]
    _check(*_kernel_tables(insts, B_pad=8), [dp_schedule(i)[1] for i in insts])


def _logdp_span():
    insts = [_instance(12, 70), _instance(12, 74)]  # the span changes their schedules
    _check(*_kernel_tables(insts, span=2), [dp_schedule(i, span=2)[1] for i in insts])


def _simpledp_disjoint():
    insts = [_instance(12, 87), _instance(12, 89)]  # the band clip changes theirs
    _check(*_kernel_tables(insts, disjoint=True), [simpledp_schedule(i)[1] for i in insts])


def _capture():
    insts = [_instance(7, 41), _instance(8, 42), _instance(3, 43)]
    results, stores = ltsp_solve_batch(insts, interpret=True, capture=True)
    assert results == ltsp_solve_batch(insts, interpret=True)
    assert results == [dp_schedule(i) for i in insts]
    for inst, (_, dets), store in zip(insts, results, stores):
        # the captured plane, walked on the host, gives the device's detours
        x = np.zeros(store._choice.shape[0], np.int64)
        x[: inst.n_req] = inst.mult
        assert traceback_detours(store._choice, x) == dets


@pytest.mark.parametrize("case", [
    # the first instance of each holds two pending frames at once, so the
    # order the walk resumes them in shows in the detours
    pytest.param(lambda: _core_dp_planes((9, 82), (6, 101)), id="core-dp-planes-a"),
    pytest.param(lambda: _core_dp_planes((9, 178), (12, 38)), id="core-dp-planes-b"),
    pytest.param(lambda: _core_dp_planes((12, 10), (12, 109), (4, 3)), id="core-dp-planes-c"),
    pytest.param(_phantom_rows, id="wavefront-B8-phantom-rows"),
    pytest.param(_logdp_span, id="wavefront-logdp-span2"),
    pytest.param(_simpledp_disjoint, id="wavefront-simpledp-disjoint"),
    pytest.param(_capture, id="solve-capture"),
])
def test_the_device_walk_replays_the_host_walk(case):
    case()


def test_the_walk_compiles_once_per_shape():
    """Span, band clip and policy live in ``C`` alone: one compile serves
    them all at one ``(B, R, S)``."""
    insts = [_instance(12, 89), _instance(11, 62)]
    variants = [{}, {"span": 2}, {"span": 3}, {"disjoint": True}]
    tables = [_kernel_tables(insts, **kw) for kw in variants]
    before = traceback_device._cache_size()
    outs = [jax.device_get(traceback_device(*t)) for t in tables]
    assert traceback_device._cache_size() - before <= 1
    assert len({o[0].tobytes() for o in outs}) > 1  # the walks differ


@pytest.mark.parametrize("skip_odd", [False, True], ids=["detours", "detours-and-skips"])
def test_the_16_bit_plane_stores_and_decodes_every_argmin_of_r512(skip_odd):
    """The argmin plane holds -1 .. R - 1 in 16 bits: every value of the
    bucket R = 512 survives the kernel's store (an int32 cast to
    ``ARGMIN_DTYPE``) and the walk's decode, which widens what it reads.  A
    plane whose cells ``(0, b)`` take the detour ``(b, b)`` walks through
    ``c = 511 .. 1``; with every odd ``b`` skipped it reads -1 too.  The
    device walk equals the host walk."""
    values = np.arange(-1, 512, dtype=np.int32)
    stored = jnp.asarray(values).astype(ARGMIN_DTYPE)
    assert stored.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(stored.astype(jnp.int32)), values)

    R, S = 512, 128
    b = np.arange(R)
    C = np.full((1, R, R, S), -1, np.int16)
    C[0, 0, b] = np.where(skip_odd & (b % 2 == 1), -1, b)[:, None]
    want = [(int(c), int(c)) for c in range(R - 1, 0, -1)
            if not (skip_odd and c % 2 == 1)]
    tiled = (1, R, R, S // 128, 128)
    _check(jnp.zeros(tiled, jnp.int32), jnp.asarray(C.reshape(tiled)),
           jnp.zeros((1, R), jnp.int32), [want])
