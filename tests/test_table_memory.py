"""The memory guard of the device DP: a launch whose tables, an int32 ``T``
and an int16 ``C`` of ``B R^2 S`` words each (``ops.table_bytes``), exceed
the device's memory is refused before anything is launched, with an error
that names ``backend='python'``; and every tape of the paper population's
bucket (512, 8192), whose tables take 12.9 GB, passes both guards."""

import dataclasses
import types

import numpy as np
import pytest

from repro.core import ExecutionContext, make_instance, solve_batch
from repro.kernels.ltsp_dp import ops
from repro.kernels.ltsp_dp.ops import (
    DEFAULT_DEVICE_BYTES,
    _int32_admits,
    bucket_shape,
    device_bytes,
    ltsp_solve_batch,
    rescale_instance,
    table_bytes,
)


def test_table_bytes_counts_an_int32_and_an_int16_table():
    assert table_bytes(1, 512, 8192) == 512 * 512 * 8192 * 6 == 12_884_901_888
    assert table_bytes(1, 256, 4096) == 1_610_612_736
    assert table_bytes(16, 256, 4096) == 16 * table_bytes(1, 256, 4096)
    assert table_bytes(1, 512, 16384) == 2 * table_bytes(1, 512, 8192)
    # what fits one v5e's 16 GB, and what does not
    assert table_bytes(1, 512, 8192) < DEFAULT_DEVICE_BYTES < table_bytes(1, 512, 16384)
    assert table_bytes(8, 256, 4096) < DEFAULT_DEVICE_BYTES < table_bytes(16, 256, 4096)


def _device(stats):
    return types.SimpleNamespace(memory_stats=lambda: stats)


@pytest.mark.parametrize("stats, want", [
    (None, DEFAULT_DEVICE_BYTES),
    ({}, DEFAULT_DEVICE_BYTES),
    ({"bytes_limit": 17_000_000_000, "bytes_in_use": 5}, 17_000_000_000),
], ids=["no-stats", "no-limit", "limit"])
def test_the_limit_is_the_devices_own_else_16_gb(monkeypatch, stats, want):
    monkeypatch.setattr(ops.jax, "devices", lambda: [_device(stats)])
    assert device_bytes() == want


def _tape(n_req, per_file):
    """``n_req`` files of width 2 edge to edge, ``per_file`` requests each:
    small coordinates, so the int32 guard admits it."""
    return make_instance(np.arange(n_req) * 2, np.full(n_req, 2),
                         np.full(n_req, per_file), m=2 * n_req, u_turn=3)


def _no_launch(monkeypatch):
    def launch(*_, **__):
        raise AssertionError("a refused batch launched the wavefront")

    monkeypatch.setattr(ops, "ltsp_dp_tables", launch)


def test_a_512_16384_tape_is_refused_before_any_launch(monkeypatch):
    inst = _tape(300, 30)  # n = 9,000
    assert bucket_shape(inst) == (512, 16384)
    assert _int32_admits(rescale_instance(inst)[0])
    _no_launch(monkeypatch)
    with pytest.raises(ValueError, match="backend='python'") as err:
        ltsp_solve_batch([inst], interpret=True)
    assert "R=512, S=16384" in str(err.value)
    # through the solver registry too
    with pytest.raises(ValueError, match="backend='python'"):
        solve_batch([inst], "dp", context=ExecutionContext(backend="pallas-interpret"))


@pytest.mark.parametrize("bucketed", [True, False], ids=["bucketed", "one-launch"])
def test_a_median_bucket_batch_padded_past_the_limit_is_refused(monkeypatch, bucketed):
    """Nine tapes of bucket (256, 4096) pad to B = 16: 25.8 GB of tables
    (bucketed); one launch of the nine at the batch maxima, (9, 150,
    3072), takes 3.7 GB, which a device of 1 GB refuses."""
    insts = [_tape(150, 20) for _ in range(9)]  # n = 3,000
    assert {bucket_shape(i) for i in insts} == {(256, 4096)}
    _no_launch(monkeypatch)
    if not bucketed:
        monkeypatch.setattr(ops.jax, "devices",
                            lambda: [_device({"bytes_limit": 10**9})])
    with pytest.raises(ValueError, match="backend='python'") as err:
        ltsp_solve_batch(insts, interpret=True, bucketed=bucketed)
    B = 16 if bucketed else 9
    R = 256 if bucketed else 150
    S = 4096 if bucketed else 3072
    assert f"(B={B}, R={R}, S={S})" in str(err.value)


def test_what_fits_is_solved_as_before():
    """The guard refuses nothing that fits: a small batch over two buckets
    solves, and its launches record their tables' bytes."""
    from repro.core import dp_schedule
    from repro.obs import KernelProfile

    rng = np.random.default_rng(20261022)
    insts = []
    for n_req in (5, 7, 12):
        size = rng.integers(1, 5, n_req)
        left = np.cumsum(size + rng.integers(0, 3, n_req)) - size
        insts.append(make_instance(left, size, rng.integers(1, 4, n_req),
                                   m=int(left[-1] + size[-1]), u_turn=2))
    profile = KernelProfile()
    assert ltsp_solve_batch(insts, interpret=True, profile=profile) == [
        dp_schedule(i) for i in insts]
    assert [r.table_bytes for r in profile.launches] == [
        table_bytes(r.B_pad, r.R_pad, r.S_pad) for r in profile.launches]


def test_every_fixed_tape_of_the_bucket_512_8192_passes_both_guards():
    """The paper population at the 2**15 grain with U at the mean
    requested-file size: 4 tapes in bucket (512, 8192), each admitted by the
    int32 guard, and one tape's tables within the device's 16 GB."""
    from repro.data.generator import PAPER_PROFILE, generate_dataset, u_turn_values

    prof = dataclasses.replace(PAPER_PROFILE, tape_capacity=2**15)
    u = u_turn_values(generate_dataset(prof))["full_seg"]
    tapes = [t for t in generate_dataset(prof, u_turn=u)
             if bucket_shape(t) == (512, 8192)]
    assert len(tapes) == 4
    assert all(_int32_admits(rescale_instance(t)[0]) for t in tapes)
    ops._check_tables_fit([(1, 512, 8192)])
