"""The wavefront's band copies and in-place writes, counted on the host:
``band_copy_bytes`` and ``LaunchRecord.operand_bytes`` against a plain loop
over the live programs of every diagonal and the chunks of their bands, and
``LaunchRecord.writeback_bytes`` and ``LaunchRecord.table_bytes`` against
their closed forms."""

import numpy as np
import pytest

from repro.core import make_instance
from repro.kernels.ltsp_dp import ltsp_dp
from repro.kernels.ltsp_dp.ltsp_dp import DEFAULT_CAND_TILE, band_copy_bytes
from repro.kernels.ltsp_dp.ops import bucket_shape, ltsp_solve_batch
from repro.obs import KernelProfile


def loop_bytes(B, R, S, itemsize, span, disjoint, cand_tile):
    """Bytes of the two ``[H, S]`` copies per chunk, program by program."""
    if span is not None:  # one chunk that holds span + 1 rows from any start
        H = span + 8
        while H % 8:
            H += 1
    else:
        H = cand_tile
    H = min(H, R)
    chunks = 0
    for d in range(1, R):
        for a in range(R - d):  # the live programs; the rest copy nothing
            b = a + d
            lo = a + 1
            if span is not None:
                lo = max(lo, b - span)
            if disjoint and a > 0:
                lo = b + 1
            # rows k = c - 1 of the live c, and the skip's row b - 1
            first = min(lo, b) - 1
            first -= first % 8
            chunks += -(-(b - first) // H)
    return chunks * B * 2 * H * S * itemsize


@pytest.mark.parametrize("span, disjoint", [(None, False), (5, False), (None, True)],
                         ids=["dp", "logdp-span5", "simpledp-disjoint"])
@pytest.mark.parametrize("B, R, S", [(1, 256, 4096), (4, 16, 512)],
                         ids=["median-bucket", "small-bucket"])
def test_band_copy_bytes_equals_a_plain_loop(B, R, S, span, disjoint):
    got = band_copy_bytes(B, R, S, 4, span, disjoint, DEFAULT_CAND_TILE)
    assert got == loop_bytes(B, R, S, 4, span, disjoint, DEFAULT_CAND_TILE)


def test_band_copy_bytes_at_the_median_bucket():
    """The exact DP in 16-row chunks copies 3,153,920 rows of 16 KiB a
    decision at (256, 4096), LOGDP(1) at span 5 in one 16-row chunk per
    program 522,240: a sixth of the exact DP's."""
    row = 4096 * 4
    assert band_copy_bytes(1, 256, 4096, 4, None, False, 16) == 2 * 3_153_920 * row
    assert band_copy_bytes(1, 256, 4096, 4, 5, False, 16) == 2 * 522_240 * row


def _bucket_16_128():
    """Two instances of the bucket (16, 128), so one launch of B = 2."""
    rng = np.random.default_rng(20261019)
    insts = []
    for n_req in (12, 15):
        size = rng.integers(1, 4, n_req)
        left = np.cumsum(size + rng.integers(0, 3, n_req)) - size
        insts.append(make_instance(left, size, rng.integers(1, 4, n_req),
                                   m=int(left[-1] + size[-1]), u_turn=3))
    assert {bucket_shape(i) for i in insts} == {(16, 128)}
    return insts


@pytest.mark.parametrize("span, disjoint", [(None, False), (5, False), (None, True)],
                         ids=["dp", "logdp-span5", "simpledp-disjoint"])
def test_launch_record_counts_the_band_copies(span, disjoint):
    insts = _bucket_16_128()
    profile = KernelProfile()
    ltsp_solve_batch(insts, span=span, disjoint=disjoint, interpret=True,
                     profile=profile)
    [rec] = profile.launches
    assert (rec.B_pad, rec.R_pad, rec.S_pad) == (2, 16, 128)
    assert rec.operand_bytes == loop_bytes(2, 16, 128, 4, span, disjoint,
                                           DEFAULT_CAND_TILE) > 0
    assert profile.summary()["operand_bytes"] == rec.operand_bytes


@pytest.mark.parametrize("span, disjoint", [(None, False), (5, False), (None, True)],
                         ids=["dp", "logdp-span5", "simpledp-disjoint"])
def test_launch_record_counts_the_in_place_writes(span, disjoint, monkeypatch):
    """Each live program writes one S-word row of T (int32) and one of C
    (int16): B R (R - 1) / 2 rows of 4 S + 2 S bytes a launch, whatever the
    policy.  Recording the counts of a second launch of one shape counts no
    band again."""
    insts = _bucket_16_128()
    profile = KernelProfile()
    ltsp_solve_batch(insts, span=span, disjoint=disjoint, interpret=True,
                     profile=profile)
    host_counts = []
    real = ltsp_dp.band_chunks

    def counted(*args, **kwargs):
        if kwargs.get("xp") is np:
            host_counts.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(ltsp_dp, "band_chunks", counted)
    ltsp_solve_batch(insts, span=span, disjoint=disjoint, interpret=True,
                     profile=profile)
    assert host_counts == []
    first, second = profile.launches
    B, R, S = 2, 16, 128
    assert first.writeback_bytes == second.writeback_bytes == B * R * (R - 1) // 2 * S * 6
    assert first.operand_bytes == second.operand_bytes
    assert first.table_bytes == B * R * R * S * 6
    assert profile.summary()["writeback_bytes"] == 2 * first.writeback_bytes


def test_writeback_bytes_at_the_median_bucket():
    """0.80 GB a launch at (1, 256, 4096): a 16 KiB row of T and an 8 KiB
    row of C for each of the 32,640 live programs."""
    assert ltsp_dp.writeback_bytes(1, 256, 4096) == 32_640 * 24_576 == 802_160_640


def test_band_copies_and_writes_at_the_bucket_512_8192():
    """At (1, 512, 8192), in 32-row chunks: 1,629.9 GB of band copies a
    launch, a row and a column band of T for every chunk, and 6.43 GB of
    in-place writes, half of what three int32 tables wrote."""
    assert band_copy_bytes(1, 512, 8192, 4, None, False, 32) == loop_bytes(
        1, 512, 8192, 4, None, False, 32)
    assert round(band_copy_bytes(1, 512, 8192, 4, None, False, 32) / 1e8) == 16_299
    assert ltsp_dp.writeback_bytes(1, 512, 8192) == 130_816 * 8192 * 6
    assert 2 * ltsp_dp.writeback_bytes(1, 512, 8192) == 3 * 130_816 * 8192 * 4
