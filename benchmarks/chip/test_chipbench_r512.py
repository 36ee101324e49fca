"""The cell ``dp.r512``: its tapes, their admission, the host memory of its
references and its readers, on the CPU.  Its tapes are the population's
recall batches of 257-511 files, bucket (512, 8192), whose two DP tables
take 12.9 GB of one chip's 16."""

import pytest

import harness
import tapes

CELL = "dp.r512"
SEED = 2**31 + 1901


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


@pytest.fixture(scope="module")
def plan(cell):
    return tapes.plan(cell.config, cell.traffic, SEED, 45)


def _scaled(t):
    from repro.core import make_instance
    from repro.kernels.ltsp_dp.ops import rescale_instance

    return rescale_instance(make_instance(t.left, t.size, t.mult, t.m, t.u_turn))[0]


def test_the_plan_draws_32_distinct_fresh_r512_tapes_from_the_seed(cell, plan):
    again = tapes.plan(cell.config, cell.traffic, SEED, 45)
    other = tapes.plan(cell.config, cell.traffic, SEED + 1, 45)
    assert plan.due_s is None and len(plan.tapes) == 32
    assert all(tapes.bucket(t) == (512, 8192) for t in plan.tapes)
    assert all(257 <= t.n_req <= 512 and 4096 <= t.n < 8192 for t in plan.tapes)
    assert len({(t.n_req, t.n, t.m) for t in plan.tapes}) == 32
    assert [(t.n, t.mult.tolist()) for t in plan.tapes] == [
        (t.n, t.mult.tolist()) for t in again.tapes]
    assert [t.n for t in plan.tapes] != [t.n for t in other.tapes]
    # none is a tape of the population's fixed dataset
    fixed = {(t.n_req, t.n, t.m)
             for t in tapes.base_dataset(cell.config, tapes.u_turn(cell.config))}
    assert not fixed & {(t.n_req, t.n, t.m) for t in plan.tapes}


def test_the_programs_guards_admit_each_tape(plan):
    from repro.kernels.ltsp_dp.ops import (
        _check_int32_safe,
        _check_tables_fit,
        bucket_shape,
        table_bytes,
    )

    scaled = [_scaled(t) for t in plan.tapes]
    _check_int32_safe(scaled)  # raises on a refusal
    assert {bucket_shape(s) for s in scaled} == {(512, 8192)}
    _check_tables_fit([(1, 512, 8192)])
    assert table_bytes(1, 512, 8192) == 12_884_901_888


def test_the_four_fixed_tapes_of_the_bucket_are_on_the_chip(cell):
    fixed = tapes.chip_population(cell.config)
    ours = [t for t in fixed if tapes.bucket(t) == (512, 8192)]
    assert len(ours) == 4


@pytest.mark.parametrize("seed", [SEED, 7, 2**31 - 5, 123_456_789_012])
def test_two_sampled_references_stay_under_32_gib_of_host_memory(cell, seed):
    """The check compares the largest decision of the window and one other,
    drawn from the seed; each reference allocates two dense int64 tables of
    n_req^2 (n + 1) words.  A window of 45 s holds about 16-20 decisions
    of the 32 tapes; here it holds 20."""
    plan = tapes.plan(cell.config, cell.traffic, seed, 45)
    window = [harness.Decision(k, plan.tapes[k % len(plan.tapes)], None, 0.0, 0.0, 1.0)
              for k in range(20)]
    sample = harness._sample(window, cell.traffic["check"], seed)
    assert len(sample) == 2
    need = [d.tape.n_req**2 * (d.tape.n + 1) * 16 for d in sample]
    assert sum(need) < 32 * 2**30, [b / 2**30 for b in need]


R512_READERS = ("wavefront_ms.r512", "wavefront_roofline.r512", "table_hbm_share")


def test_the_cell_loads_with_its_metrics(cell):
    assert cell.chips == 1 and cell.config["policy"] == "dp"
    assert cell.config["cartridges_per_call"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"decision_p50_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert set(R512_READERS) <= layer
    assert not {"wavefront_ms.median", "wavefront_ms.tail", "pack_ms",
                "pack_ms.tail"} & layer


@pytest.mark.parametrize("name", R512_READERS)
def test_the_new_readers_read_nothing_without_launches(name):
    from test_chipbench_spans import hand_made_run

    run = harness.Run(harness.load_cell(CELL), 0.0, [], 0.0)
    assert harness.load_reader(name)(run) is None
    # a run of a program whose launch records count no tables
    assert harness.load_reader("table_hbm_share")(hand_made_run()) is None


def test_table_hbm_share_reads_the_largest_launch():
    import types

    run = harness.Run(harness.load_cell(CELL), 0.0, [], 0.0)
    run.peaks = {"hbm_bytes": 16 * 10**9}
    run.launches = [types.SimpleNamespace(table_bytes=b)
                    for b in (1_610_612_736, 12_884_901_888)]
    assert harness.load_reader("table_hbm_share")(run) == pytest.approx(80.5306368)
