"""The tail cell ``dp.paper-tail``: its tapes, its admission and its readers,
on the CPU.  Its tapes' candidate sums may pass int32, which the program's
guard refused before the kernel clipped its tables and saturated its sums;
the cells a reader takes stay below ``2**30 - 1``."""

import numpy as np
import pytest

import harness
import ltsp_reference
import tapes

CELL = "dp.paper-tail"
SEED = 2**31 + 1501


@pytest.fixture(scope="module")
def plan():
    cell = harness.load_cell(CELL)
    return tapes.plan(cell.config, cell.traffic, SEED, 45)


def _scaled(t):
    from repro.core import make_instance
    from repro.kernels.ltsp_dp.ops import rescale_instance

    return rescale_instance(make_instance(t.left, t.size, t.mult, t.m, t.u_turn))[0]


def test_the_plan_draws_64_distinct_fresh_tail_tapes_from_the_seed(plan):
    cell = harness.load_cell(CELL)
    again = tapes.plan(cell.config, cell.traffic, SEED, 45)
    other = tapes.plan(cell.config, cell.traffic, SEED + 1, 45)
    assert plan.due_s is None and len(plan.tapes) == 64
    assert all(tapes.bucket(t) == (256, 8192) for t in plan.tapes)
    assert len({(t.n_req, t.n, t.m) for t in plan.tapes}) == 64
    assert [(t.n, t.mult.tolist()) for t in plan.tapes] == [
        (t.n, t.mult.tolist()) for t in again.tapes]
    assert [t.n for t in plan.tapes] != [t.n for t in other.tapes]
    # none is a tape of the population's fixed dataset
    fixed = {(t.n_req, t.n, t.m) for t in tapes.base_dataset(cell.config, tapes.u_turn(cell.config))}
    assert not fixed & {(t.n_req, t.n, t.m) for t in plan.tapes}


def test_the_old_candidate_bound_refuses_each_and_the_programs_guard_admits_it(plan):
    from repro.kernels.ltsp_dp.ops import _check_int32_safe

    assert all(tapes.table_bound(t) >= 2**31 for t in plan.tapes)
    _check_int32_safe([_scaled(t) for t in plan.tapes])  # raises on a refusal


class _PastTheRangeCheck(Exception):
    pass


def test_the_reference_takes_every_tail_tape_in_its_exact_domain(plan, monkeypatch):
    """``ltsp_reference.solve`` raises its range error before it allocates
    its tables: stopping it at the allocation shows the tape is in range
    without the ten seconds and 5 GB a tail tape's solve takes."""

    def stop(*_, **__):
        raise _PastTheRangeCheck

    monkeypatch.setattr(ltsp_reference.np, "empty", stop)
    for t in plan.tapes:
        with pytest.raises(_PastTheRangeCheck):
            ltsp_reference.solve(t.left, t.right, t.mult, t.m, t.u_turn)


TAIL_READERS = ("wavefront_ms", "wavefront_roofline", "pack_ms", "argmin_fetch_ms",
                "traceback_ms", "idle_unattributed_share")


def test_the_cell_loads_with_its_per_layer_metrics():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.config["policy"] == "dp"
    assert {m["name"] for m in cell.end_to_end} == {"decision_p50_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer}
    assert {f"{name}.tail" for name in TAIL_READERS} <= layer
    assert not {"wavefront_ms.median", "wavefront_roofline.median", "pack_ms",
                "argmin_fetch_ms", "traceback_ms", "idle_unattributed_share"} & layer


@pytest.mark.parametrize("name", ["pack_ms", "argmin_fetch_ms", "traceback_ms",
                                  "idle_unattributed_share"])
def test_a_tail_span_reader_reads_as_the_median_cells_reader(name):
    """Each ``<name>.tail`` reads the program's spans as ``<name>`` does, and
    leaves a program without them out."""
    from test_chipbench_spans import hand_made_run

    tail, median = harness.load_reader(f"{name}.tail"), harness.load_reader(name)
    run = hand_made_run()
    assert tail(run) is not None and tail(run) == median(run)
    assert tail(hand_made_run(with_spans=False)) is None


def test_every_fresh_tail_tape_is_admitted_at_this_grain():
    """Bucket (256, 8192) at 2**15 units: n < 8192, span <= 2**15 and U = 50,
    so the guard's cell bound 4nm stays below 2**30 - 1 and its term bound
    4n (m + U) below 2**31 - 1, whatever the draw."""
    from repro.kernels.ltsp_dp.ops import _int32_admits

    cell = harness.load_cell(CELL)
    rng = np.random.default_rng(np.random.SeedSequence([SEED, 3]))
    u = tapes.u_turn(cell.config)
    drawn = [tapes.draw_tape(cell.config["population"], cell.config["tape_capacity"], rng, u)
             for _ in range(400)]
    tail = [t for t in drawn if tapes.bucket(t) == (256, 8192)]
    assert len(tail) >= 40
    assert all(_int32_admits(_scaled(t)) for t in tail)
    assert all(tapes.fits_chip(t, cell.config["chip_limits"]) for t in tail)
