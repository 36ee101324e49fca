"""The chip benchmark's files, traffic, byte counts and reference, on the CPU."""

import dataclasses
import json
import math
import re

import numpy as np
import pytest

import harness
import ltsp_reference
import tapes
from roofline_bytes import peaks, wavefront_bytes

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def config(name):
    [c] = [c for c in BENCH["configs"] if c["name"] == name]
    return json.loads((harness.ROOT / c["file"]).read_text())


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    cells = {w["name"]: w for w in BENCH["workloads"]}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = list(cells) + [m["name"] for m in metrics] + [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(1 <= len(c[k]) <= 200 and "\n" not in c[k] for k in ("source", "why"))
        assert c["file"].startswith("benchmarks/chip/") and (harness.ROOT / c["file"]).exists()
        assert all(k in config(c["name"]) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").exists()
    for m in metrics:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").exists()
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert set(m.get("workloads", [])) <= set(cells)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        # each cell a per-layer metric reads reports the metric it moves
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configurations_load_and_state_their_policy(name):
    from repro.core.solver import get_solver

    cfg = config(name)
    assert cfg["name"] == name
    assert get_solver(cfg["policy"]).name == cfg["policy"]
    assert cfg["u_turn"] in ("zero", "half_seg", "full_seg")
    assert cfg["cartridges_per_call"] == 1


@pytest.mark.parametrize("name", sorted({w["name"] for w in BENCH["workloads"]}))
def test_every_cell_loads(name):
    cell = harness.load_cell(name)
    assert cell.traffic["loop"] in ("closed", "open")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "decision_p50_ms"}
    assert cell.per_layer


def test_generator_copy_matches_the_program():
    from repro.data.generator import PAPER_PROFILE, generate_dataset, u_turn_values

    cfg = config("in2p3-dp")
    prof = dataclasses.replace(PAPER_PROFILE, tape_capacity=cfg["tape_capacity"])
    theirs = generate_dataset(prof, u_turn=50)
    ours = tapes.base_dataset(cfg, 50)
    for a, b in zip(theirs, ours):
        assert (a.left == b.left).all() and (a.right == b.right).all()
        assert (a.mult == b.mult).all() and (a.m, a.u_turn) == (b.m, b.u_turn)
    assert tapes.u_turn(cfg) == u_turn_values(generate_dataset(prof))["full_seg"] == 50


def test_chip_population_is_the_one_the_cuts_name():
    pop = tapes.chip_population(config("in2p3-dp"))
    buckets = [tapes.bucket(t) for t in pop]
    assert len(pop) == 96
    assert sum(R <= 64 for R, _ in buckets) == 51
    assert sum(R == 128 for R, _ in buckets) == 36
    assert buckets.count((256, 4096)) == 9


def test_median_traffic_draws_fresh_median_bucket_tapes():
    cell = harness.load_cell("dp.median")
    plan = tapes.plan(cell.config, cell.traffic, 2**31 + 7, 45)
    again = tapes.plan(cell.config, cell.traffic, 2**31 + 7, 45)
    assert plan.due_s is None and len(plan.tapes) == cell.traffic["pool"]
    assert all(tapes.bucket(t) == (256, 4096) for t in plan.tapes)
    assert all(tapes.fits_chip(t, cell.config["chip_limits"]) for t in plan.tapes)
    assert len({(t.n_req, t.n, t.m) for t in plan.tapes}) == len(plan.tapes)
    assert [t.n for t in plan.tapes] == [t.n for t in again.tapes]


def test_stream_traffic_sends_the_same_work_from_every_seed():
    cell = harness.make_cell(BENCH, "dp.stream", "in2p3-dp", "stream")
    a = tapes.plan(cell.config, cell.traffic, 5, 45)
    b = tapes.plan(cell.config, cell.traffic, 6_000_000_000, 45)
    n = math.ceil(cell.traffic["rate_per_s"] * 45)
    assert len(a.tapes) == len(b.tapes) == n
    assert sorted(map(tapes.bucket, a.tapes)) == sorted(map(tapes.bucket, b.tapes))
    assert [tapes.bucket(t) for t in a.tapes] != [tapes.bucket(t) for t in b.tapes]
    assert np.allclose(np.sort(np.diff([0.0] + a.due_s)), np.sort(np.diff([0.0] + b.due_s)))
    assert all(tapes.fits_chip(t, cell.config["chip_limits"]) for t in a.tapes)
    counts = {}
    for t in tapes.chip_population(cell.config):
        counts[tapes.bucket(t)] = counts.get(tapes.bucket(t), 0) + 1
    assert set(map(tapes.bucket, a.tapes)) == set(counts)


def _brute_force_words(R, S, span):
    """Words the per-cell recurrence reads and writes, one by one."""
    words = 0
    for a in range(R):
        for b in range(a + 1, R):
            for _s in range(S):
                words += 1  # skip: T[a, b-1, s + x_b]
                for c in range(a + 1, b + 1):
                    if span is None or b - c <= span:
                        words += 2  # T[a, c-1, s] and T[c, b, s]
                words += 2  # T[a, b, s] and C[a, b, s] written
    return words


@pytest.mark.parametrize("R, n", [(2, 3), (5, 7), (9, 4), (12, 10)])
@pytest.mark.parametrize("policy", ["dp", "logdp1"])
def test_wavefront_bytes_counts_the_band(R, n, policy):
    span = ltsp_reference.span_limit(None if policy == "dp" else {"lambda": 1.0}, R)
    assert wavefront_bytes(R, n, span) == 4 * _brute_force_words(R, n + 1, span)


def test_peaks_know_the_v5e_and_nothing_else():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")


def test_span_limit_is_the_papers_logdp():
    from repro.core.dp import logdp_span

    for n_req in (2, 3, 20, 148, 149, 256, 852):
        assert ltsp_reference.span_limit({"lambda": 1.0}, n_req) == logdp_span(n_req, 1.0)
    assert ltsp_reference.span_limit(None, 99) is None


@pytest.mark.parametrize("policy", ["dp", "logdp1"])
def test_reference_equals_the_exact_python_dp(policy):
    from repro.core.dp import dp_schedule

    rule = None if policy == "dp" else {"lambda": 1.0}
    small = [t for t in tapes.base_dataset(config("in2p3-dp"), 50) if t.n_req <= 34][:8]
    from repro.core import make_instance

    for t in small:
        inst = make_instance(t.left, t.size, t.mult, t.m, t.u_turn)
        span = ltsp_reference.span_limit(rule, t.n_req)
        cost, dets = dp_schedule(inst, span=span)
        assert ltsp_reference.solve(t.left, t.right, t.mult, t.m, t.u_turn, span) == (
            cost, sorted(dets))
