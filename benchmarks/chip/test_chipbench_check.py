"""The benchmark's check, on the CPU at small sizes: the run is correct on the
program as it is, and not correct under the float32 control or with the
timed path broken underneath.  Every run here takes the harness's own path
after its look for a chip, through the Pallas interpreter."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

import f32_control
import harness

#: small shape buckets, so the interpreter and the reference take seconds
SMALL = {"max_bucket": [32, 1024], "table_bits": 32}


def small_cell(name, **traffic):
    if name == "dp.stream":  # the open-loop mix, kept for a later cell
        bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        cell = harness.make_cell(bench, name, "in2p3-dp", "stream")
    else:
        cell = harness.load_cell(name)
    return dataclasses.replace(
        cell,
        config=dict(cell.config, chip_limits=SMALL),
        traffic={**cell.traffic, **traffic},
    )


CLOSED = dict(buckets=[[32, 1024]], pool=3, check={"largest": 1, "random": 2})
OPEN = dict(rate_per_s=3.0, check={"largest": 1, "random": 2})


def run(cell, seconds=1.0):
    out = open(os.devnull, "w")
    try:
        return harness.run_cell(cell, 2**32 + 17, seconds, False, 0.0,
                                backend="pallas-interpret", workers=0, out=out)
    finally:
        out.close()


@pytest.mark.parametrize("name, traffic", [("dp.median", CLOSED),
                                           ("logdp1.median", CLOSED),
                                           ("dp.stream", OPEN)])
def test_the_program_as_it_is_runs_correct(name, traffic):
    result = run(small_cell(name, **traffic))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert {"decision_p50_ms", "setup_s"} <= set(result["metrics"])


def test_the_float32_control_is_not_correct(monkeypatch):
    """The reference in float32 in the program's place, through the harness's
    own run: on tapes whose table values pass 2**24 it rounds, and the exact
    comparison catches it."""
    from repro.kernels.ltsp_dp import ops

    monkeypatch.setattr(ops, "ltsp_solve_batch", f32_control.float32_solve_batch)
    big = {"max_bucket": [64, 4096], "table_bits": 32}
    cell = small_cell("dp.median", buckets=[[64, 4096]], pool=4,
                      check={"largest": 2, "random": 2})
    cell = dataclasses.replace(cell, config=dict(cell.config, chip_limits=big))
    result = run(cell, seconds=2.0)
    assert not result["correct"], result["checks"]
    assert result["checks"]["cost_mismatches"][0] > 0, result["checks"]


def _alter_table(monkeypatch):
    """The wavefront's value table off by one: a wrong cost, where it is made."""
    from repro.kernels.ltsp_dp import ops

    real = ops.ltsp_dp_tables

    def broken(*args, **kwargs):
        T, C = real(*args, **kwargs)
        return T + 1, C

    monkeypatch.setattr(ops, "ltsp_dp_tables", broken)


def _alter_schedule(monkeypatch):
    """A valid schedule that is not the optimum, returned with its own cost,
    so that the timed path's own verify passes it."""
    from repro.core import evaluate_detours
    from repro.kernels.ltsp_dp import ops

    real = ops.ltsp_solve_batch

    def broken(instances, *args, **kwargs):
        out = []
        for inst, (_, dets) in zip(instances, real(instances, *args, **kwargs)):
            dets = dets[1:] if dets else [(0, 0)]
            out.append((evaluate_detours(inst, dets), dets))
        return out

    monkeypatch.setattr(ops, "ltsp_solve_batch", broken)


@pytest.mark.parametrize("fault", [_alter_table, _alter_schedule])
@pytest.mark.parametrize("name, traffic", [("dp.median", CLOSED), ("dp.stream", OPEN)])
def test_an_answer_altered_where_it_is_made_is_not_correct(monkeypatch, fault, name, traffic):
    fault(monkeypatch)
    result = run(small_cell(name, **traffic))
    assert not result["correct"], result["checks"]
    assert result["checks"]["cost_mismatches"][0] + result["checks"]["detour_mismatches"][0] > 0


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    cmd = json.loads((harness.ROOT / "BENCHMARK.json").read_text())["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, *cmd[1:], "--workload", "dp.median", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
