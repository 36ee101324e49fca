"""The reader of the wavefront's operand copy rate, ``wavefront_operand_gbps``:
on hand-made events, and silent where it has nothing to read."""

import types

import numpy as np
import pytest

import harness
import tapes
import trace_reduce
from trace_reduce import Interval

KERNEL = Interval("ltsp_wavefront.13", 1_000, 501_000,
                  'custom_call_target="tpu_custom_call"')


def run_with(launches, ops=(KERNEL,)):
    tape = tapes.Tape(np.zeros(8), np.ones(8), np.ones(8, np.int64), 1, 0)
    run = harness.Run(harness.load_cell("dp.median"), 0.0,
                      [harness.Decision(0, tape, None, 0.0, 0.0, 9e-4)], 0.001)
    run.trace = trace_reduce.Reduced({"/device:TPU:0": list(ops)},
                                     [Interval("window", 0, 1_000_000)])
    run.launches = launches
    return run


def read(run):
    return harness.load_reader("wavefront_operand_gbps")(run)


def test_bytes_over_kernel_time():
    # 300 MB in 0.5 ms of kernel
    run = run_with([types.SimpleNamespace(operand_bytes=100_000_000),
                    types.SimpleNamespace(operand_bytes=200_000_000)])
    assert read(run) == pytest.approx(600.0)


def test_silent_without_a_trace_launches_a_kernel_or_the_counter():
    run = run_with([types.SimpleNamespace(operand_bytes=1)])
    run.trace = None
    assert read(run) is None
    assert read(run_with([])) is None
    assert read(run_with([types.SimpleNamespace(operand_bytes=1)], ops=())) is None
    # a program without the counter: the parent of the band-only kernel
    assert read(run_with([types.SimpleNamespace(real_cells=1)])) is None
