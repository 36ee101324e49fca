"""The check's control: the reference in float32, put in the program's place.

The configurations state exact integer tables (int32 in the program, exact
below 2**31, which its guard ensures).  The nearest lower precision is
float32, exact only below 2**24.  The program's own float32 wavefront does
not compile for the TPU (Mosaic refuses its float ``iota``), so the control
replaces the program's batch solve (``ops.ltsp_solve_batch``, under the
solver registry) by the plain reference computed in float32, and then runs
the cell through the harness's own :func:`harness.run_cell`: set-up, a
window at the cell's load, and the same check.  The check has to come out
not correct::

    python3 benchmarks/chip/f32_control.py --workload dp.median --seconds 25 \\
        --seeds 1 2 3

It prints each seed's checks and, last, a JSON object with every reading.
The float32 solves run on the host and are slower than the program's, so the
window is given its own length: long enough for the check's sample to be
decided.  The benchmark's own runs do not run it.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time

import numpy as np

import harness


def float32_solve_batch(instances, span=None, **_):
    """``ops.ltsp_solve_batch``'s answers, from the reference in float32."""
    return [
        harness.ltsp_reference.solve(inst.left, inst.right, inst.mult, inst.m,
                                     inst.u_turn, span, dtype=np.float32)
        for inst in instances
    ]


@contextlib.contextmanager
def in_the_programs_place():
    """The program's batch solve replaced by :func:`float32_solve_batch`."""
    from repro.kernels.ltsp_dp import ops

    real = ops.ltsp_solve_batch
    ops.ltsp_solve_batch = float32_solve_batch
    try:
        yield
    finally:
        ops.ltsp_solve_batch = real


def main(argv: list[str]) -> int:
    import argparse
    import os

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    out = []
    with in_the_programs_place(), open(os.devnull, "w") as quiet:
        for seed in args.seeds:
            t0 = time.perf_counter()
            result = harness.run_cell(cell, seed, args.seconds, False, t0, out=quiet)
            row = {"seed": seed, "correct": result["correct"],
                   "attempted": result["attempted"], "checks": result["checks"],
                   "seconds": time.perf_counter() - t0}
            print(f"control {cell.name}: {json.dumps(row)}", flush=True)
            out.append(row)
    print(json.dumps({"workload": cell.name, "control": "float32", "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
