"""Record a short traced window of one cell as test data for the reduction.

On a machine with a TPU, from the root of a checkout::

    python3 benchmarks/chip/record_trace.py --workload dp.median --seed 11 \\
        --seconds 2 --out benchmarks/chip/testdata/dp.median.trace.json.gz

It runs the cell's set-up and a window of ``--seconds`` under the profiler,
as a ``--trace 1`` run does, and writes the reduced trace
(``trace_reduce.to_json``), each decision's tape sizes and times, the
launches' cell counts, and the device it ran on; gzipped where ``--out``
ends in ``.gz``.  ``test_chipbench_trace.py``
reads the readers' numbers back from it.
"""

from __future__ import annotations

import gzip
import json
import shutil
import sys
import time

T_PROCESS = time.perf_counter()


def main(argv: list[str]) -> int:
    import argparse

    import harness
    import trace_reduce

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print("the recording runs on a TPU", file=sys.stderr)
        return 3
    session = harness.Session(cell, args.seed, args.seconds, True)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(harness.TRACE_DIR))
    decisions, closed_at, _, _ = session.window(args.seconds)
    jax.profiler.stop_trace()
    reduced = trace_reduce.load(trace_reduce.find_xplane(str(harness.TRACE_DIR)))
    launches = session.profile.launches[session.n_setup_launches:]
    record = {
        "workload": cell.name,
        "seed": args.seed,
        "device_kind": device.device_kind,
        "closed_at": closed_at,
        "decisions": [[d.tape.n_req, d.tape.n, d.span, d.due, d.start, d.done]
                      for d in decisions],
        "launches": [[r.real_cells, r.padded_cells] for r in launches],
        "trace": trace_reduce.to_json(reduced),
    }
    with (gzip.open if args.out.endswith(".gz") else open)(args.out, "wt") as f:
        json.dump(record, f, separators=(",", ":"))
    print(f"{len(decisions)} decisions, {sum(map(len, record['trace']['device_ops'].values()))} "
          f"device operations -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, __import__("os").path.dirname(__file__))
    sys.exit(main(sys.argv[1:]))
