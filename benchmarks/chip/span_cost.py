"""What the program's spans cost with no profiler running: one cell's
``decision_p50_ms`` with a ``repro.obs.KernelProfile`` attached and without.

On a machine with a TPU, from the root of a checkout::

    python3 benchmarks/chip/span_cost.py --workload dp.median --seed 7 \\
        --seconds 45 --runs 3

One process runs the cell's set-up once, then ``2 * --runs`` windows of
``--seconds``, alternating which side goes first: ``off`` with the context
the driver's ``--trace 0`` runs use (``obs`` unset), ``on`` with
``Observability(kernel=KernelProfile())`` attached and no profiler session.
It prints one JSON line per window, then the medians of each side.
"""

from __future__ import annotations

import json
import os
import statistics
import sys


def main(argv: list[str]) -> int:
    import argparse

    import harness

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--runs", type=int, default=3)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("the measurement runs on a TPU", file=sys.stderr)
        return 3
    from repro.kernels.compile_cache import enable_compile_cache
    from repro.obs import KernelProfile, Observability

    enable_compile_cache()
    session = harness.Session(cell, args.seed, args.seconds, False)
    contexts = {"off": session._ctx,
                "on": session._ctx.replace(obs=Observability(kernel=KernelProfile()))}
    p50 = harness.load_reader("decision_p50_ms")
    readings: dict[str, list[float]] = {"off": [], "on": []}
    for k in range(args.runs):
        for side in ("off", "on") if k % 2 == 0 else ("on", "off"):
            session._ctx = contexts[side]
            decisions, closed_at, _, _ = session.window(args.seconds)
            value = p50(harness.Run(cell, 0.0, decisions, closed_at))
            readings[side].append(value)
            print(json.dumps({"side": side, "run": k, "decisions": len(decisions),
                              "decision_p50_ms": value}), flush=True)
    print(json.dumps({side: {"median_ms": statistics.median(v), "runs": v}
                      for side, v in readings.items()}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(__file__))
    sys.exit(main(sys.argv[1:]))
