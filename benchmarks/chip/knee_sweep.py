"""The knee of an open-loop cell: the highest rate whose backlog does not
grow over the window.  One process, one rate after another::

    python3 benchmarks/chip/knee_sweep.py --config in2p3-dp --traffic stream \\
        --seed 5 --seconds 30 --rates 3 4 5 6 7

For each rate it prints the cartridges due in the window, those decided by
its close, the backlog at the close, the median and 95th-percentile latency,
and the mean latency of the first and of the second half of the arrivals
(a backlog that grows shows as a second half slower than the first).  The
traffic file then fixes its rate at about 4/5 of the knee.  The pair need
not be a cell of ``BENCHMARK.json`` yet.
"""

from __future__ import annotations

import dataclasses
import json
import sys

import numpy as np

import harness


def sweep(cell: harness.Cell, seed: int, seconds: float, rates: list[float]) -> list[dict]:
    rows = []
    for rate in rates:
        c = dataclasses.replace(cell, traffic={**cell.traffic, "rate_per_s": rate})
        session = harness.Session(c, seed, seconds, False)
        decisions, closed_at, _, _ = session.window(seconds)
        run = harness.Run(c, 0.0, decisions, closed_at)
        lat = np.asarray(run.latencies_s())
        half = len(lat) // 2
        rows.append({
            "rate_per_s": rate,
            "due": len(decisions),
            "decided": sum(d.done is not None and d.done <= closed_at for d in decisions),
            "backlog_at_close": sum(d.start is None for d in decisions),
            "p50_ms": 1e3 * float(np.median(lat)),
            "p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "first_half_mean_ms": 1e3 * float(lat[:half].mean()),
            "second_half_mean_ms": 1e3 * float(lat[half:].mean()),
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv: list[str]) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.make_cell(bench, f"{args.config}.{args.traffic}", args.config, args.traffic)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("the sweep runs on a TPU", file=sys.stderr)
        return 3
    sweep(cell, args.seed, args.seconds, args.rates)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
