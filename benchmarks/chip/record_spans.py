"""Record a short traced window of one cell with the program's ``ltsp.*``
spans, as test data for their readers.

On a machine with a TPU, from the root of a checkout::

    python3 benchmarks/chip/record_spans.py --workload dp.median --seed 11 \\
        --seconds 2 --out benchmarks/chip/testdata/dp.median.spans.trace.json.gz

It writes what ``record_trace.py`` writes (the reduced trace, each
decision's tape sizes and times, the launches' cell counts, the device) and
beside it the program's spans in the window (``program_spans.to_json``) and
each launch's bytes moved each way; gzipped where ``--out`` ends in
``.gz``.  It prints how many device events carry the kernel's name
(``ltsp_wavefront``) and the scatters' scope (``ltsp_scatter``) in their
text.  ``test_chipbench_spans.py`` reads the readers' numbers back from it.
"""

from __future__ import annotations

import gzip
import json
import shutil
import sys


def main(argv: list[str]) -> int:
    import argparse

    import harness
    import program_spans
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
    path = trace_reduce.find_xplane(str(harness.TRACE_DIR))
    reduced = trace_reduce.load(path)
    launches = session.profile.launches[session.n_setup_launches:]
    record = {
        "workload": cell.name,
        "seed": args.seed,
        "device_kind": device.device_kind,
        "closed_at": closed_at,
        "decisions": [[d.tape.n_req, d.tape.n, d.span, d.due, d.start, d.done]
                      for d in decisions],
        "launches": [[r.real_cells, r.padded_cells, r.h2d_bytes, r.d2h_bytes]
                     for r in launches],
        "trace": trace_reduce.to_json(reduced),
        "program_spans": program_spans.to_json(program_spans.load(path), reduced.window()),
    }
    with (gzip.open if args.out.endswith(".gz") else open)(args.out, "wt") as f:
        json.dump(record, f, separators=(",", ":"))
    for line in _names_in_device_events(path, ("ltsp_wavefront", "ltsp_scatter")):
        print(line)
    print(f"{len(decisions)} decisions, {len(record['program_spans'])} program spans "
          f"-> {args.out}")
    return 0


def _names_in_device_events(path: str, names: tuple[str, ...]) -> list[str]:
    """For each name, how many device events carry it in their text or in a
    stat, and one such event's text and stat keys."""
    import trace_reduce
    from jax.profiler import ProfileData

    events = [
        (e.name, {k: str(v) for k, v in e.stats})
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        for line in plane.lines for e in line.events
    ]
    out = []
    for name in names:
        hits = [(text, stats) for text, stats in events
                if name in text or any(name in v for v in stats.values())]
        out.append(f"device events that carry {name!r}: {len(hits)} of {len(events)}")
        if hits:
            text, stats = hits[0]
            out.append(f"  e.g. {text[:200]!r}; stats {sorted(stats)}")
    return out


if __name__ == "__main__":
    sys.path.insert(0, __import__("os").path.dirname(__file__))
    sys.exit(main(sys.argv[1:]))
