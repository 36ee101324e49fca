"""Chip benchmark of exact LTSP scheduling: one cell per process.

From the root of a checkout, on a machine with a TPU::

    python3 benchmarks/chip/run.py --workload dp.median --seed 7 --seconds 45 --trace 0

prints information lines, then as its last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``
and, last, ``checks``: each number compared with the reference, beside its
limit.  The same checks are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.  See ``harness.py``.
"""

import time

T_PROCESS = time.perf_counter()

if __name__ == "__main__":
    import sys

    import harness

    sys.exit(harness.main(sys.argv[1:], T_PROCESS))
