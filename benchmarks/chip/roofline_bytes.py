"""HBM bytes of the wavefront's recurrence, and the chip peaks to divide by.

:func:`wavefront_bytes` counts, for one tape, the 4-byte words that the
per-cell recurrence reads and writes when every operand comes from HBM: for
each cell ``(a, b)`` with ``a < b`` and each of the ``n + 1`` skip counts,
the band's row and column terms (``T[a, c-1]`` and ``T[c, b]`` for each live
candidate ``c``: ``a < c <= b``, and ``b - c <= span`` under a span limit),
the skip term's read, and the writes of the value and the argmin.  It
depends only on the tape's real ``n_req`` and ``n`` and on the policy's
span, not on how a kernel pads or schedules the work.  It is not a floor
for a kernel that reuses operands on the chip: such a kernel moves fewer
bytes, and its share would pass 100%.  No VPU integer peak is published, so
the roofline is by bytes alone.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["wavefront_bytes", "peaks"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def wavefront_bytes(n_req: int, n: int, span: int | None) -> int:
    """``4 (n + 1) sum_{a<b} (2 min(b - a, span + 1) + 3)`` bytes."""
    words = 0
    for d in range(1, n_req):
        band = d if span is None else min(d, span + 1)
        words += (n_req - d) * (2 * band + 3)
    return 4 * (n + 1) * words


def peaks(device_kind: str) -> dict:
    """Published peaks of a chip, by the ``device_kind`` JAX reports.  A chip
    that is not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table["devices"][device_kind]
