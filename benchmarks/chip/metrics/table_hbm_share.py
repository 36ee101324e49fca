"""Share of the chip's HBM that the largest launch of the window holds in
its DP tables, in %: ``LaunchRecord.table_bytes``, counted by the program
from each launch's shape, over ``hbm_bytes`` of peaks.json.  A program that
does not count them reads nothing."""


def read(run):
    held = [getattr(r, "table_bytes", None) for r in run.launches]
    if not held or None in held or run.peaks is None:
        return None
    return 100.0 * max(held) / run.peaks["hbm_bytes"]
