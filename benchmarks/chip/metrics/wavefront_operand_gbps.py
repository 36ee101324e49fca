"""HBM rate of the wavefront's operand copies, in GB/s: the bytes its band
copies of the two tables moved over the window's launches
(``LaunchRecord.operand_bytes``, counted by the program from each launch's
shape) over the kernel's device time (device trace)."""

from layers import wavefront_ns


def read(run):
    moved = [getattr(r, "operand_bytes", None) for r in run.launches]
    if not moved or None in moved:
        return None
    ns = wavefront_ns(run)
    return sum(moved) / ns if ns else None  # bytes per ns is GB/s
