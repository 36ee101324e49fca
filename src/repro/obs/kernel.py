"""Kernel launch profiling: bucket shapes, padding waste, bytes moved, spans.

The device path (:mod:`repro.kernels.ltsp_dp.ops`) launches one bucketed
wavefront per power-of-two ``(R, S, B)`` shape.  A :class:`KernelProfile`
attached through ``ExecutionContext.obs`` records one
:class:`LaunchRecord` per launch:

* the padded bucket shape and the **exact** real-vs-padded DP cell counts
  (``padded = B_pad * R_pad * R_pad * S_pad``; ``real`` sums each
  instance's ``n_req^2 * (n + 1)`` table) — the padding-waste ratio the
  ROADMAP's ragged-grid item targets, as an exact fraction;
* ``cold`` — whether this profile has seen the launch's jit signature
  (shape bucket x dtype x interpret x band layout) before: a cold
  launch's ``ltsp.dispatch`` span includes trace+compile, a warm one is
  enqueue-only.  (Scoped to the profile: a fresh profile on a warm process
  marks the first launch cold even though jax's jit cache may already hold
  it.)
* ``h2d_bytes`` / ``d2h_bytes`` — the exact bytes the launch moved to the
  device (the packed arrays) and back to the host (the device traceback's
  detours, detour counts, root values and step counts, and both dense
  planes when captured);
* ``walk_steps`` — the loop steps of the device traceback, summed over the
  launch's rows (all-phantom padding rows included);
* ``operand_bytes`` — the exact HBM bytes the wavefront's band copies of
  its two table operands move over the launch's diagonals
  (``ltsp_dp.band_copy_bytes``), counted from the launch's shape;
* ``writeback_bytes`` — the exact HBM bytes the wavefront writes in place
  over the launch's diagonals: one ``S``-word row of its int32 value table
  and one of its int16 argmin plane per live program
  (``ltsp_dp.writeback_bytes``, in closed form);
* ``table_bytes`` — the HBM bytes the launch's two tables take on the
  device, ``B R^2 S (4 + 2)`` (``ops.table_bytes``): what the memory guard
  holds to the device's memory.

Time comes from :meth:`KernelProfile.span`: the device path opens one
``jax.profiler.TraceAnnotation`` per phase (``ltsp.rescale`` …
``ltsp.release``), each tagged with the index of its launch's record, so a
``jax.profiler`` trace shows the host's phases on the device's clock.
Without a profiler session running they record nothing; without a profile
attached no annotation is built at all.
"""

from __future__ import annotations

import dataclasses

__all__ = ["LaunchRecord", "KernelProfile"]


@dataclasses.dataclass(frozen=True)
class LaunchRecord:
    """One device launch: shape, exact cell and byte accounting."""

    n_instances: int
    R_pad: int
    S_pad: int
    B_pad: int
    real_cells: int
    padded_cells: int
    interpret: bool
    cold: bool
    h2d_bytes: int
    d2h_bytes: int
    walk_steps: int = 0
    operand_bytes: int = 0
    writeback_bytes: int = 0
    table_bytes: int = 0

    @property
    def waste(self) -> tuple[int, int]:
        """Padding waste as the exact fraction ``(wasted, padded)`` cells."""
        return (self.padded_cells - self.real_cells, self.padded_cells)


class KernelProfile:
    """Accumulates :class:`LaunchRecord` rows across a run.

    ``wall`` is accepted and has no effect: host wall time around a launch
    is gone, replaced by the spans of :meth:`span`.  The benchmark's harness
    still passes ``wall=False``; the keyword goes once it no longer does.
    """

    def __init__(self, *, wall: bool = False):
        del wall
        self.launches: list[LaunchRecord] = []
        self._seen: set[tuple] = set()

    def record(
        self,
        *,
        signature: tuple,
        n_instances: int,
        R_pad: int,
        S_pad: int,
        B_pad: int,
        real_cells: int,
        interpret: bool,
        h2d_bytes: int,
        d2h_bytes: int,
        walk_steps: int = 0,
        operand_bytes: int = 0,
        writeback_bytes: int = 0,
        table_bytes: int = 0,
    ) -> None:
        cold = signature not in self._seen
        self._seen.add(signature)
        self.launches.append(
            LaunchRecord(
                n_instances=n_instances,
                R_pad=R_pad,
                S_pad=S_pad,
                B_pad=B_pad,
                real_cells=real_cells,
                padded_cells=B_pad * R_pad * R_pad * S_pad,
                interpret=interpret,
                cold=cold,
                h2d_bytes=h2d_bytes,
                d2h_bytes=d2h_bytes,
                walk_steps=walk_steps,
                operand_bytes=operand_bytes,
                writeback_bytes=writeback_bytes,
                table_bytes=table_bytes,
            )
        )

    def span(self, name: str, **args):
        """A profiler span of one phase of the launch this profile records
        next, tagged ``launch=<its index in launches>``."""
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name, launch=len(self.launches), **args)

    def summary(self) -> dict:
        """Exact totals: launch counts, cell and byte accounting."""
        real = sum(r.real_cells for r in self.launches)
        padded = sum(r.padded_cells for r in self.launches)
        return {
            "n_launches": len(self.launches),
            "n_cold": sum(1 for r in self.launches if r.cold),
            "n_instances": sum(r.n_instances for r in self.launches),
            "real_cells": real,
            "padded_cells": padded,
            "wasted_cells": padded - real,
            "h2d_bytes": sum(r.h2d_bytes for r in self.launches),
            "d2h_bytes": sum(r.d2h_bytes for r in self.launches),
            "walk_steps": sum(r.walk_steps for r in self.launches),
            "operand_bytes": sum(r.operand_bytes for r in self.launches),
            "writeback_bytes": sum(r.writeback_bytes for r in self.launches),
        }

    def __len__(self) -> int:
        return len(self.launches)
