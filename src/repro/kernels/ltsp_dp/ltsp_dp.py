"""Pallas TPU kernel for the LTSP DP wavefront — single-trace, batched,
traceback-capable, each program copying and scanning only its live band.

TPU adaptation of the paper's CPU dynamic program (DESIGN.md §Hardware
adaptation): the O(n_req) inner minimisation of ``detour_c`` is the compute
hot-spot (O(n_req^3 · n) total).  On TPU the per-cell scalar loop becomes a
dense candidate tile in VMEM reduced with ``min`` on the VPU — the ``s`` axis
(skip count) is the 128-lane vector axis, the ``c`` candidate axis is the
sublane axis.

The whole table is built in **one trace**: :func:`ltsp_dp_tables` runs a
jitted ``lax.fori_loop`` over the diagonal index ``d`` whose carry is the
table workspace; each launch writes its diagonal into the carried tables in
place (*Writing the diagonal in place* below), and the kernel receives ``d``
as a scalar-prefetch operand, so the same compiled kernel serves every
diagonal.  The same kernel runs compiled
(Mosaic, ``interpret=False``) and through the Pallas interpreter
(``interpret=True``); every construct below is one Mosaic lowers.

Table layout
------------
The value table ``T`` is ``[B, R, R, S/128, 128]`` int32 and the argmin
plane ``C`` the same shape in int16: the skip-count axis is split into tile
rows of 128 lanes, so cell ``(a, b)`` of instance ``i`` is the ``(S/128,
128)`` tile ``T[i, a, b]``, whose element ``(p, l)`` is skip count ``s = 128
p + l``.  ``(i, a, b)`` are all leading, untiled dimensions, so a cell's row
is one tile-aligned block (on ``[R, R, S]`` it would be one row of an ``(8,
128)`` tile, which no block or copy at a dynamic ``b`` can name).  Below ``S
= 1024`` a tile pads to 8 rows in HBM (16 in ``C``); those tables are at
most a few MiB.  ``C`` holds -1 .. R - 1, so 16 bits hold it up to R =
2**15; the two tables take ``B R^2 S (4 + 2)`` bytes, 12.9 GB at the bucket
(512, 8192).

A program computing cell ``(a, b)`` reads row ``a`` of the value table,
``T[a, :]``, and column ``b``, ``T[:, b]``.  Both come from ``T`` itself:
in this layout row ``c`` of column ``b`` is the tile ``T[i, c, b]``, so ``H``
rows of a column are one strided copy of ``H`` tile-aligned blocks,
``T.at[i, pl.ds(c0, H), b]``.  Candidate ``c`` reads ``T[a, c - 1, s]`` and
``T[c, b, s]``: a chunk of the band at row ``k0`` of ``T[a]`` (``k = c - 1``)
takes the column's rows from ``c0 = k0 + 1``, clamped to ``R - H``, so that
the copy stays inside the table.  Where the clamp moves it (``k0 = R - H``),
row ``h`` of the chunk sits at ``h + 1`` of the column's copy, and the
chunk's last row (``c = R``) is no candidate.

Both tables stay in HBM (``memory_space=pl.ANY``).  A program copies only
the rows of its band (below) from ``T[i, a]`` and column ``T[i, :, b]`` into
two VMEM slots of ``[H, S/128, 128]`` each, with ``pltpu.make_async_copy``:
while chunk
``j`` is scanned, chunk ``j + 1`` is in flight, and a program's last chunk
starts the first chunk of the next live program in grid order, so no
program waits for its first copy but the grid's first.  The skip's row
``T[a, b - 1]`` is the band's last row and needs no copy of its own.

Per-file scalars (``left/right/x/nl`` flattened to ``[B * R]``, and ``u``)
ride as scalar-prefetch operands in SMEM, read by ``program_id``; the two
per-candidate scalars ``right[c - 1]`` and ``nl[c]`` are read there too, one
pair per band row.

Writing the diagonal in place
-----------------------------
``T`` and ``C`` enter the launch as inputs aliased
(``input_output_aliases``) to its two outputs, each in blocks of one cell's
tile ``(1, 1, 1, S/128, 128)``: program ``(i, a)`` writes its value to ``T[i,
a, a + d]`` and its argmin, cast to 16 bits, to ``C[i, a, a + d]``; nothing
else touches the tables between launches.  A launch reads only diagonals
``< d`` and the rows of its own window, so no program reads what another
writes in it.  A dead program (``a + d >= R``) maps its two blocks to those
of the diagonal's last live program, ``(i, R - d - 1)``, and writes nothing.
The dead programs of an instance follow that program in grid order, so the
pipeline sees the same block index again, keeps the live program's output
in place, and writes it back once, unchanged, when the index moves on (the
accumulator pattern).  So no cell outside diagonal ``d`` is written: the
lower triangle keeps 0 in ``T`` and -1 in ``C``.

Banded candidate scan
---------------------
A cell ``(a, b)`` on diagonal ``d = b - a`` has exactly ``d`` detour
candidates ``c in (a, b]`` (fewer under a LOGDP span restriction; none on
non-root cells under the SIMPLEDP ``disjoint=True`` restriction, which clips
the candidate band to ``a == 0`` cells — forbidding detours inside detours
collapses the table to SIMPLEDP's 2-D recursion exactly).  A program's band
is the rows ``k = c - 1`` of those candidates and of the skip's row
``k = b - 1``, from the first one aligned down to a multiple of 8 (the
sublane tile) to ``b - 1`` (:func:`band_chunks`).  The kernel walks it in
chunks of ``H`` rows (:func:`chunk_rows`: ``cand_tile``, or under a LOGDP
span the one chunk of ``span + 8`` rows, rounded up to 8, that holds any
band whole): a ``fori_loop`` over the chunks, and within a chunk over its
rows, each an ``(S/128, 128)`` slab folded elementwise into a running
``(min, argmin)``; rows outside the live candidates are lifted to ``big``
first.  Rows ascend in ``c`` and the fold improves strictly, so the result
is the *smallest* minimising ``c`` — identical tie-breaking to the exact
Python DP (skip wins ties against detours; among detours the smallest ``c``
wins).  The last chunk base is clamped to ``R - H``; the overlap
re-evaluates candidates the strict fold ignores.  A program with ``a + d >=
R`` lies past the diagonal's end: its band is empty, and it copies, computes
and writes nothing.  :func:`band_copy_bytes` counts the bytes the copies
move and :func:`writeback_bytes` those the output blocks write.

``dimension_semantics`` audit of the ``(B, R)`` grid: the batch dimension
indexes independent instances and the window-start dimension indexes cells of
*one* anti-diagonal, which only read diagonals ``< d`` (frozen in this launch)
and write disjoint output blocks (a dead program revisits a live one's block
and writes nothing) — no program on the grid observes another's write.  The
copies run ahead across programs in grid order, though, so both dimensions
are declared ``"arbitrary"``: a chip that split the grid over cores would
leave a core's first program waiting on a copy nobody started.
Compiled mode only; the interpreter runs the grid in order.

The kernel additionally emits a per-cell **argmin plane** ``C[a, b, s]``
(-1 = "skip b", else the winning detour start ``c``) so a host-side traceback
(:mod:`.ops`) can reconstruct the optimal detour list — the device path is a
complete solver, not a value oracle.

Batching: the grid is ``(B, R)`` — several padded instances solve in one
launch.  Padded files (zero width, zero multiplicity, at the rightmost
coordinate) provably never win a detour choice, so padding changes neither the
root value nor the traceback; all-phantom padding *rows* (batch-dimension
padding, see ``ops.prepare_batch``) are simply never traced back.

Layout notes
------------
* ``S`` must be a multiple of 128 (lane width).  When the band runs
  compiled in chunks shorter than ``R``, ``H`` and ``R`` must be multiples
  of 8 (sublane tile); the power-of-two buckets of :mod:`.ops` are.
* ``cand_tile`` is the chunk height ``H`` (sublane axis) of the exact DP's
  band, at most ``R``: 32 by default, the fastest of 16, 32 and 64 on a
  v5e at the buckets (256, 4096) and (256, 8192).
* The value table is ``int32``, exact under the guard of :mod:`.ops` (see
  *Saturating sums* below), and :func:`ltsp_dp_tables` refuses inputs of
  any other dtype; the argmin plane is ``int16`` (:data:`ARGMIN_DTYPE`).
* The ``skip`` term needs the shifted gather ``row[min(s + x_b, S - 1)]``;
  ``x_b`` is a scalar per program.  With ``q = x_b // 128`` and ``r = x_b %
  128``, element ``(p, l)`` reads lane ``l + r`` of tile row ``p + q``, or
  lane ``l + r - 128`` of row ``p + q + 1``: one lane rotation by ``-r``,
  two reads of the rotated row from tile rows ``q`` and ``q + 1`` on (a
  small VMEM buffer, as a dynamic sublane rotation of fewer than 8 rows does
  not lower), a select on ``l + r < 128``, and a mask that substitutes
  ``row[S - 1]`` where ``s + x_b`` passes the end.

Saturating sums
---------------
An int32 table stores every value clipped to :data:`INT32_CAP`
(``2**30 - 1``), so the two table values of a candidate add without
wrapping.  Its two linear terms are one affine function of ``s`` per
candidate row, and :func:`_add_sat` adds it clipped at ``big = 2**31 - 1``
instead of wrapping; the skip's terms are added the same way.  A lane ``s``
reads only lanes ``>= s`` of other cells (the skip reads ``s + x_b``, the
candidates the same ``s``), and every cell a reader takes (the root and the
cells the traceback or a warm start's ``DenseStore`` reads) has ``s <= n``.
The guard of :mod:`.ops` admits an instance only when (i) every term and
product the kernel forms at the lanes ``s <= n`` is below ``big``, so none
wraps there, and (ii) every cell a reader takes is below ``INT32_CAP``.
Those cells read only cells of their own kind, so by induction over the
diagonals their operands are exact, each candidate and the skip read
``min(true sum, big)``, and clipping is monotone and leaves values below
``big`` alone: the clipped minimum is the true minimum, a candidate reads it
exactly when its true sum is it, the smallest minimising ``c`` and the
skip's tie win are unchanged, and the clip to ``INT32_CAP`` leaves the
result alone.  The lanes past ``n`` of a padded launch may wrap and hold any
value; they feed none of those cells.  ``big`` stays strictly above every
cell a reader takes, so a masked candidate row never wins there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "wavefront_kernel",
    "ltsp_dp_wavefront",
    "ltsp_dp_tables",
    "band_chunks",
    "band_copy_bytes",
    "writeback_bytes",
    "chunk_rows",
    "INT32_CAP",
    "ARGMIN_DTYPE",
]

#: default chunk height of the band (sublane rows per copy and scan step).
DEFAULT_CAND_TILE = 32

#: sublane tile height: dynamic row windows start at multiples of this.
_SUBLANES = 8
#: lane width: the skip-count axis is tiled as [S / 128, 128]
_LANES = 128

#: int32 sums saturate here, and masked candidate rows read it
_INT32_MAX = 2**31 - 1
#: the largest value an int32 table stores: two of them add without wrapping
INT32_CAP = _INT32_MAX // 2
#: the argmin plane's type: -1 .. R - 1 in 16 bits
ARGMIN_DTYPE = jnp.int16


def _add_sat(x, y, big):
    """``x + y`` clipped at ``big``: ``x + min(y, big - x)``, which never
    wraps for ``x`` in ``[0, big]`` and ``y >= 0``."""
    return x + jnp.minimum(y, big - x)


def chunk_rows(R: int, span: int | None, cand_tile: int) -> int:
    """Height ``H`` of the band's chunks: ``cand_tile`` rows, or under a
    LOGDP span the fewest multiple-of-8 rows that hold any band whole
    (``span + 8``: ``span + 1`` rows from a start aligned down by up to 7),
    so that every program copies and scans one chunk; at most ``R``."""
    if span is not None:
        cand_tile = -(-(span + _SUBLANES) // _SUBLANES) * _SUBLANES
    return min(cand_tile, R)


def _c_min(a, b, span: int | None, disjoint: bool, xp):
    """The first live detour start of cell ``(a, b)``: the candidates are
    ``c in [c_min, b]``, none where ``c_min > b``."""
    c_min = a + 1
    if span is not None:  # LOGDP restriction: b - c <= span
        c_min = xp.maximum(c_min, b - span)
    if disjoint:  # SIMPLEDP restriction: detours only at the root level
        c_min = xp.where(a > 0, b + 1, c_min)
    return c_min


def band_chunks(a, d, R: int, H: int, span: int | None, disjoint: bool, xp=jnp):
    """``(k_first, n_chunks)`` of the program at window start ``a`` on
    diagonal ``d``: its band is the rows ``k = c - 1`` of the live candidates
    ``c in [c_min, b]``, ``b = a + d``, and of the skip's row ``k = b - 1``,
    from ``k_first`` (aligned down to the sublane tile) in ``n_chunks``
    chunks of ``H`` rows, chunk ``j`` at ``min(k_first + j H, R - H)``.  A
    dead program (``b >= R``) has none.  ``xp`` is ``jnp`` in the kernel and
    ``numpy`` where the host counts the copies (:func:`band_copy_bytes`)."""
    b = a + d
    c_min = _c_min(a, b, span, disjoint, xp)
    k_first = ((xp.minimum(c_min, b) - 1) // _SUBLANES) * _SUBLANES
    n_chunks = xp.where(b < R, (b - k_first + H - 1) // H, 0)
    return k_first, n_chunks


@functools.lru_cache(maxsize=64)
def band_copy_bytes(
    B: int, R: int, S: int, itemsize: int, span: int | None, disjoint: bool,
    cand_tile: int,
) -> int:
    """HBM bytes the wavefront's band copies move in one launch of
    :func:`ltsp_dp_tables`: two ``[H, S]`` copies of ``T`` per chunk of
    every live program, one of its row and one of its column, over the
    ``R - 1`` diagonals and ``B`` instances.
    Cached per launch shape: a profiled solve records it after every launch,
    and at R = 256 counting takes about a millisecond of host time."""
    H = chunk_rows(R, span, cand_tile)
    d, a = np.meshgrid(np.arange(1, R), np.arange(R), indexing="ij")
    _, n_chunks = band_chunks(a, d, R, H, span, disjoint, xp=np)
    return int(n_chunks.sum()) * B * 2 * H * S * itemsize


def writeback_bytes(B: int, R: int, S: int) -> int:
    """HBM bytes the wavefront writes in place in one launch of
    :func:`ltsp_dp_tables`: one ``S``-word row of ``T`` (int32) and one of
    ``C`` (int16) per live program, ``B R (R - 1) / 2`` of them over the
    ``R - 1`` diagonals.  Dead programs write nothing, whatever the span or
    clip."""
    return B * (R * (R - 1) // 2) * S * (4 + 2)


def wavefront_kernel(
    # scalar-prefetch inputs (SMEM)
    d_ref,  # [1] int32 — current anti-diagonal
    u_ref,  # [B] int32 — U-turn penalty per instance
    left_ref,  # [B * R] int32
    right_ref,  # [B * R] int32
    x_ref,  # [B * R] int32
    nl_ref,  # [B * R] int32
    # tensor inputs, in HBM
    t_hbm,  # [B, R, R, L, 128] int32 — T
    c_hbm,  # [B, R, R, L, 128] int16 — C, never read: aliased to c_out
    # outputs: one (L, 128) block of each table, aliased to the inputs
    t_out,  # T[i, a, a + d]
    c_out,  # C[i, a, a + d]: -1 = skip, else the detour start c
    # scratch
    t_buf,  # [2, H, L, 128] — two slots of band rows of T[i, a]
    c_buf,  # [2, H, L, 128] — the column's rows T[i, c0 : c0 + H, b]
    skip_buf,  # [2 L, 128] — the skip's row, rolled by its lane shift
    sem,  # DMA semaphores [2 tables, 2 slots]
    slot_ref,  # [1] int32 SMEM — slot of this program's first chunk
    *,
    span: int | None,
    disjoint: bool,
):
    del c_hbm
    i = pl.program_id(0)
    a = pl.program_id(1)
    B, R, L = t_hbm.shape[0], t_hbm.shape[1], t_hbm.shape[3]
    S = L * _LANES
    H = t_buf.shape[1]
    unroll = math.gcd(H, _SUBLANES)  # rows folded per step of the row loop
    d = d_ref[0]
    dtype = jnp.int32
    # stored values are clipped to cap and sums to big, strictly above them,
    # so a masked row never beats a live one (module docstring)
    big = jnp.asarray(_INT32_MAX, dtype)
    cap = jnp.asarray(INT32_CAP, dtype)
    two = jnp.asarray(2, dtype)

    def band(win):
        return band_chunks(win, d, R, H, span, disjoint)

    def chunk_base(k_first, j):
        # the last chunk is clamped into the table: it may overlap the one
        # before, whose candidates the strict fold keeps
        return pl.multiple_of(jnp.minimum(k_first + j * H, R - H), _SUBLANES)

    def column_base(k0):
        # candidate c = k0 + 1 + h reads column row c; the copy is clamped
        # into the table (module docstring, *Table layout*)
        return jnp.minimum(k0 + 1, R - H)

    def copies(inst, win, k0, slot):
        """The two copies of program ``(inst, win)``'s chunk at row ``k0``:
        rows ``k0 ..`` of its row ``T[inst, win]`` and rows ``column_base(k0)
        ..`` of its column ``T[inst, :, win + d]``."""
        return (
            pltpu.make_async_copy(
                t_hbm.at[inst, win, pl.ds(k0, H)], t_buf.at[slot], sem.at[0, slot]
            ),
            pltpu.make_async_copy(
                t_hbm.at[inst, pl.ds(column_base(k0), H), win + d], c_buf.at[slot],
                sem.at[1, slot],
            ),
        )

    # The copies run one chunk ahead of the scan, across programs too: the
    # last chunk of a program starts the first chunk of the next live
    # program in grid order, (i, a + 1), or (i + 1, 0) after the diagonal's
    # last window.  Program (0, 0) is always live and starts its own.
    @pl.when((i == 0) & (a == 0))
    def _():
        slot_ref[0] = 0
        for cp in copies(0, 0, chunk_base(band(0)[0], 0), 0):
            cp.start()

    k_first, n_chunks = band(a)

    # a dead program (a + d >= R) has no chunk: it copies and computes
    # nothing and leaves its output blocks alone, which are the last live
    # program's (module docstring, *Writing the diagonal in place*)
    @pl.when(n_chunks > 0)
    def _():
        b = a + d
        base = i * R
        u = u_ref[i]
        nl_a = nl_ref[base + a]
        x_b = x_ref[base + b]
        r_b = right_ref[base + b]
        r_bm1 = right_ref[base + b - 1]
        l_b = left_ref[base + b]
        row_id = jax.lax.broadcasted_iota(dtype, (L, _LANES), 0)
        lane = jax.lax.broadcasted_iota(dtype, (L, _LANES), 1)
        svec = row_id * _LANES + lane  # the skip count s of each element

        # -------------- min over detour_c, banded to a < c <= b ------------
        # Live candidates: c in [c_min, b] (module docstring).  Band rows
        # outside them hold other cells' values; they compute harmless
        # integers that the floor below lifts to big.
        c_min = _c_min(a, b, span, disjoint, jnp)

        def fold(slot, k0, carry):
            """Fold candidates ``c = k0 + 1 + h``, ``h in [0, H)``, in
            ascending ``c`` into the running ``(min, smallest minimising
            c)``: each row is one ``(L, 128)`` slab, folded elementwise."""

            # row h of the chunk is row h + shift of the column's copy; the
            # one row past its end (c = R) is no candidate
            shift = k0 + 1 - column_base(k0)

            def row(h, carry):
                run_min, run_arg = carry
                c = k0 + 1 + h
                col = c_buf[slot, jnp.minimum(h + shift, H - 1)]
                r_cm1 = right_ref[base + c - 1]
                nl_c = nl_ref[base + jnp.minimum(c, R - 1)]
                # the linear terms 2 (r_b - r_{c-1}) (s + nl_a) + 2 U (s + nl_c)
                # as one affine function of s: a multiply and an add per
                # element
                slope = two * (r_b - r_cm1 + u)
                offset = two * ((r_b - r_cm1) * nl_a + u * nl_c)
                cand = _add_sat(t_buf[slot, h] + col,  # T[a, c-1] + T[c, b]
                                svec * slope + offset, big)
                live = (c >= c_min) & (c <= b)
                cand = jnp.maximum(cand, jnp.where(live, jnp.iinfo(dtype).min, big))
                # strict improvement in ascending c keeps the smallest
                # minimising c, as the exact DP's scan does
                better = cand < run_min
                return jnp.where(better, cand, run_min), jnp.where(better, c, run_arg)

            def rows(g, carry):
                g = jnp.asarray(g, jnp.int32)  # fori_loop index may be int64 (x64)
                for h in range(unroll):
                    carry = row(g * unroll + h, carry)
                return carry

            return jax.lax.fori_loop(0, H // unroll, rows, carry)

        s0 = slot_ref[0]
        last = b + 1 >= R
        next_i = jnp.where(last, i + 1, i)
        next_a = jnp.where(last, 0, a + 1)

        def body(j, carry):
            j = jnp.asarray(j, jnp.int32)  # fori_loop index may be int64 (x64)
            slot = (s0 + j) % 2
            k0 = chunk_base(k_first, j)

            @pl.when(j + 1 < n_chunks)
            def _():
                for cp in copies(i, a, chunk_base(k_first, j + 1), 1 - slot):
                    cp.start()

            @pl.when((j + 1 == n_chunks) & (next_i < B))
            def _():
                slot_ref[0] = 1 - slot
                k0_next = chunk_base(band(next_a)[0], 0)
                for cp in copies(next_i, next_a, k0_next, 1 - slot):
                    cp.start()

            for cp in copies(i, a, k0, slot):
                cp.wait()
            return fold(slot, k0, carry)

        det, argc = jax.lax.fori_loop(
            0,
            n_chunks,
            body,
            (jnp.full((L, _LANES), big, dtype), jnp.zeros((L, _LANES), jnp.int32)),
        )

        # -------------- skip(a, b, s) --------------------------------------
        # T[a, b-1, min(s + x_b, S-1)] from the band's last row, still in the
        # last chunk's slot.  Element (p, l) holds s = 128 p + l and reads
        # s + x_b = 128 (p + q) + l + r: lane l + r of tile row p + q, or
        # lane l + r - 128 of row p + q + 1 where l + r passes the lanes.
        # A lane roll by r puts both lanes in place; the rolled row, parked
        # in skip_buf, is read back from rows q and q + 1 on.  Row[S-1] goes
        # wherever s + x_b runs past the end (the clamp), which covers every
        # element the roll wrapped around and every element read from the
        # unwritten rows L .. 2L - 1 of skip_buf.
        j_last = n_chunks - 1
        k_last = chunk_base(k_first, j_last)
        row_bm1 = t_buf[(s0 + j_last) % 2, b - 1 - k_last]  # [L, 128]
        q = jnp.minimum(x_b // _LANES, L - 1)
        r = x_b % _LANES
        skip_buf[pl.ds(0, L)] = pltpu.roll(row_bm1, (_LANES - r) % _LANES, 1)
        lanes = jnp.where(
            lane + r < _LANES, skip_buf[pl.ds(q, L)], skip_buf[pl.ds(q + 1, L)]
        )
        row_end = jnp.max(
            jnp.max(jnp.where(svec == S - 1, row_bm1, -big), axis=1, keepdims=True),
            axis=0, keepdims=True,
        )
        shifted = jnp.where(svec + x_b <= S - 1, lanes, row_end)
        skip = _add_sat(
            _add_sat(shifted, two * (r_b - r_bm1) * (svec + nl_a), big),
            two * (l_b - r_bm1) * x_b,
            big,
        )
        val = jnp.minimum(jnp.minimum(skip, det), cap)
        t_out[0, 0, 0] = val
        arg = jnp.where(skip <= det, jnp.int32(-1), argc)
        c_out[0, 0, 0] = arg.astype(c_out.dtype)


#: scoped-VMEM limit Mosaic applies on v5e when a kernel asks for none.
_DEFAULT_SCOPED_VMEM = 16 << 20


def _vmem_limit_bytes(S: int, itemsize: int, H: int) -> int | None:
    """Scoped-VMEM request for one program, or ``None`` for the compiler's
    default where that suffices.

    A program holds two slots of ``[H, S]`` band rows of its row and of its
    column (``4 H S itemsize``) and its two ``S``-word output blocks, each
    double-buffered; its fold keeps a few ``S``-word rows live at a time.
    The rows are ``(S/128, 128)`` tiles, padded to 8 sublanes below
    ``S = 1024``.
    """
    row = max(S, _SUBLANES * _LANES) * itemsize
    need = 4 * H * row + 2 * 2 * row + 16 * row
    if need <= _DEFAULT_SCOPED_VMEM:
        return None
    return -(-need // (1 << 20)) << 20


def ltsp_dp_wavefront(
    T: jax.Array,  # [B, R, R, L, 128] int32
    C: jax.Array,  # [B, R, R, L, 128] int16
    left: jax.Array,  # [B, R]
    right: jax.Array,  # [B, R]
    x: jax.Array,  # [B, R] int32
    nl: jax.Array,  # [B, R]
    u: jax.Array,  # [B]
    d: jax.Array,  # scalar int32 (traced — same kernel serves every diagonal)
    *,
    span: int | None,
    disjoint: bool,
    interpret: bool,
    cand_tile: int,
) -> tuple[jax.Array, jax.Array]:
    """One anti-diagonal for every instance, written in place: ``(T, C)``
    with diagonal ``d`` filled.

    Both tables stay in HBM; each program copies only its band's rows of
    its row ``T[i, a]`` and its column ``T[i, :, a + d]`` and writes its
    cell's row into each table through an output block aliased to it
    (module docstring).
    """
    B, R = left.shape
    L = T.shape[3]
    H = chunk_rows(R, span, cand_tile)
    # chunk bases (the last one clamped to R - H) must be sublane-aligned,
    # which the kernel asserts to Mosaic; a single chunk of R rows is at 0
    if not interpret and H < R and (H % _SUBLANES or R % _SUBLANES):
        raise ValueError(
            f"compiled banded wavefront needs cand_tile and R multiples of "
            f"{_SUBLANES}, got cand_tile={cand_tile}, R={R}"
        )
    kern = functools.partial(wavefront_kernel, span=span, disjoint=disjoint)

    def window(a, d_ref):
        # a dead program (a + d >= R) revisits the last live program's block
        return jnp.minimum(a, R - 1 - d_ref[0])

    def cell(i, a, d_ref, *_):  # T[i, a, a + d] and C[i, a, a + d]
        w = window(a, d_ref)
        return i, w, w + d_ref[0], 0, 0

    row_block = (1, 1, 1, L, _LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,  # d, u, left, right, x, nl
        grid=(B, R),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,  # T, C
        out_specs=[pl.BlockSpec(row_block, cell)] * 2,
        scratch_shapes=[
            pltpu.VMEM((2, H, L, _LANES), T.dtype),
            pltpu.VMEM((2, H, L, _LANES), T.dtype),
            pltpu.VMEM((2 * L, _LANES), T.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kwargs = {}
    if not interpret:
        # dimension_semantics audit (see module docstring): the copies run
        # ahead across programs in grid order, so neither dim is split
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit_bytes(L * _LANES, T.dtype.itemsize, H),
        )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (T, C)],
        # operands 6 and 7 (after the six scalars) are T and C
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
        name="ltsp_wavefront",
        **kwargs,
    )(
        jnp.asarray(d, jnp.int32).reshape(1),
        u,
        left.reshape(-1),
        right.reshape(-1),
        x.reshape(-1),
        nl.reshape(-1),
        T,
        C,
    )


@functools.partial(
    jax.jit, static_argnames=("S", "span", "disjoint", "interpret", "cand_tile")
)
def ltsp_dp_tables(
    left: jax.Array,  # [B, R]
    right: jax.Array,  # [B, R]
    x: jax.Array,  # [B, R] int32
    nl: jax.Array,  # [B, R]
    u: jax.Array,  # [B]
    *,
    S: int,
    interpret: bool,
    span: int | None = None,
    disjoint: bool = False,
    cand_tile: int = DEFAULT_CAND_TILE,
) -> tuple[jax.Array, jax.Array]:
    """Full batched DP tables ``(T, C)`` in a single jitted wavefront.

    Both are ``[B, R, R, S/128, 128]``, ``T`` int32 and ``C`` int16: cell
    ``(a, b)`` of instance ``i`` is the ``(S/128, 128)`` tile ``T[i, a, b]``,
    whose element ``(p, l)`` is skip count ``s = 128 p + l``.  ``T`` is the
    DP value table and ``C`` the argmin plane (-1 = skip, else detour start
    ``c``) that the traceback consumes; ``ops.host_table`` views either as
    ``[B, R, R, S]`` on the host.  Cells below the diagonal hold 0 in ``T``
    and -1 in ``C``.  One ``lax.fori_loop`` over the diagonal index carries
    ``(T, C)``; each iteration is one Pallas launch over the ``(instance,
    window-start)`` grid that writes its diagonal into both in place.
    ``interpret`` has no default: every caller says whether it runs the
    compiled kernel or the interpreter.  The inputs are int32
    (``ops.prepare_batch``) and ``S`` a multiple of 128; any other dtype
    raises ``TypeError`` and any other ``S`` ``ValueError`` when the call is
    traced.
    """
    dtype = left.dtype
    if dtype != jnp.int32:
        raise TypeError(f"the wavefront computes in int32 only, got {dtype}")
    if S % _LANES:
        raise ValueError(f"S must be a multiple of {_LANES}, got {S}")
    B, R = left.shape
    L = S // _LANES
    shape = (B, R, R, L, _LANES)
    # base diagonal T[b, b, s] = 2 s(b) (s + n_l(b)), batched, clipped like
    # every stored value
    svec = jnp.arange(S, dtype=dtype).reshape(L, _LANES)
    base = 2 * (right - left)[:, :, None, None] * (svec + nl[:, :, None, None])
    base = jnp.minimum(base, jnp.asarray(INT32_CAP, dtype))[:, :, None]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    T = jnp.where(row == col, base, 0)
    C = jnp.full(shape, -1, ARGMIN_DTYPE)
    if R == 1:
        return T, C

    def body(d, carry):
        return ltsp_dp_wavefront(
            *carry, left, right, x, nl, u, d,
            span=span, disjoint=disjoint, interpret=interpret, cand_tile=cand_tile,
        )

    return jax.lax.fori_loop(1, R, body, (T, C))
