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
table workspace; XLA donates the carry so each diagonal is an in-place
scatter, and the kernel receives ``d`` as a scalar-prefetch operand, so the
same compiled kernel serves every diagonal.  The same kernel runs compiled
(Mosaic, ``interpret=False``) and through the Pallas interpreter
(``interpret=True``); every construct below is one Mosaic lowers.

Table layout
------------
A program computing cell ``(a, b)`` reads row ``a`` of the value table,
``T[a, :, s]``, and column ``b``, ``T[:, b, s]``.  A column of ``[R, R, S]`` is
a strided ``(R, 1, S)`` slab, which no legal TPU block (last two block dims
multiples of ``(8, 128)`` or equal to the array's) can name.  The wavefront
therefore carries a second, transposed copy ``Tc[b, k, s] = T[k + 1, b, s]``
in which column ``b`` is the contiguous row ``Tc[b]``.  The one-row shift
aligns the two terms of candidate ``c``: ``T[a, c - 1, s]`` and
``T[c, b, s]`` both sit at index ``k = c - 1`` of their row, so one
8-aligned sublane window of ``k`` serves both.

Both tables stay in HBM (``memory_space=pl.ANY``).  A program copies only
the rows of its band (below) from ``T[i, a]`` and ``Tc[i, b]`` into two
VMEM slots of ``[H, S]`` each, with ``pltpu.make_async_copy``: while chunk
``j`` is scanned, chunk ``j + 1`` is in flight, and a program's last chunk
starts the first chunk of the next live program in grid order, so no
program waits for its first copy but the grid's first.  The skip's row
``T[a, b - 1]`` is the band's last row and needs no copy of its own.

Per-file scalars (``left/right/x/nl`` flattened to ``[B * R]``, and ``u``)
ride as scalar-prefetch operands in SMEM, read by ``program_id``.  The two
per-candidate vectors ``right[c - 1]`` and ``nl[c]`` come as ``[B, R, 1]``
VMEM columns.  Outputs are ``[B, R, 1, S]`` (one legal ``(1, S)`` block per
program), reshaped to ``[B, R, S]`` outside the kernel.

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
band whole): a ``fori_loop`` over the chunks, each masked to the live
candidates and folded into a running ``(min, argmin)``.  The argmin of a
chunk is ``min`` over ``where(cand == min, c, INT_MAX)`` — the smallest
minimising ``c``.  Chunks ascend in ``c`` and the fold improves strictly, so
the result is the *smallest* minimising ``c`` — identical tie-breaking to
the exact Python DP (skip wins ties against detours; among detours the
smallest ``c`` wins).  The last chunk base is clamped to ``R - H``; the
overlap re-evaluates candidates the strict fold ignores.  A program with
``a + d >= R`` lies past the diagonal's end: its band is empty, it copies
and computes nothing, and the scatter of :func:`ltsp_dp_tables` drops its
output blocks.  :func:`band_copy_bytes` counts the bytes the copies move.

``dimension_semantics`` audit of the ``(B, R)`` grid: the batch dimension
indexes independent instances and the window-start dimension indexes cells of
*one* anti-diagonal, which only read diagonals ``< d`` (frozen in this launch)
and write disjoint output blocks — no program on the grid observes another's
write.  The copies run ahead across programs in grid order, though, so both
dimensions are declared ``"arbitrary"``: a chip that split the grid over
cores would leave a core's first program waiting on a copy nobody started.
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
* The tables are ``int32``, exact under the guard of :mod:`.ops` (see
  *Saturating sums* below); :func:`ltsp_dp_tables` refuses any other dtype.
* The ``skip`` term needs the shifted gather ``row[min(s + x_b, S - 1)]``;
  ``x_b`` is a scalar per program, so it is a lane rotation by ``-x_b`` plus
  a mask that substitutes ``row[S - 1]`` where ``s + x_b`` passes the end.

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
    "chunk_rows",
    "INT32_CAP",
]

#: default chunk height of the band (sublane rows per copy and scan step).
DEFAULT_CAND_TILE = 32

#: sublane tile height: dynamic row windows start at multiples of this.
_SUBLANES = 8

#: int32 sums saturate here, and masked candidate rows read it
_INT32_MAX = 2**31 - 1
#: the largest value an int32 table stores: two of them add without wrapping
INT32_CAP = _INT32_MAX // 2


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
    """HBM bytes the wavefront's band copies of ``T`` and ``Tc`` move in one
    launch of :func:`ltsp_dp_tables`: two ``[H, S]`` copies per chunk of
    every live program, over the ``R - 1`` diagonals and ``B`` instances.
    Cached per launch shape: a profiled solve records it after every launch,
    and at R = 256 counting takes about a millisecond of host time."""
    H = chunk_rows(R, span, cand_tile)
    d, a = np.meshgrid(np.arange(1, R), np.arange(R), indexing="ij")
    _, n_chunks = band_chunks(a, d, R, H, span, disjoint, xp=np)
    return int(n_chunks.sum()) * B * 2 * H * S * itemsize


def wavefront_kernel(
    # scalar-prefetch inputs (SMEM)
    d_ref,  # [1] int32 — current anti-diagonal
    u_ref,  # [B] int32 — U-turn penalty per instance
    left_ref,  # [B * R] int32
    right_ref,  # [B * R] int32
    x_ref,  # [B * R] int32
    nl_ref,  # [B * R] int32
    # tensor inputs
    t_hbm,  # [B, R, R, S] in HBM — T
    tc_hbm,  # [B, R, R, S] in HBM — Tc; Tc[i, b, k] = T[i, k + 1, b]
    rk_ref,  # [1, R, 1] int32 — right[i, k]   (= r_{c-1} at k = c - 1)
    nk_ref,  # [1, R, 1] int32 — nl[i, k + 1]  (= nl_c   at k = c - 1)
    # outputs
    val_ref,  # [1, 1, 1, S] — new T[a, a+d, :]
    cho_ref,  # [1, 1, 1, S] int32 — argmin plane (-1 = skip, else c)
    # scratch
    t_buf,  # [2, H, S] — two slots of band rows of T[i, a]
    c_buf,  # [2, H, S] — the same rows of Tc[i, b]
    sem,  # DMA semaphores [2 tables, 2 slots]
    slot_ref,  # [1] int32 SMEM — slot of this program's first chunk
    *,
    S: int,
    span: int | None,
    disjoint: bool,
):
    i = pl.program_id(0)
    a = pl.program_id(1)
    B, R = t_hbm.shape[0], t_hbm.shape[1]
    H = t_buf.shape[1]
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

    def copies(inst, win, k0, slot):
        """The two copies of program ``(inst, win)``'s chunk at row ``k0``."""
        return (
            pltpu.make_async_copy(
                t_hbm.at[inst, win, pl.ds(k0, H)], t_buf.at[slot], sem.at[0, slot]
            ),
            pltpu.make_async_copy(
                tc_hbm.at[inst, win + d, pl.ds(k0, H)], c_buf.at[slot],
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
    # nothing, and the host-side scatter drops its output blocks
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
        svec = jax.lax.broadcasted_iota(dtype, (1, S), 1)

        # -------------- min over detour_c, banded to a < c <= b ------------
        # Live candidates: c in [c_min, b] (module docstring).  Band rows
        # outside them hold other cells' values, so they compute harmless
        # integers before the mask replaces them.
        c_min = _c_min(a, b, span, disjoint, jnp)

        def chunk(slot, k0):
            """Fold candidates ``c = k0 + 1 + j``, ``j in [0, H)``, masked to
            the live band: ``(min, smallest minimising c)``, each ``[1, S]``."""
            t_left = t_buf[slot]  # T[a, c-1, s]
            t_right = c_buf[slot]  # T[c, b, s]
            r_cm1 = rk_ref[0, pl.ds(k0, H), :]  # [H, 1]
            nl_c = nk_ref[0, pl.ds(k0, H), :]  # [H, 1]
            # the linear terms 2 (r_b - r_{c-1}) (s + nl_a) + 2 U (s + nl_c)
            # as one affine function of s per row: a multiply and an add per
            # element
            slope = two * (r_b - r_cm1 + u)
            offset = two * ((r_b - r_cm1) * nl_a + u * nl_c)
            cand = _add_sat(t_left + t_right, svec * slope + offset, big)
            cvec = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) + (k0 + 1)
            cand = jnp.where((cvec >= c_min) & (cvec <= b), cand, big)
            cmin = jnp.min(cand, axis=0, keepdims=True)
            # first minimiser == smallest c, matching the exact DP's
            # ascending-c strict-improvement scan
            carg = jnp.min(
                jnp.where(cand == cmin, cvec, jnp.iinfo(jnp.int32).max),
                axis=0,
                keepdims=True,
            )
            return cmin, carg

        s0 = slot_ref[0]
        last = b + 1 >= R
        next_i = jnp.where(last, i + 1, i)
        next_a = jnp.where(last, 0, a + 1)

        def body(j, carry):
            run_min, run_arg = carry
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
            cmin, carg = chunk(slot, k0)
            improve = cmin < run_min
            return jnp.minimum(run_min, cmin), jnp.where(improve, carg, run_arg)

        det, argc = jax.lax.fori_loop(
            0,
            n_chunks,
            body,
            (jnp.full((1, S), big, dtype), jnp.zeros((1, S), jnp.int32)),
        )

        # -------------- skip(a, b, s) --------------------------------------
        # T[a, b-1, min(s + x_b, S-1)] from the band's last row, still in the
        # last chunk's slot: rotate it left by x_b lanes, then put row[S-1]
        # wherever s + x_b ran past the end (the clamp).
        j_last = n_chunks - 1
        k_last = chunk_base(k_first, j_last)
        row_bm1 = t_buf[(s0 + j_last) % 2, pl.ds(b - 1 - k_last, 1), :]  # [1, S]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        rolled = pltpu.roll(row_bm1, (S - x_b) % S, 1)
        row_end = jnp.max(
            jnp.where(lane == S - 1, row_bm1, -big), axis=1, keepdims=True
        )
        shifted = jnp.where(lane + x_b <= S - 1, rolled, row_end)
        skip = _add_sat(
            _add_sat(shifted, two * (r_b - r_bm1) * (svec + nl_a), big),
            two * (l_b - r_bm1) * x_b,
            big,
        )
        val_ref[0, 0] = jnp.minimum(jnp.minimum(skip, det), cap)
        cho_ref[0, 0] = jnp.where(skip <= det, jnp.int32(-1), argc)


#: scoped-VMEM limit Mosaic applies on v5e when a kernel asks for none.
_DEFAULT_SCOPED_VMEM = 16 << 20


def _vmem_limit_bytes(R: int, S: int, itemsize: int, H: int) -> int | None:
    """Scoped-VMEM request for one program, or ``None`` for the compiler's
    default where that suffices.

    A program holds two slots of ``[H, S]`` band rows of each table
    (``4 H S itemsize``), about four ``[H, S]`` int32 candidate temporaries,
    its two ``(1, S)`` output blocks and its two ``[R, 1]`` candidate
    columns (lane-padded to 128), each double-buffered.
    """
    need = 4 * H * S * itemsize + 4 * H * S * 4 + 2 * 2 * S * 4 + 2 * 2 * R * 128 * 4
    if need <= _DEFAULT_SCOPED_VMEM:
        return None
    return -(-need // (1 << 20)) << 20


def ltsp_dp_wavefront(
    T: jax.Array,  # [B, R, R, S]
    Tc: jax.Array,  # [B, R, R, S] — Tc[i, b, k] = T[i, k + 1, b]
    left: jax.Array,  # [B, R]
    right: jax.Array,  # [B, R]
    x: jax.Array,  # [B, R] int32
    nl: jax.Array,  # [B, R]
    u: jax.Array,  # [B]
    d: jax.Array,  # scalar int32 (traced — same kernel serves every diagonal)
    *,
    S: int,
    span: int | None,
    disjoint: bool,
    interpret: bool,
    cand_tile: int,
) -> tuple[jax.Array, jax.Array]:
    """One anti-diagonal for every instance: ``([B, R, S], [B, R, S])``.

    ``T`` and ``Tc`` stay in HBM; each program copies only its band's rows
    of ``T[i, a]`` and ``Tc[i, a + d]`` (module docstring).
    """
    B, R = left.shape
    H = chunk_rows(R, span, cand_tile)
    # chunk bases (the last one clamped to R - H) must be sublane-aligned,
    # which the kernel asserts to Mosaic; a single chunk of R rows is at 0
    if not interpret and H < R and (H % _SUBLANES or R % _SUBLANES):
        raise ValueError(
            f"compiled banded wavefront needs cand_tile and R multiples of "
            f"{_SUBLANES}, got cand_tile={cand_tile}, R={R}"
        )
    kern = functools.partial(wavefront_kernel, S=S, span=span, disjoint=disjoint)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,  # d, u, left, right, x, nl
        grid=(B, R),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # T, copied by band
            pl.BlockSpec(memory_space=pl.ANY),  # Tc, copied by band
            pl.BlockSpec((1, R, 1), lambda i, a, *_: (i, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda i, a, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, S), lambda i, a, *_: (i, a, 0, 0)),
            pl.BlockSpec((1, 1, 1, S), lambda i, a, *_: (i, a, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, H, S), T.dtype),
            pltpu.VMEM((2, H, S), T.dtype),
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
            vmem_limit_bytes=_vmem_limit_bytes(R, S, T.dtype.itemsize, H),
        )
    nk = jnp.concatenate([nl[:, 1:], jnp.zeros_like(nl[:, :1])], axis=1)
    vals, chos = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, R, 1, S), T.dtype),
            jax.ShapeDtypeStruct((B, R, 1, S), jnp.int32),
        ],
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
        Tc,
        right[:, :, None],
        nk[:, :, None],
    )
    return vals.reshape(B, R, S), chos.reshape(B, R, S)


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

    ``T[i, a, b, s]`` is the DP value table of instance ``i`` and
    ``C[i, a, b, s]`` the argmin plane (-1 = skip, else detour start ``c``)
    that the host traceback consumes.  One ``lax.fori_loop`` over the diagonal
    index carries the ``(T, Tc, C)`` workspace (``Tc`` is the transposed,
    one-row-shifted copy the column reads use, see the module docstring);
    each iteration is one Pallas launch over the ``(instance, window-start)``
    grid plus in-place diagonal scatters (``mode="drop"`` discards the
    output blocks of the windows past the diagonal's end).  ``interpret`` has no default:
    every caller says whether it runs the compiled kernel or the interpreter.
    The inputs are int32 (``ops.prepare_batch``); any other dtype raises
    ``TypeError`` when the call is traced.
    """
    dtype = left.dtype
    if dtype != jnp.int32:
        raise TypeError(f"the wavefront computes in int32 only, got {dtype}")
    B, R = left.shape
    rr = jnp.arange(R)
    # base diagonal T[b, b, s] = 2 s(b) (s + n_l(b)), batched, clipped like
    # every stored value
    svec = jnp.arange(S, dtype=dtype)
    base = 2 * (right - left)[:, :, None] * (svec[None, None, :] + nl[:, :, None])
    base = jnp.minimum(base, jnp.asarray(INT32_CAP, dtype))
    T = jnp.zeros((B, R, R, S), dtype)
    T = T.at[:, rr, rr, :].set(base)
    # Tc[i, b, a - 1] = T[i, a, b]; a = 0 never appears as a column term
    Tc = jnp.zeros((B, R, R, S), dtype)
    Tc = Tc.at[:, rr[1:], rr[1:] - 1, :].set(base[:, 1:])
    C = jnp.full((B, R, R, S), -1, jnp.int32)
    if R == 1:
        return T, C

    def body(d, carry):
        T, Tc, C = carry
        vals, chos = ltsp_dp_wavefront(
            T, Tc, left, right, x, nl, u, d,
            S=S, span=span, disjoint=disjoint, interpret=interpret,
            cand_tile=cand_tile,
        )
        with jax.named_scope("ltsp_scatter"):
            T = T.at[:, rr, rr + d, :].set(vals, mode="drop")
            Tc = Tc.at[:, rr[1:] + d, rr[1:] - 1, :].set(vals[:, 1:], mode="drop")
            C = C.at[:, rr, rr + d, :].set(chos, mode="drop")
        return T, Tc, C

    T, _, C = jax.lax.fori_loop(1, R, body, (T, Tc, C))
    return T, C
