"""Pallas TPU kernel for the LTSP DP wavefront — single-trace, batched,
traceback-capable, with a banded candidate scan and per-program DMA slices.

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
collapses the table to SIMPLEDP's 2-D recursion exactly).  The kernel walks
the live band in ``cand_tile``-row chunks: a ``fori_loop`` over chunk bases
aligned down to a multiple of 8 (the sublane tile), each chunk masked to the
live band and folded into a running ``(min, argmin)``.  The argmin of a chunk
is ``min`` over ``where(cand == min, c, INT_MAX)`` — the smallest minimising
``c``.  Chunks ascend in ``c`` and the fold improves strictly, so the result
is the *smallest* minimising ``c`` — identical tie-breaking to the exact
Python DP (skip wins ties against detours; among detours the smallest ``c``
wins).  The last chunk base is clamped to ``R - cand_tile``; the overlap
re-evaluates candidates the strict fold ignores.  When
``R - 1 <= cand_tile`` the kernel statically takes one masked tile over
every ``c`` (same arithmetic, no loop).

``dimension_semantics`` audit of the ``(B, R)`` grid: the batch dimension
indexes independent instances and the window-start dimension indexes cells of
*one* anti-diagonal, which only read diagonals ``< d`` (frozen in this launch)
and write disjoint output blocks — no program on the grid observes another's
write, so both dimensions are declared ``"parallel"``.  Compiled mode only;
the interpreter ignores scheduling hints.

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
* ``S`` must be a multiple of 128 (lane width).  When the banded scan runs
  compiled, ``cand_tile`` and ``R`` must be multiples of 8 (sublane tile);
  the power-of-two buckets of :mod:`.ops` are.
* ``cand_tile`` is the candidate-chunk height (sublane axis); 128 by default
  so instances up to R = 129 take the single-tile path, while large
  instances stream the band in 128-row tiles.
* ``dtype`` is ``float32`` (exact for values < 2**24, the oracle-comparison
  path), ``int32`` (the solver path, exact under the guard of :mod:`.ops`,
  see *Saturating sums* below), or ``float64`` (exact for values < 2**53 —
  the interpret-only numeric fallback in :mod:`.ops` for instances whose
  coprime byte-scale coordinates fail the int32 guard even after gcd/shift
  rescaling).
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
cell a reader takes, so a masked candidate row never wins there.  Floats do
not wrap: they add plainly and clip at infinity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wavefront_kernel", "ltsp_dp_wavefront", "ltsp_dp_tables", "INT32_CAP"]

#: default candidate-chunk height (sublane rows per banded-scan step).
DEFAULT_CAND_TILE = 128

#: sublane tile height: dynamic row windows start at multiples of this.
_SUBLANES = 8

#: int32 sums saturate here, and masked candidate rows read it
_INT32_MAX = 2**31 - 1
#: the largest value an int32 table stores: two of them add without wrapping
INT32_CAP = _INT32_MAX // 2


def _limits(dtype):
    """``(big, cap)``: where sums clip and where stored values clip."""
    if dtype == jnp.int32:
        return jnp.asarray(_INT32_MAX, dtype), jnp.asarray(INT32_CAP, dtype)
    inf = jnp.asarray(jnp.inf, dtype)
    return inf, inf


def _add_sat(x, y, big):
    """``x + y`` clipped at ``big``: ``x + min(y, big - x)`` in integers,
    which never wraps for ``x`` in ``[0, big]`` and ``y >= 0``; plain
    addition in floats."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x + jnp.minimum(y, big - x)
    return x + y


def wavefront_kernel(
    # scalar-prefetch inputs (SMEM)
    d_ref,  # [1] int32 — current anti-diagonal
    u_ref,  # [B] dtype — U-turn penalty per instance
    left_ref,  # [B * R] dtype
    right_ref,  # [B * R] dtype
    x_ref,  # [B * R] int32
    nl_ref,  # [B * R] dtype
    # tensor inputs (VMEM blocks)
    row_ref,  # [1, 1, R, S] — T[i, a, :, :]
    col_ref,  # [1, 1, R, S] — Tc[i, b, :, :]; Tc[i, b, k] = T[i, k + 1, b]
    rk_ref,  # [1, R, 1] dtype — right[i, k]   (= r_{c-1} at k = c - 1)
    nk_ref,  # [1, R, 1] dtype — nl[i, k + 1]  (= nl_c   at k = c - 1)
    # outputs
    val_ref,  # [1, 1, 1, S] — new T[a, a+d, :]
    cho_ref,  # [1, 1, 1, S] int32 — argmin plane (-1 = skip, else c)
    *,
    S: int,
    span: int | None,
    disjoint: bool,
    cand_tile: int,
):
    i = pl.program_id(0)
    a = pl.program_id(1)
    R = row_ref.shape[2]
    d = d_ref[0]
    # programs with a + d >= R are out of this diagonal: compute at a clamped
    # b (cheap, garbage) and let the host-side scatter drop the result.
    b = jnp.minimum(a + d, R - 1)
    dtype = row_ref.dtype
    # stored values are clipped to cap and sums to big, strictly above them,
    # so a masked row never beats a live one (module docstring)
    big, cap = _limits(dtype)
    two = jnp.asarray(2, dtype)
    base = i * R

    u = u_ref[i]
    nl_a = nl_ref[base + a]
    x_b = x_ref[base + b]
    r_b = right_ref[base + b]
    r_bm1 = right_ref[base + b - 1]
    l_b = left_ref[base + b]

    # ---------------- skip(a, b, s) ----------------------------------------
    # T[a, b-1, min(s + x_b, S-1)]: rotate the row left by x_b lanes, then
    # put row[S-1] wherever s + x_b ran past the end (the clamp).
    row_bm1 = row_ref[0, 0, pl.ds(b - 1, 1), :]  # [1, S]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    rolled = pltpu.roll(row_bm1, (S - x_b) % S, 1)
    last = jnp.max(jnp.where(lane == S - 1, row_bm1, -big), axis=1, keepdims=True)
    shifted = jnp.where(lane + x_b <= S - 1, rolled, last)
    svec = jax.lax.broadcasted_iota(dtype, (1, S), 1)
    skip = _add_sat(
        _add_sat(shifted, two * (r_b - r_bm1) * (svec + nl_a), big),
        two * (l_b - r_bm1) * x_b.astype(dtype),
        big,
    )

    # ---------------- min over detour_c, banded to a < c <= b --------------
    # Live candidates: c in (a, b], further clipped to c >= b - span under a
    # LOGDP restriction, and to the empty band on non-root cells under the
    # SIMPLEDP restriction (disjoint detours = no detour may start inside
    # another, i.e. cells with a > 0 may only skip; the 3-D table then
    # collapses to SIMPLEDP's 2-D recursion exactly, traceback included).
    # Table rows outside the wavefront are zeros (or stale values of other
    # cells), so the masked rows compute harmless integers before the mask;
    # rows with c - 1 > b may go negative there, and the mask replaces them.
    c_min = a + 1
    if span is not None:  # LOGDP restriction: b - c <= span
        c_min = jnp.maximum(c_min, b - span)
    if disjoint:  # SIMPLEDP restriction: detours only at the root level
        c_min = jnp.where(a > 0, b + 1, c_min)

    def chunk(k0, n_rows: int):
        """Fold candidates ``c = k0 + 1 + j``, ``j in [0, n_rows)``, masked to
        the live band: ``(min, smallest minimising c)``, each ``[1, S]``."""
        t_left = row_ref[0, 0, pl.ds(k0, n_rows), :]  # T[a, c-1, s]
        t_right = col_ref[0, 0, pl.ds(k0, n_rows), :]  # T[c, b, s]
        r_cm1 = rk_ref[0, pl.ds(k0, n_rows), :]  # [n_rows, 1]
        nl_c = nk_ref[0, pl.ds(k0, n_rows), :]  # [n_rows, 1]
        # the linear terms 2 (r_b - r_{c-1}) (s + nl_a) + 2 U (s + nl_c) as
        # one affine function of s per row: a multiply and an add per element
        slope = two * (r_b - r_cm1 + u)
        offset = two * ((r_b - r_cm1) * nl_a + u * nl_c)
        cand = _add_sat(t_left + t_right, svec * slope + offset, big)
        cvec = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0) + (k0 + 1)
        cand = jnp.where((cvec >= c_min) & (cvec <= b), cand, big)
        cmin = jnp.min(cand, axis=0, keepdims=True)
        # first minimiser == smallest c, matching the exact DP's ascending-c
        # strict-improvement scan
        carg = jnp.min(
            jnp.where(cand == cmin, cvec, jnp.iinfo(jnp.int32).max),
            axis=0,
            keepdims=True,
        )
        return cmin, carg

    if R - 1 <= cand_tile:
        # static path: one tile over k = c - 1 in [0, R); c = R never lives.
        det, argc = chunk(0, R)
    else:
        # banded scan over cand_tile-row chunks from the live band's first
        # row, aligned down to the sublane tile.
        k_first = ((c_min - 1) // _SUBLANES) * _SUBLANES
        n_chunks = jnp.where(
            c_min <= b, (b - k_first + cand_tile - 1) // cand_tile, 0
        )

        def body(j, carry):
            run_min, run_arg = carry
            j = jnp.asarray(j, jnp.int32)  # fori_loop index may be int64 (x64)
            k0 = jnp.minimum(k_first + j * cand_tile, R - cand_tile)
            cmin, carg = chunk(pl.multiple_of(k0, _SUBLANES), cand_tile)
            improve = cmin < run_min
            return jnp.minimum(run_min, cmin), jnp.where(improve, carg, run_arg)

        det, argc = jax.lax.fori_loop(
            0,
            n_chunks,
            body,
            (jnp.full((1, S), big, dtype), jnp.zeros((1, S), jnp.int32)),
        )

    val_ref[0, 0] = jnp.minimum(jnp.minimum(skip, det), cap)
    cho_ref[0, 0] = jnp.where(skip <= det, jnp.int32(-1), argc)


#: scoped-VMEM limit Mosaic applies on v5e when a kernel asks for none.
_DEFAULT_SCOPED_VMEM = 16 << 20


def _vmem_limit_bytes(R: int, S: int, itemsize: int, cand_tile: int) -> int | None:
    """Scoped-VMEM request for one program, or ``None`` for the compiler's
    default where that suffices.

    A program holds its row and column blocks, double-buffered
    (``4 R S itemsize``), plus about four ``[cand_tile, S]`` int32 candidate
    temporaries.  At ``(R, S) = (256, 4096)`` that is 24 MiB; compiling for
    v5e refuses 20 MiB and accepts 22 MiB.
    """
    need = 4 * R * S * itemsize + 4 * min(R, cand_tile) * S * 4
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

    ``d`` rides as a scalar-prefetch operand so the column BlockSpec can DMA
    exactly the ``Tc[i, a + d]`` row each program reads; neither table is
    ever mapped whole into VMEM.
    """
    B, R = left.shape
    # the banded scan's chunk bases (last one clamped to R - cand_tile) must
    # be sublane-aligned, which the kernel asserts to Mosaic
    banded = R - 1 > cand_tile
    if not interpret and banded and (cand_tile % _SUBLANES or R % _SUBLANES):
        raise ValueError(
            f"compiled banded wavefront needs cand_tile and R multiples of "
            f"{_SUBLANES}, got cand_tile={cand_tile}, R={R}"
        )
    kern = functools.partial(
        wavefront_kernel, S=S, span=span, disjoint=disjoint, cand_tile=cand_tile
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,  # d, u, left, right, x, nl
        grid=(B, R),
        in_specs=[
            # row a of T
            pl.BlockSpec((1, 1, R, S), lambda i, a, d, *_: (i, a, 0, 0)),
            # row b = min(a + d, R - 1) of Tc, i.e. column b of T
            pl.BlockSpec(
                (1, 1, R, S),
                lambda i, a, d, *_: (i, jnp.minimum(a + d[0], R - 1), 0, 0),
            ),
            pl.BlockSpec((1, R, 1), lambda i, a, *_: (i, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda i, a, *_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, S), lambda i, a, *_: (i, a, 0, 0)),
            pl.BlockSpec((1, 1, 1, S), lambda i, a, *_: (i, a, 0, 0)),
        ],
    )
    kwargs = {}
    if not interpret:
        # dimension_semantics audit (see module docstring): both grid dims are
        # data-parallel within one diagonal launch — disjoint writes, reads
        # only of diagonals < d.
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit_bytes(R, S, T.dtype.itemsize, cand_tile),
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
    clamped windows past the diagonal's end).  ``interpret`` has no default:
    every caller says whether it runs the compiled kernel or the interpreter.
    """
    B, R = left.shape
    dtype = left.dtype
    rr = jnp.arange(R)
    # base diagonal T[b, b, s] = 2 s(b) (s + n_l(b)), batched (same op order
    # as ref.base_diagonal so the f32 path stays bit-identical to the oracle)
    svec = jnp.arange(S, dtype=dtype)
    base = 2 * (right - left)[:, :, None] * (svec[None, None, :] + nl[:, :, None])
    base = jnp.minimum(base, _limits(dtype)[1])
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
