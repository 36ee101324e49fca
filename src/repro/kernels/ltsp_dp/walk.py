"""On-device traceback of the wavefront's argmin plane.

:func:`traceback_device` replays the argmin plane ``C`` where the wavefront
left it, so only the detour list (a few KB) crosses to the host instead of
the ``B·R²·S`` int16 plane (512 MiB at the paper's median bucket, 4 GiB at
the bucket (512, 8192)).  It is the
same pre-order walk as the host reference :func:`.ops.traceback_detours`,
step for step, so it emits the same detours in the same order:

* ``C[a, b, s] == -1`` — skip ``b``: ``s += x[b]``, ``b -= 1``;
* otherwise ``c`` — emit ``(c, b)``, push the frame ``(a, c - 1, s)`` for
  later, descend with ``a = c``;
* a frame with ``a >= b`` is done: resume the last one pushed.

Frames with ``a >= c - 1`` emit nothing, so they are never pushed; every
loop step then reads exactly one cell of ``C``.  A frame ``(a, b, s)`` has
the files ``a+1 .. b`` left to place, and each step places one of them: a
skip places ``b``, a detour ``(c, b)`` places ``c`` and splits the rest
between the frame it pushes and the one it enters.  So every instance walks
exactly ``R - 1`` steps, with at most ``R - 1`` detours and pushed frames.

The walk reads ``C``, ``x`` and the root of ``T`` in place (single-cell
dynamic slices, no copy of either plane), in the wavefront's lane-tiled
layout: skip count ``s`` of cell ``(a, b)`` is ``C[i, a, b, s // 128, s %
128]``, a 16-bit word widened to int32 as it is read.  It is keyed by the
arrays' shapes alone: the LOGDP span, the SIMPLEDP band clip and the policy
leave their mark in ``C`` only.  All-phantom padding rows walk as ``R - 1``
skips.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["traceback_device"]


@jax.jit
def traceback_device(T: jax.Array, C: jax.Array, x: jax.Array):
    """Walk each instance's argmin plane on the device.

    ``T`` and ``C`` are the ``[B, R, R, S/128, 128]`` tables of
    :func:`.ltsp_dp.ltsp_dp_tables` (``T`` in any dtype, ``C`` in any
    integer dtype), ``x`` the
    ``[B, R]`` int32 multiplicities.  Returns ``(detours, n_detours, root,
    steps)``: ``detours[i, :n_detours[i]]`` are instance ``i``'s ``(c, b)``
    pairs in emission order (int32 ``[B, R, 2]``, ``-1`` past the end),
    ``root[i] = T[i, 0, R-1, 0, 0]`` (skip count 0) in ``T``'s dtype, and
    ``steps[i]`` the walk's loop steps (int32 ``[B]``).
    """
    B, R = x.shape
    lanes = C.shape[-1]
    i32 = jnp.int32

    def walk(i):
        def body(st):
            a, b, s, sp, stack, dets, nd, steps = st
            # a finished frame resumes the last one pushed (the loop runs on
            # only while one is left, so then sp > 0)
            pop = a >= b
            top = stack[jnp.maximum(sp - 1, 0)]
            a = jnp.where(pop, top[0], a)
            b = jnp.where(pop, top[1], b)
            s = jnp.where(pop, top[2], s)
            sp = sp - pop.astype(i32)
            c = lax.dynamic_slice(
                C, (i, a, b, s // lanes, s % lanes), (1, 1, 1, 1, 1)
            )[0, 0, 0, 0, 0].astype(i32)
            skip = c == -1
            # slot nd is still -1 here, so a skip writes it back unchanged
            dets = dets.at[nd].set(jnp.where(skip, -1, jnp.stack([c, b])))
            nd = nd + (~skip).astype(i32)
            push = ~skip & (a < c - 1)
            stack = stack.at[sp].set(jnp.where(push, jnp.stack([a, c - 1, s]), stack[sp]))
            sp = sp + push.astype(i32)
            xb = lax.dynamic_slice(x, (i, b), (1, 1))[0, 0]
            s = jnp.where(skip, s + xb, s)
            a = jnp.where(skip, a, c)
            b = jnp.where(skip, b - 1, b)
            return a, b, s, sp, stack, dets, nd, steps + 1

        def cond(st):
            a, b, _, sp = st[:4]
            return (a < b) | (sp > 0)

        zero = jnp.zeros((), i32)
        init = (
            zero, jnp.asarray(R - 1, i32), zero, zero,
            jnp.full((R, 3), -1, i32), jnp.full((R, 2), -1, i32), zero, zero,
        )
        *_, dets, nd, steps = lax.while_loop(cond, body, init)
        return dets, nd, steps

    dets, nd, steps = lax.map(walk, jnp.arange(B, dtype=i32))
    return dets, nd, T[:, 0, R - 1, 0, 0], steps
