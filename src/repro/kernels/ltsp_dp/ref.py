"""Plain NumPy reference of the wavefront LTSP DP: dense int64 tables.

The exact Python DP (:mod:`repro.core.dp`) memoises only reachable
``(a, b, n_skip)`` cells; the device formulation instead materialises the full
table ``T[R, R, S]`` over every skip count ``s in [0, S)`` and fills it one
anti-diagonal ``d = b - a`` at a time.  Every recurrence is valid for an
arbitrary ``s`` parameter, so the dense table contains no garbage: the only
approximation is the clamped gather ``T[a, b-1, min(s + x_b, S-1)]``, which
can only be hit from cells that are themselves unreachable from the root
``(0, R-1, 0)`` (a reachable chain keeps ``s + sum(x) <= n < S``).

:func:`ltsp_tables_ref` computes the value table ``T`` *and* the argmin plane
``C`` the same way, over every cell and lane, with the kernel's rules: the
skip reads lane ``min(s + x_b, S - 1)``; the skip wins ties, then the
smallest detour start ``c``; LOGDP spans and the SIMPLEDP ``disjoint`` clip
narrow the candidates as they narrow the kernel's band.  It computes in
int64 and neither clips nor saturates, so it equals the int32 kernel on
every instance whose values stay below the kernel's clip — the small
instances the kernel tests hold it to.
"""

from __future__ import annotations

import numpy as np

from ...core.instance import Instance, virtual_lb

__all__ = ["ltsp_tables_ref", "root_cost"]

#: what a masked candidate reads, as in the kernel
_BIG = 2**31 - 1


def ltsp_tables_ref(left, right, x, nl, u, S: int, span=None, disjoint=False):
    """Dense ``(T, C, ties)`` of one instance (``[R]`` arrays, ``u`` the
    U-turn penalty): ``T[a, b, s]`` the values, ``C[a, b, s]`` the argmin
    (-1 = skip, else the detour start ``c``), and ``ties`` the number of
    skip-detour and detour-detour ties met on the way."""
    left, right, x, nl = (np.asarray(v, np.int64) for v in (left, right, x, nl))
    u = int(u)
    R, s = len(left), np.arange(S)
    T = np.zeros((R, R, S), np.int64)
    C = np.full((R, R, S), -1, np.int64)
    rr = np.arange(R)
    T[rr, rr] = 2 * (right - left)[:, None] * (s + nl[:, None])
    ties = np.zeros(2, np.int64)
    for d in range(1, R):
        a = np.arange(R - d)
        b = a + d
        idx = np.minimum(s[None] + x[b][:, None], S - 1)
        skip = (np.take_along_axis(T[a, b - 1], idx, 1)
                + 2 * (right[b] - right[b - 1])[:, None] * (s + nl[a][:, None])
                + (2 * (left[b] - right[b - 1]) * x[b])[:, None])
        c_min = a + 1
        if span is not None:  # LOGDP restriction: b - c <= span
            c_min = np.maximum(c_min, b - span)
        if disjoint:  # SIMPLEDP restriction: detours only at the root level
            c_min = np.where(a > 0, b + 1, c_min)
        det = np.full((R - d, S), _BIG, np.int64)
        arg = np.zeros((R - d, S), np.int64)
        for k in range(1, d + 1):
            c = a + k
            cand = (T[a, c - 1] + T[c, b]
                    + 2 * (right[b] - right[c - 1])[:, None] * (s + nl[a][:, None])
                    + 2 * u * (s + nl[c][:, None]))
            cand = np.where((c >= c_min)[:, None], cand, _BIG)
            ties[1] += np.sum((cand == det) & (cand < _BIG))
            better = cand < det
            det = np.where(better, cand, det)
            arg = np.where(better, c[:, None], arg)
        ties[0] += np.sum(skip == det)
        T[a, b] = np.minimum(skip, det)
        C[a, b] = np.where(skip <= det, -1, arg)
    return T, C, ties


def root_cost(T, inst: Instance) -> int:
    """The optimal LTSP objective from a dense table of ``inst`` (padded
    files included): ``T[0, R - 1, 0] + VirtualLB``."""
    return int(T[0, T.shape[0] - 1, 0]) + virtual_lb(inst)
