"""Plain reference for the LTSP dynamic program: NumPy, int64, exact.

It follows the paper's recurrence (arXiv:2112.09384, section 4.3) over the
requested files of one tape and imports nothing of the system under test.
``T[a, b, s]`` is the cost, beyond *VirtualLB*, of the head movement between
its first arrival at ``r(b)`` and its return there after reading ``a``, with
``s`` requests skipped at the first arrival::

  T[b, b, s]    = 2 (r_b - l_b) (s + nl_b)
  skip(a, b, s) = T[a, b-1, s + x_b] + 2 (r_b - r_{b-1}) (s + nl_a)
                  + 2 (l_b - r_{b-1}) x_b
  det_c(a,b,s)  = T[a, c-1, s] + T[c, b, s] + 2 (r_b - r_{c-1}) (s + nl_a)
                  + 2 U (s + nl_c)                      for a < c <= b
  T[a, b, s]    = min(skip, min_c det_c)

and the optimum is ``T[0, R-1, 0] + VirtualLB``.  A span limit keeps only
``b - c <= span`` (the paper's LOGDP).  Ties go to the skip, then to the
smallest ``c``, so the schedule is the canonical one.

Only cells whose ``s`` is at most the number of requests right of ``b``
are computed: the root is one, and every cell such a cell reads is one too,
so no other cell can reach the result.  Values stay below 2**55 for tapes
under 2**20 units and 2**16 requests, which :func:`solve` checks.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["solve", "span_limit"]


def span_limit(rule: dict | None, n_req: int) -> int | None:
    """The detour span a configuration's rule allows at ``n_req`` files:
    ``None`` (no limit) or ``ceil(lam * ln n_req)``, at least 1."""
    if rule is None:
        return None
    return max(1, math.ceil(rule["lambda"] * math.log(max(2, n_req))))


def solve(left, right, mult, m: int, u_turn: int, span: int | None = None,
          dtype=np.int64):
    """Optimal ``(cost, detours)`` of one tape; detours sorted.

    ``dtype`` is the tables' type: ``int64`` is exact here; ``float32`` is
    the check's control, the same recurrence in a lower precision.
    """
    x = np.asarray(mult, np.int64)
    R = len(x)
    n = int(x.sum())
    if dtype == np.int64 and not (m < 2**20 and n < 2**16 and u_turn < 2**20):
        raise ValueError("reference is exact only below 2**20 units and 2**16 requests")
    csum = np.concatenate([[0], np.cumsum(x)])
    # cell (a, b) holds s = 0 .. n - csum[b + 1], the requests right of b
    left = np.asarray(left).astype(dtype)
    right = np.asarray(right).astype(dtype)
    xv = x.astype(dtype)
    nl = csum[:-1].astype(dtype)
    U = np.asarray(u_turn, dtype)
    s_all = np.arange(n + 1).astype(dtype)
    # A[a, k, s] = T[a, k, s] - 2 r_k (s + nl_a) and
    # B[c, b, s] = T[c, b, s] + 2 U (s + nl_c), so that
    # det_c = A[a, c-1, s] + B[c, b, s] + 2 r_b (s + nl_a).
    A = np.empty((R, R, n + 1), dtype)
    B = np.empty((R, R, n + 1), dtype)
    buf = np.empty((R, n + 1), dtype)

    def store(a, b, t, top):
        lin_a = 2 * (s_all[:top] + nl[a])
        A[a, b, :top] = t - right[b] * lin_a
        B[a, b, :top] = t + U * lin_a

    def lowest(a, b):
        return a + 1 if span is None else max(a + 1, b - span)

    def skip(a, b, s0, s1):
        """``skip(a, b, s)`` for ``s`` in ``[s0, s1)``."""
        lin_a = 2 * (s_all[s0:s1] + nl[a])
        shifted = A[a, b - 1, s0 + x[b]: s1 + x[b]] + right[b - 1] * (lin_a + 2 * xv[b])
        return (shifted + (right[b] - right[b - 1]) * lin_a
                + 2 * (left[b] - right[b - 1]) * xv[b])

    for b in range(R):
        store(b, b, 2 * (right[b] - left[b]) * (s_all + nl[b]), n + 1)
    root = 0  # T[0, 0, 0]: no request left of file 0
    for d in range(1, R):
        for a in range(R - d):
            b = a + d
            top = n - int(csum[b + 1]) + 1  # s = 0 .. top - 1
            lo = lowest(a, b)
            det = buf[: b + 1 - lo, :top]
            np.add(A[a, lo - 1: b, :top], B[lo: b + 1, b, :top], out=det)
            best = det.min(axis=0) + right[b] * (2 * (s_all[:top] + nl[a]))
            t = np.minimum(best, skip(a, b, 0, top))
            store(a, b, t, top)
            if a == 0 and b == R - 1:
                root = t[0]
    cost = int(np.rint(root)) + sum(
        int(xi) * (m - int(li) + int(si) + int(u_turn))
        for li, si, xi in zip(np.asarray(left, np.int64).tolist(),
                              (np.asarray(right, np.int64) - np.asarray(left, np.int64)).tolist(),
                              x.tolist())
    )
    # the choice is read again only at the cells the traceback visits: the
    # skip wins ties, then the smallest c
    detours = []
    work = [(0, R - 1, 0)]
    while work:
        a, b, s = work.pop()
        while a < b:
            lo = lowest(a, b)
            det = A[a, lo - 1: b, s] + B[lo: b + 1, b, s]
            k = int(np.argmin(det))
            if skip(a, b, s, s + 1)[0] <= det[k] + right[b] * (2 * (s_all[s] + nl[a])):
                s += int(x[b])
                b -= 1
                continue
            c = lo + k
            detours.append((c, b))
            work.append((a, c - 1, s))
            a = c
    return cost, sorted(detours)
