"""Tape traffic for the chip benchmark, drawn from a seed.

A configuration's ``population`` holds the paper's tape statistics
(arXiv:2112.09384, Tables 1-2 and Appendix C.1).  :func:`draw_tape` is a copy
of the program's ``repro.data.generator.generate_instance`` (same draws, in
the same order), kept here so that the traffic cannot move with the program.
A traffic file (``traffic/<name>.json``) says which tapes a cell sends and
when; :func:`plan` turns it and a seed into the cell's list of decisions.

Two traffic shapes are read:

* ``"loop": "closed"`` -- one caller, decisions back to back, over a pool of
  ``pool`` fresh tapes that cycles if the window outlasts it;
* ``"loop": "open"`` -- cartridges due at ``rate_per_s``; the gaps are the
  quantiles of an exponential distribution, shuffled by the seed, so every
  seed sends the same gaps and the same tape sizes in another order.

``"buckets"`` names the ``[R, S]`` shape buckets the tapes come from, or
``"population"`` for every bucket of the configuration's chip population.
Each bucket gets the share it has in that population (largest remainder),
and each slot is a fresh tape drawn from the seed and conditioned on its
bucket.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = [
    "Tape",
    "Plan",
    "draw_tape",
    "base_dataset",
    "u_turn",
    "bucket",
    "table_bound",
    "fits_chip",
    "chip_population",
    "plan",
]

#: give up when conditioned draws need more than this many tapes per slot
MAX_DRAWS_PER_SLOT = 2000


@dataclasses.dataclass(frozen=True)
class Tape:
    """Requested files of one cartridge, left to right (integer units)."""

    left: np.ndarray
    size: np.ndarray
    mult: np.ndarray
    m: int
    u_turn: int

    @property
    def right(self) -> np.ndarray:
        return self.left + self.size

    @property
    def n_req(self) -> int:
        return len(self.left)

    @property
    def n(self) -> int:
        return int(self.mult.sum())


@dataclasses.dataclass(frozen=True)
class Plan:
    """A cell's decisions: tapes in order and, in an open loop, due times."""

    tapes: list[Tape]
    due_s: list[float] | None  # None: closed loop


def _lognormal(z: float, median: float, sigma: float, lo, hi):
    return np.clip(median * np.exp(sigma * z), lo, hi)


def draw_tape(pop: dict, cap: int, rng: np.random.Generator, u: int) -> Tape:
    """One tape of ``cap`` units from the population's statistics (the
    generator's draws)."""
    n_f = int(_lognormal(rng.standard_normal(), pop["nf_median"],
                         pop["nf_sigma"], *pop["nf_clip"]))
    frac = float(_lognormal(rng.standard_normal(), pop["req_frac_median"],
                            pop["req_frac_sigma"], *pop["req_frac_clip"]))
    n_req = max(2, min(n_f, pop["n_req_cap"], int(round(frac * n_f))))
    cv = float(_lognormal(rng.standard_normal(), pop["cv_median"],
                          pop["cv_sigma"], *pop["cv_clip"]))
    sigma2 = np.log1p(cv**2)
    mu = np.log(cap / n_f) - sigma2 / 2
    sizes = np.exp(rng.normal(mu, np.sqrt(sigma2), size=n_f))
    sizes = np.maximum(1, np.round(sizes * cap / sizes.sum())).astype(np.int64)
    lefts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    req = np.sort(rng.choice(n_f, size=n_req, replace=False))
    mult = 1 + np.minimum(rng.zipf(pop["mult_alpha"], size=n_req),
                          pop["mult_cap"] - 1)
    return Tape(lefts[req], sizes[req], mult.astype(np.int64),
                int(sizes.sum()), int(u))


def base_dataset(cfg: dict, u: int = 0) -> list[Tape]:
    """The population's fixed tapes (``n_tapes`` from ``base_seed``)."""
    pop = cfg["population"]
    return [draw_tape(pop, cfg["tape_capacity"],
                      np.random.default_rng(pop["base_seed"] + i), u)
            for i in range(pop["n_tapes"])]


def u_turn(cfg: dict) -> int:
    """Paper section 5.3's U-turn penalty named by ``cfg["u_turn"]``: 0, or
    half or all of the mean requested-file size over the fixed tapes."""
    tapes = base_dataset(cfg)
    seg = sum(int(t.size.sum()) for t in tapes) // sum(t.n_req for t in tapes)
    return {"zero": 0, "half_seg": seg // 2, "full_seg": seg}[cfg["u_turn"]]


def _pow2(v: int) -> int:
    return 1 << max(0, int(v) - 1).bit_length()


def bucket(t: Tape) -> tuple[int, int]:
    """Power-of-two shape bucket ``(R, S)``: R files, S = n + 1 skip counts
    rounded to a power-of-two multiple of 128 lanes."""
    return _pow2(t.n_req), 128 * _pow2(-(-(t.n + 1) // 128))


def table_bound(t: Tape) -> int:
    """Bound on the DP's candidate sums once the tape is shifted to its first
    requested byte and divided by the gcd of its coordinates and U."""
    base = int(t.left[0])
    g = 0
    for v in np.concatenate([t.left, t.right]).tolist():
        g = math.gcd(g, v - base)
    g = math.gcd(g, t.u_turn) or 1
    span = (int(t.right[-1]) - base) // g
    return 2 * t.n * (8 * span + (2 * t.n_req + 2) * (t.u_turn // g))


def fits_chip(t: Tape, limits: dict) -> bool:
    """Whether one chip runs the tape: its bucket is within ``max_bucket`` and
    its table fits ``table_bits``-bit signed integers."""
    R, S = bucket(t)
    R_max, S_max = limits["max_bucket"]
    return R <= R_max and S <= S_max and table_bound(t) < 2 ** (limits["table_bits"] - 1)


def chip_population(cfg: dict) -> list[Tape]:
    """The configuration's fixed tapes that one chip runs."""
    return [t for t in base_dataset(cfg, u_turn(cfg))
            if fits_chip(t, cfg["chip_limits"])]


def _apportion(n: int, counts: dict) -> dict:
    """Split ``n`` slots over keys in proportion to ``counts`` (largest
    remainder; ties to the key first in sorted order)."""
    total = sum(counts.values())
    quota = {k: n * v / total for k, v in counts.items()}
    out = {k: int(q) for k, q in quota.items()}
    rest = sorted(quota, key=lambda k: (-(quota[k] - out[k]), k))
    for k in rest[: n - sum(out.values())]:
        out[k] += 1
    return out


def _draw_buckets(cfg: dict, want: list, rng: np.random.Generator) -> list[Tape]:
    """A fresh tape for each wanted bucket, in the order wanted."""
    u = u_turn(cfg)
    need: dict = {}
    for b in want:
        need[b] = need.get(b, 0) + 1
    got: dict = {b: [] for b in need}
    draws = 0
    while any(len(got[b]) < k for b, k in need.items()):
        if draws == MAX_DRAWS_PER_SLOT * len(want):
            raise RuntimeError(f"could not draw tapes for buckets {sorted(need)}")
        draws += 1
        t = draw_tape(cfg["population"], cfg["tape_capacity"], rng, u)
        b = bucket(t)
        if b in need and len(got[b]) < need[b] and fits_chip(t, cfg["chip_limits"]):
            got[b].append(t)
    return [got[b].pop() for b in want]


def plan(cfg: dict, traffic: dict, seed: int, seconds: float) -> Plan:
    """The cell's decisions for one run, from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    counts: dict = {}
    for t in chip_population(cfg):
        counts[bucket(t)] = counts.get(bucket(t), 0) + 1
    if traffic["buckets"] != "population":
        keep = {tuple(b) for b in traffic["buckets"]}
        counts = {b: k for b, k in counts.items() if b in keep}
    if traffic["loop"] == "closed":
        n = traffic["pool"]
    else:
        n = math.ceil(traffic["rate_per_s"] * seconds)
    want = [b for b, k in sorted(_apportion(n, counts).items()) for _ in range(k)]
    rng.shuffle(want)
    tapes = _draw_buckets(cfg, want, rng)
    if traffic["loop"] == "closed":
        return Plan(tapes, None)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / traffic["rate_per_s"]
    rng.shuffle(gaps)
    return Plan(tapes, np.cumsum(gaps).tolist())
