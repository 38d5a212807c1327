"""The plain reference: every answer computed again, independently.

Straightforward float64 NumPy copies of the repo's plain references, made
from the benchmark's own data and importing nothing of the program:

- Eq. 3 as ``repro.core.scoring.availability_scores_ref`` (trapezoid area,
  least-squares slope, population std, each MinMax-normalised over the
  candidates a request keeps), Eq. 2 as ``cost_scores_ref``, Eq. 4;
- Algorithm 1 as ``repro.core.pool.greedy_pool``, line by line;
- the filter semantics of ``ResourceRequest.filter_mask`` (set membership
  per column).

``precision="bfloat16"`` switches the reference to the control of the
output check: the statistics and scores in bfloat16, the step below the
float32 archive.

A rolling integer tier (``TierWindows``) is followed version by version:
every window is held as the tier documents that it stores it (codes of a
per-candidate step fixed when the ring is primed, clipped to the code
range) and its statistics are float64.  ``bits=4`` is the control of an
int8 tier.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

BLOCK = 16384          # rows per block of the O(K * T) statistics pass


def window_stats(t3: np.ndarray, precision: str = "float64"):
    """``(area, slope, std)`` of every candidate's T3 window: area by the
    trapezoid rule, the least-squares slope against the sample index, the
    population standard deviation.  Computed in blocks of rows."""
    k, t = t3.shape
    out = tuple(np.empty(k) for _ in range(3))
    t_c = np.arange(t) - (t - 1) / 2.0
    denom = t_c @ t_c if t > 1 else 1.0
    for a in range(0, k, BLOCK):
        b = min(a + BLOCK, k)
        if precision == "bfloat16":
            vals = _stats_bf16(np.asarray(t3[a:b], np.float32))
        else:
            y = np.asarray(t3[a:b], np.float64)
            mean = y.mean(axis=1, keepdims=True)
            d = y - mean
            vals = (y.sum(axis=1) - 0.5 * (y[:, 0] + y[:, -1]),
                    d @ t_c / denom,
                    np.sqrt((d * d).mean(axis=1)))
        for s, v in zip(out, vals):
            s[a:b] = v
    return out


def _stats_bf16(y: np.ndarray):
    """The statistics with bfloat16 arithmetic (on the default device)."""
    import jax.numpy as jnp
    bf = jnp.bfloat16
    x = jnp.asarray(y, bf)
    n = x.shape[1]
    w = jnp.ones(n, bf).at[0].set(0.5).at[-1].set(0.5)
    t = jnp.arange(n, dtype=bf) - bf((n - 1) / 2.0)
    mean = jnp.mean(x, axis=1, keepdims=True, dtype=bf)
    area = jnp.sum(x * w, axis=1, dtype=bf)
    slope = jnp.sum((x - mean) * t, axis=1, dtype=bf) / jnp.sum(t * t, dtype=bf)
    std = jnp.sqrt(jnp.mean((x - mean) ** 2, axis=1, dtype=bf))
    return (np.asarray(area, np.float64), np.asarray(slope, np.float64),
            np.asarray(std, np.float64))


def tier_scale(t3: np.ndarray, headroom: float, bits: int) -> np.ndarray:
    """Per-candidate step of an integer tier, float32: the largest magnitude
    of the primed window x ``headroom``, over the top code
    ``2**(bits-1) - 1``."""
    top = 2 ** (bits - 1) - 1
    maxabs = np.empty(t3.shape[0], np.float32)
    for a in range(0, t3.shape[0], BLOCK):
        maxabs[a:a + BLOCK] = np.abs(t3[a:a + BLOCK]).max(axis=1)
    return (np.maximum(maxabs * np.float32(headroom), np.float32(1e-12))
            * np.float32(1.0 / top))


def tier_decode(y: np.ndarray, scale: np.ndarray, bits: int) -> np.ndarray:
    """Values as the tier stores them: the code ``round(y / step)`` (half to
    even, float32), clipped to +-``2**(bits-1) - 1``, times the step.
    ``scale`` broadcasts against ``y``."""
    top = 2 ** (bits - 1) - 1
    codes = np.clip(np.round(np.asarray(y, np.float32) / scale), -top, top)
    return codes.astype(np.float32) * scale


class TierWindows:
    """``(area, slope, std)`` of every version of a rolling tier's window.

    Version ``v`` holds columns ``v .. v + T - 1`` of the primed window
    ``t3`` (K, T) followed by ``ticks`` (K, n): each tick absorbed is one
    version.  The first window is summed directly; each later version
    slides by one column, adding the new and dropping the oldest, all in
    float64 with the index counted from the primed window's first column.
    Versions are visited in increasing order.
    """

    def __init__(self, t3: np.ndarray, ticks: np.ndarray, headroom: float,
                 bits: int):
        self.t3, self.ticks, self.bits = t3, ticks, bits
        k, self.n = t3.shape
        self.scale = tier_scale(t3, headroom, bits)
        self.s0, self.s1, self.q = np.empty(k), np.empty(k), np.empty(k)
        self.ref = np.empty(k)
        j = np.arange(self.n, dtype=np.float64)
        for a in range(0, k, BLOCK):
            b = min(a + BLOCK, k)
            y = tier_decode(t3[a:b], self.scale[a:b, None],
                            bits).astype(np.float64)
            self.ref[a:b] = y.mean(axis=1)
            self.s0[a:b] = y.sum(axis=1)
            self.s1[a:b] = y @ j
            self.q[a:b] = ((y - self.ref[a:b, None]) ** 2).sum(axis=1)
        self.version = 0

    def column(self, j: int) -> np.ndarray:
        """Column ``j`` (counted from the primed window's first) as the tier
        stores it, float64."""
        y = self.t3[:, j] if j < self.n else self.ticks[:, j - self.n]
        return tier_decode(y, self.scale, self.bits).astype(np.float64)

    def stats(self, version: int):
        if version < self.version:
            raise ValueError(f"version {version} after {self.version}")
        if version - self.version > self.ticks.shape[1]:
            raise ValueError(f"version {version}: only "
                             f"{self.ticks.shape[1]} ticks")
        while self.version < version:
            v = self.version
            old, new = self.column(v), self.column(v + self.n)
            self.s0 += new - old
            self.s1 += (v + self.n) * new - v * old
            self.q += (new - self.ref) ** 2 - (old - self.ref) ** 2
            self.version += 1
        v, n = self.version, self.n
        mean = self.s0 / n
        t_c = np.arange(n) - (n - 1) / 2.0
        denom = t_c @ t_c if n > 1 else 1.0
        area = self.s0 - 0.5 * (self.column(v) + self.column(v + n - 1))
        slope = (self.s1 - (v + (n - 1) / 2.0) * self.s0) / denom
        var = self.q / n - (mean - self.ref) ** 2
        return area, slope, np.sqrt(np.maximum(var, 0.0))


FILTERS = ("regions", "azs", "families", "categories", "types")


def request_key(req) -> tuple:
    """Everything of a request that its answer depends on."""
    filters = tuple(None if getattr(req, f) is None
                    else tuple(sorted(getattr(req, f))) for f in FILTERS)
    return (filters, req.cpus, req.memory_gb, req.weight, req.lam)


def filter_mask(req, cands) -> np.ndarray:
    mask = np.ones(len(cands.names), bool)
    for values, col in ((req.regions, cands.regions), (req.azs, cands.azs),
                        (req.families, cands.families),
                        (req.categories, cands.categories),
                        (req.types, cands.names)):
        if values is not None:
            mask &= np.isin(col, np.asarray(values))
    return mask


def _minmax(x: np.ndarray) -> np.ndarray:
    rng = x.max() - x.min()
    return (x - x.min()) / rng if rng > 0 else np.zeros_like(x)


def greedy_pool(scores, caps, required: float):
    """Algorithm 1, line by line: ``(indices, counts)`` in pool order."""
    scores = np.asarray(scores, np.float64)
    caps = np.asarray(caps, np.float64)
    order = np.argsort(-scores, kind="stable")
    pool: list[int] = []
    best: dict = {}
    prev_top = math.inf
    top = int(order[0])
    for i in order:
        pool.append(int(i))
        total = float(scores[pool].sum())
        if total <= 0:
            break
        cur = {j: int(math.ceil(scores[j] / total * required / caps[j]))
               for j in pool}
        if cur[top] >= prev_top or cur[int(i)] == 0:
            break
        best = cur
        prev_top = cur[top]
    if not best:
        best = {top: int(math.ceil(required / caps[top]))}
    idx = np.array(sorted(best, key=lambda j: -scores[j]), np.int64)
    return idx, np.array([best[j] for j in idx], np.int64)


@dataclass
class Answer:
    members: np.ndarray      # candidate indices, in pool order
    counts: np.ndarray
    hourly: float
    considered: int
    mask: np.ndarray
    combined: np.ndarray     # (K,) over the mask, NaN elsewhere
    availability: np.ndarray
    cost: np.ndarray


def answer(req, cands, stats, precision: str = "float64") -> Answer:
    """The recommendation for ``req`` from the window's statistics."""
    mask = filter_mask(req, cands)
    idx = np.flatnonzero(mask)
    caps = np.asarray(cands.vcpus if req.cpus is not None
                      else cands.memory_gb, np.float64)[idx]
    prices = np.asarray(cands.prices, np.float64)[idx]
    area, slope, std = (np.asarray(s)[idx] for s in stats)
    if precision == "bfloat16":
        import ml_dtypes
        bf = ml_dtypes.bfloat16
        area, slope, std, caps_c, prices_c = (
            np.asarray(x, bf) for x in (area, slope, std, caps, prices))
        one, hundred = bf(1.0), bf(100.0)
        lam, w, amt = bf(req.lam), bf(req.weight), bf(req.amount)
        avail = hundred * _minmax(area) * (one + lam * (_minmax(slope)
                                                        - _minmax(std)))
        avail = np.maximum(avail, bf(0.0))
        total = prices_c * np.ceil(amt / caps_c)
        cost = hundred * total.min() / total
        comb = w * avail + (one - w) * cost
        avail, cost, comb = (np.asarray(x, np.float64)
                             for x in (avail, cost, comb))
    else:
        avail = np.maximum(100.0 * _minmax(area) * (
            1.0 + req.lam * (_minmax(slope) - _minmax(std))), 0.0)
        total = prices * np.ceil(req.amount / caps)
        cost = 100.0 * total.min() / total
        comb = req.weight * avail + (1.0 - req.weight) * cost
    pick, counts = greedy_pool(comb, caps, req.amount)
    rows = []
    for v in (comb, avail, cost):
        row = np.full(len(mask), np.nan)
        row[idx] = v
        rows.append(row)
    members = idx[pick]
    hourly = float((np.asarray(cands.prices, np.float64)[members]
                    * counts).sum())
    return Answer(members, counts, hourly, int(mask.sum()), mask, *rows)
