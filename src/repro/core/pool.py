"""Heterogeneous spot-pool formation (paper §4.3, Algorithm 1) + ILP baseline (§6.3.1).

Three implementations are provided:

- ``greedy_pool``          : faithful line-by-line Algorithm 1 (Python loop) —
                             the oracle used by property tests.
- ``greedy_pool_vectorized``: the same algorithm expressed as one vectorised
                             JAX computation over *all* candidate prefixes at
                             once.  This is the production path — jit-able,
                             accelerator-friendly, and bit-identical to the
                             loop version.  It has two interchangeable
                             all-prefix scan implementations selected by
                             ``pool_impl``:

                             * ``"dense"`` — an O(K^2) outer product of
                               prefix score sums against per-candidate node
                               requirements, termination conditions as masks
                               (fastest for small K, memory-bound beyond a
                               few thousand candidates);
                             * ``"tiled"`` — the streaming O(K) kernel in
                               :mod:`repro.kernels.pool_scan` (Pallas on
                               TPU, ``lax.scan`` tiles elsewhere) that never
                               materializes the K x K allocation matrix;
                             * ``"auto"`` (default) — ``"tiled"`` from
                               ``POOL_TILED_AUTO_K`` candidates up.
- ``ilp_pool``             : the paper's comparison ILP (score + diversity
                             bonus objective), solved with scipy's HiGHS MILP
                             (stands in for PuLP+CBC, which is unavailable).
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.pool_scan import _clamped_prefix_sums, pool_scan

#: "auto" switches from the dense K x K scan to the tiled streaming kernel at
#: this many candidates: below it the one-shot dense formulation amortizes
#: better than the sequential tile scan; above it the K^2 buffer dominates
#: both memory and runtime (see benchmarks/pool_scan_scaling.py).
POOL_TILED_AUTO_K = 512

POOL_IMPLS = ("dense", "tiled", "auto")


def resolve_pool_impl(impl: str, k: int) -> str:
    """Resolve the ``pool_impl`` switch for a K-candidate scan."""
    if impl not in POOL_IMPLS:
        raise ValueError(f"pool_impl must be one of {POOL_IMPLS}, got {impl!r}")
    if impl == "auto":
        return "tiled" if k >= POOL_TILED_AUTO_K else "dense"
    return impl


@dataclass
class PoolResult:
    """Allocation result: parallel arrays over the *selected* candidates."""

    indices: np.ndarray       # (M,) indices into the original candidate arrays
    counts: np.ndarray        # (M,) node count per selected type
    scores: np.ndarray        # (M,) combined score S_i of each selected type
    iterations: int = 0       # greedy iterations executed
    solve_time_s: float = 0.0
    extra: dict = field(default_factory=dict)

    @property
    def num_types(self) -> int:
        return int((self.counts > 0).sum())

    def total_cpus(self, cpus: np.ndarray) -> float:
        return float((np.asarray(cpus)[self.indices] * self.counts).sum())

    def total_score(self, scores_all: np.ndarray | None = None) -> float:
        """Sum of S_i over allocated nodes (score-weighted pool quality)."""
        s = self.scores if scores_all is None else np.asarray(scores_all)[self.indices]
        return float((s * self.counts).sum())


# ---------------------------------------------------------------------------
# Algorithm 1 — faithful loop implementation (oracle).
# ---------------------------------------------------------------------------

def greedy_pool(scores, cpus, required: float) -> PoolResult:
    """Greedy heuristic for spot instance pool formation (Algorithm 1)."""
    t0 = time.perf_counter()
    scores = np.asarray(scores, np.float64)
    cpus = np.asarray(cpus, np.float64)
    order = np.argsort(-scores, kind="stable")  # descending, deterministic ties

    pool: list[int] = []
    x_best: dict[int, int] = {}
    x_prev_top = math.inf
    top = int(order[0])
    iters = 0
    for i in order:
        pool.append(int(i))
        iters += 1
        s_total = float(scores[pool].sum())
        if s_total <= 0:
            break
        x_curr = {}
        for j in pool:
            r_j = scores[j] / s_total * required           # score-based allocation
            x_curr[j] = int(math.ceil(r_j / cpus[j]))
        if x_curr[top] >= x_prev_top or x_curr[int(i)] == 0:
            break  # return previous iteration's allocation
        x_best = x_curr
        x_prev_top = x_curr[top]

    if not x_best:  # degenerate: first iteration already terminated
        x_best = {top: int(math.ceil(required / cpus[top]))}
    idx = np.array(sorted(x_best, key=lambda j: -scores[j]), np.int64)
    return PoolResult(
        indices=idx,
        counts=np.array([x_best[int(j)] for j in idx], np.int64),
        scores=scores[idx],
        iterations=iters,
        solve_time_s=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# Algorithm 1 — vectorised JAX implementation (production path).
# ---------------------------------------------------------------------------

def _prefix_allocations(s: jax.Array, c: jax.Array, required: jax.Array,
                        *, impl: str = "dense", tile: int | None = None):
    """All-prefix formulation of Algorithm 1 over pre-sorted (s, c).

    For the score-descending ordering, compute the allocation matrix for every
    prefix length k simultaneously::

        X[k, j] = ceil( S_j * R / (cumsum(S)[k] * CPU_j) )    for j <= k

    and evaluate the termination conditions as masks.  Returns the allocation
    row of the last prefix before the first terminating prefix.

    ``impl="dense"`` materializes X (O(K^2) memory); ``impl="tiled"`` streams
    the same statistics through :func:`repro.kernels.pool_scan.pool_scan` in
    O(K + tile) memory with identical pool output.
    """
    if impl == "tiled":
        return pool_scan(s, c, required, tile=tile)
    K = s.shape[0]
    # Shared with the tiled kernel: identical staging is what makes the two
    # implementations bit-identical, so keep it in one place.
    s_tot = _clamped_prefix_sums(s)                          # (K,) prefix sums
    # X[k, j]: allocation of candidate j within prefix k (j <= k).
    raw = s[None, :] * required / (s_tot[:, None] * c[None, :])
    X = jnp.ceil(raw).astype(jnp.int32)
    tri = jnp.tril(jnp.ones((K, K), bool))
    X = jnp.where(tri, X, 0)

    # Rows and the diagonal are picked by masked integer sums (exact: one
    # term, zeros elsewhere), because a per-row dynamic index or
    # ``jnp.diagonal`` is a gather under the batched engine's vmap.
    rows = jnp.arange(K, dtype=jnp.int32)[:, None]
    top = X[:, 0]                                            # (K,) top-ranked alloc per prefix
    newest = jnp.sum(jnp.where(rows == rows.T, X, 0), axis=1,
                     dtype=jnp.int32)                        # (K,) newest member's alloc
    prev_top = jnp.concatenate([jnp.array([jnp.iinfo(jnp.int32).max],
                                          jnp.int32), top[:-1]])
    terminate = (top >= prev_top) | (newest == 0)
    terminate = terminate.at[0].set(newest[0] == 0)          # x_prev_top = inf at k=0
    any_term = jnp.any(terminate)
    k_stop = jnp.argmax(terminate)                           # first terminating prefix
    k_best = jnp.where(any_term, jnp.maximum(k_stop - 1, 0), K - 1)
    counts_sorted = jnp.sum(jnp.where(rows == k_best, X, 0), axis=0,
                            dtype=jnp.int32)                 # X[k_best]
    # Degenerate guard: if termination fired at k=0 keep the single-type pool.
    fallback = jnp.zeros_like(counts_sorted).at[0].set(
        jnp.ceil(required / c[0]).astype(jnp.int32))
    counts_sorted = jnp.where((any_term & (k_stop == 0)), fallback, counts_sorted)
    return counts_sorted, k_stop, any_term


def _sort_candidates(scores: jax.Array, cpus: jax.Array, mask: jax.Array):
    """Stable score-descending order of the ``mask`` lanes of one candidate row.

    Masked-out lanes take the sort key ``+inf``, so they follow every valid
    one.  The capacities and the mask ride through the same stable sort as
    payloads, and the scores come back as the negated sorted key (negation
    is exact), so nothing is gathered by ``order``: under the batched
    engine's vmap such a gather was ~96% of the served program's device
    time.  The permutation is ``jnp.argsort(key, stable=True)``'s bit for
    bit: both are one ``lax.sort`` on the key with an iota of the default
    integer dtype.  Returns ``(order, s, c, mask_sorted)``: ``s`` is 0 and
    ``c`` is 1 on masked-out lanes, both in the enabled float precision.
    """
    x64 = jax.config.read("jax_enable_x64")
    dtype = jnp.float64 if x64 else jnp.float32
    iota = jnp.arange(scores.shape[0], dtype=jnp.int64 if x64 else jnp.int32)
    key = jnp.where(mask, -scores, jnp.inf)
    key, order, c, mask_sorted = jax.lax.sort(
        (key, iota, cpus, mask), num_keys=1, is_stable=True)
    s = jnp.where(mask_sorted, -key, 0.0).astype(dtype)
    c = jnp.where(mask_sorted, c, 1.0).astype(dtype)
    return order, s, c, mask_sorted


@functools.partial(jax.jit, static_argnames=("impl", "tile"))
def _greedy_pool_core(scores: jax.Array, cpus: jax.Array, required: jax.Array,
                      *, impl: str = "dense", tile: int | None = None):
    order, s, c, _ = _sort_candidates(scores, cpus,
                                      jnp.ones(scores.shape, bool))
    counts_sorted, k_stop, any_term = _prefix_allocations(
        s, c, required, impl=impl, tile=tile)
    return order, counts_sorted, k_stop, any_term


def greedy_pool_masked(scores: jax.Array, cpus: jax.Array, required: jax.Array,
                       mask: jax.Array, *, impl: str = "dense",
                       tile: int | None = None):
    """Algorithm 1 over the ``mask`` lanes of a full-width candidate axis.

    Masked-out candidates sort strictly after every valid one (sort key
    ``+inf``) and contribute score 0 to the prefix sums, so their allocation is
    0 and the ``newest == 0`` condition terminates the prefix scan no later
    than the first masked lane — exactly where the gathered-subset scan would
    have run out of candidates.  Prefixes over valid lanes are bitwise
    identical to ``_greedy_pool_core`` on the gathered subset (zeros appended
    to a cumsum do not perturb earlier partial sums), which is what makes the
    batched path bit-compatible with per-request ``recommend``.

    Trace-safe (no host sync): composes under ``jax.vmap`` / ``jax.jit``.
    Returns ``(order, counts_sorted, k_stop, any_term)`` like the core.
    ``impl`` selects the all-prefix scan ("dense" or "tiled", see module
    docstring); it must be resolved (not "auto") by the caller because the
    choice is trace-static.
    """
    order, s, c, _ = _sort_candidates(scores, cpus, mask)
    counts_sorted, k_stop, any_term = _prefix_allocations(
        s, c, required, impl=impl, tile=tile)
    return order, counts_sorted, k_stop, any_term


def greedy_pool_vectorized(scores, cpus, required: float, *,
                           impl: str = "auto") -> PoolResult:
    t0 = time.perf_counter()
    # Honor the enabled precision end-to-end: staging through float32 when
    # jax_enable_x64 is on would silently discard the x64 path the core
    # selects for its prefix sums.
    dtype = jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32
    scores = jnp.asarray(scores, dtype)
    cpus = jnp.asarray(cpus, dtype)
    impl = resolve_pool_impl(impl, scores.shape[0])
    order, counts_sorted, k_stop, _ = jax.device_get(
        _greedy_pool_core(scores, cpus, jnp.asarray(required, dtype),
                          impl=impl))
    sel = counts_sorted > 0
    idx = np.asarray(order)[sel]
    return PoolResult(
        indices=idx.astype(np.int64),
        counts=np.asarray(counts_sorted)[sel].astype(np.int64),
        scores=np.asarray(scores)[idx],
        iterations=int(k_stop) + 1,
        solve_time_s=time.perf_counter() - t0,
    )


# ---------------------------------------------------------------------------
# ILP baseline (§6.3.1): max  sum S_i * CPU_i * x_i  +  gamma * sum z_i
#                        s.t. R <= sum CPU_i x_i <= R + slack,
#                             z_i = 1 iff x_i > 0  (linking constraints).
# ---------------------------------------------------------------------------

def ilp_pool(scores, cpus, required: float, *, gamma: float = 1.0,
             slack: float | None = None, time_limit: float | None = None) -> PoolResult:
    from scipy.optimize import Bounds, LinearConstraint, milp
    from scipy.sparse import hstack as sp_hstack, identity as sp_eye, diags as sp_diags

    t0 = time.perf_counter()
    scores = np.asarray(scores, np.float64)
    cpus = np.asarray(cpus, np.float64)
    K = scores.shape[0]
    if slack is None:
        slack = float(cpus.max())  # tightest always-feasible over-provision bound
    M = np.ceil((required + slack) / cpus)

    # Variables: [x_0..x_{K-1}, z_0..z_{K-1}]
    c = -np.concatenate([scores * cpus, np.full(K, gamma)])
    constraints = [
        # R <= sum CPU_i x_i <= R + slack
        LinearConstraint(np.concatenate([cpus, np.zeros(K)])[None, :], required, required + slack),
        # x_i - M_i z_i <= 0   (x>0 forces z=1)
        LinearConstraint(sp_hstack([sp_eye(K), sp_diags(-M)]), -np.inf, 0),
        # z_i - x_i <= 0       (z=1 requires x>=1; keeps the bonus honest)
        LinearConstraint(sp_hstack([-sp_eye(K), sp_eye(K)]), -np.inf, 0),
    ]
    bounds = Bounds(np.zeros(2 * K), np.concatenate([M, np.ones(K)]))
    options = {} if time_limit is None else {"time_limit": time_limit}
    res = milp(c, constraints=constraints, integrality=np.ones(2 * K),
               bounds=bounds, options=options)
    if res.x is None:
        raise RuntimeError(f"ILP infeasible / failed: {res.message}")
    x = np.round(res.x[:K]).astype(np.int64)
    idx = np.flatnonzero(x > 0)
    idx = idx[np.argsort(-scores[idx], kind="stable")]
    return PoolResult(
        indices=idx,
        counts=x[idx],
        scores=scores[idx],
        solve_time_s=time.perf_counter() - t0,
        extra={"status": res.status, "objective": -float(res.fun)},
    )
