"""Recommendation engine facade (paper §4 + Fig. 3's serverless handler path).

Given a :class:`ResourceRequest` and a :class:`CandidateSet` (the T3 archive
slice for the scoring window), the engine:

1. applies the user's filters (region / AZ / family / category / type),
2. computes availability (Eq. 3) + cost (Eq. 2) + combined (Eq. 4) scores in
   one vectorised JAX evaluation over all surviving candidates,
3. forms the heterogeneous pool with the greedy heuristic (Algorithm 1).

This is the exact code path the public web service's FaaS handler would call.

Two entry points:

- :meth:`RecommendationEngine.recommend` — one request at a time; gathers the
  filtered subset and round-trips scores through numpy between stages.
- :meth:`RecommendationEngine.recommend_batch` — B requests in one fused,
  vmapped dispatch.  Filtering is expressed as per-request boolean masks over
  the full candidate axis (static shapes — no per-filter recompiles), and
  Eq. 2-4 scoring plus the all-prefix Algorithm 1 run as a single XLA
  computation.  Bit-compatible with the per-request loop (see
  ``recommend_batch``'s docstring for the exact guarantee); ``serve/`` adds
  the bucketing + archive-cache layer on top.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import pool as pool_lib
from . import scoring
from ..kernels import score_fuse as score_fuse_lib
from .config import EngineConfig, resolve_engine_config
from .types import CandidateSet, Recommendation, RequestBatch, ResourceRequest


# ---------------------------------------------------------------------------
# Fused batched path: Eq. 3 -> Eq. 2 -> Eq. 4 -> Algorithm 1, one dispatch.
# ---------------------------------------------------------------------------

def _dedup_masks(masks: np.ndarray):
    """Collapse identical filter masks: ``(unique_masks, inverse)``.

    The Eq. 3 MinMax bounds depend only on (stats, mask), so requests that
    share a filter combination share one extrema scan.  A batch of
    filterless requests — the common serve case — collapses to one row.
    The unique count is padded to the next power of two (extra rows repeat
    row 0, computed-and-ignored) so the set of compiled (U, K) shapes stays
    bounded at log2(B) per batch shape.
    """
    packed = np.packbits(masks, axis=1)
    index: dict = {}
    rows: list[int] = []
    inv = np.empty(masks.shape[0], np.int32)
    for b in range(masks.shape[0]):
        key = packed[b].tobytes()
        i = index.setdefault(key, len(rows))
        if i == len(rows):
            rows.append(b)
        inv[b] = i
    u_pad = 1 << (len(rows) - 1).bit_length()
    rows = rows + [rows[0]] * (u_pad - len(rows))
    return masks[np.asarray(rows)], inv


@functools.partial(jax.jit, static_argnames=("score_impl",))
def _batched_scores(t3, prices, vcpus, memory_gb, masks, use_cpus,
                    weights, lams, amounts, stats=None, uniq_masks=None,
                    uniq_inv=None, *, score_impl: str = "dense"):
    """The batched scoring stage: (B, K) combined / availability / cost.

    ``score_impl="dense"`` is the vmapped full-Eq. 3 evaluation (re-reduces
    the (K, T) archive slice every call).  ``"tiled"`` runs the streaming
    masked kernel over precomputed per-candidate ``stats`` (computed here
    from ``t3`` when not supplied by the archive cache), with the Eq. 3
    MinMax bounds shared per unique filter mask (``uniq_masks``/``uniq_inv``
    from :func:`_dedup_masks`).
    """
    if score_impl == "tiled":
        if stats is None:
            stats = scoring.candidate_stats(t3)
        area, slope, std = stats
        lo_u, hi_u = jax.vmap(
            lambda m: score_fuse_lib.stat_extrema(area, slope, std, m)
        )(uniq_masks)
        lo_b, hi_b = lo_u[uniq_inv], hi_u[uniq_inv]
        comb, avail, cost = jax.vmap(
            lambda m, uc, amt, lam, wt, lo, hi: score_fuse_lib.score_fuse(
                area, slope, std, prices, vcpus, memory_gb, m, uc, amt,
                lam, wt, extrema=(lo, hi))
        )(masks, use_cpus, amounts, lams, weights, lo_b, hi_b)
        return comb, avail, cost
    avail = jax.vmap(scoring.availability_scores_masked,
                     in_axes=(None, 0, 0))(t3, lams, masks)
    caps = jnp.where(use_cpus[:, None], vcpus[None, :],
                     memory_gb[None, :]).astype(jnp.float32)       # (B, K)
    cost = jax.vmap(scoring.cost_scores_masked,
                    in_axes=(None, 0, 0, 0))(prices, caps, amounts, masks)
    comb = scoring.combined_scores(avail, cost, weights[:, None])
    return comb, avail, cost


@functools.partial(jax.jit, static_argnames=("pool_impl", "score_impl"))
def _fused_recommend_batch(t3, prices, vcpus, memory_gb,
                           masks, use_cpus, weights, lams, amounts,
                           stats=None, uniq_masks=None, uniq_inv=None,
                           *, pool_impl: str = "dense",
                           score_impl: str = "dense"):
    """Eq. 3 -> Eq. 2 -> Eq. 4 -> Algorithm 1 for B masked requests, fused
    into one XLA computation (each stage vmapped over the batch axis).

    ``pool_impl`` selects the all-prefix Algorithm 1 scan: the dense
    O(B*K^2) allocation-matrix formulation, or the tiled streaming kernel
    (O(B*K) memory) that lifts the candidate-fan-out ceiling.  ``score_impl``
    selects the scoring stage the same way (see :func:`_batched_scores`).
    Both are resolved, not "auto", because the choice is a compile-time
    branch.
    """
    caps = jnp.where(use_cpus[:, None], vcpus[None, :],
                     memory_gb[None, :]).astype(jnp.float32)       # (B, K)
    comb, avail, cost = _batched_scores(
        t3, prices, vcpus, memory_gb, masks, use_cpus, weights, lams,
        amounts, stats, uniq_masks, uniq_inv, score_impl=score_impl)
    order, counts, k_stop, any_term = jax.vmap(
        functools.partial(pool_lib.greedy_pool_masked, impl=pool_impl)
    )(comb, caps, amounts, masks)
    return comb, avail, cost, order, counts, k_stop, any_term


def _apply_max_types(idx: np.ndarray, counts: np.ndarray, comb: np.ndarray,
                     caps: np.ndarray, amount: float, max_types: int | None):
    """Cap pool diversity: keep the top-scoring members, re-allocate."""
    if max_types is None or len(idx) <= max_types:
        return idx, counts
    keep = idx[:max_types]
    s = comb[keep]
    total = s.sum()
    if total > 0:
        r = s / total * amount
    else:
        # All kept scores zero (e.g. W=1 with a flat archive): the
        # score-proportional split is 0/0, so allocate equally instead.
        r = np.full(len(keep), amount / len(keep))
    counts = np.ceil(r / caps[keep]).astype(np.int64)
    return keep, counts


class RecommendationEngine:
    """Stateless scoring + pool formation over a candidate archive slice.

    ``config`` (an :class:`~repro.core.EngineConfig`) is the one place the
    stack's tunables live; the engine consumes its ``pool_impl`` and
    ``score_impl`` fields:

    - ``pool_impl`` selects the Algorithm 1 all-prefix scan: ``"dense"``
      (O(K^2) allocation matrix), ``"tiled"`` (streaming kernel, O(K)
      memory — required for archives of tens of thousands of candidates),
      or ``"auto"`` (default: tiled from ``pool_lib.POOL_TILED_AUTO_K``
      candidates up).  Both produce bit-identical pools.
    - ``score_impl`` selects the batched scoring stage the same way:
      ``"dense"`` re-evaluates the full Eq. 3 chain over the (K, T) archive
      slice every batch; ``"tiled"`` streams the per-request O(K) remainder
      (``repro.kernels.score_fuse``) over per-candidate statistics that are
      computed once — and cached on the staged archive when one is
      supplied — turning the batched scoring stage from O(K*T + B*K) per
      batch into O(B*K) amortized.  ``"auto"`` switches at
      ``scoring.SCORE_TILED_AUTO_K`` candidates.

    The per-knob ``pool_impl=`` / ``score_impl=`` keyword arguments are
    deprecated (:class:`~repro.core.config.APIDeprecationWarning`); they
    still work and map onto an equivalent config.
    """

    def __init__(self, config: EngineConfig | None = None, *,
                 use_vectorized_pool: bool = True,
                 pool_impl: str | None = None, score_impl: str | None = None):
        self.config = resolve_engine_config(
            config, pool_impl=pool_impl, score_impl=score_impl)
        self._use_vectorized = use_vectorized_pool
        self.pool_impl = self.config.pool_impl
        self.score_impl = self.config.score_impl
        #: optional callable ``(request, recommendation) -> None`` invoked for
        #: every recommendation this engine returns (both entry points).  The
        #: closed-loop operator (``repro.operator``) registers issued pools
        #: into its CMDB through this hook; ``BatchServer`` exposes the same
        #: attribute of its engine, so one subscription covers direct engine
        #: calls and the whole serving stack.  A raising sink is a bug in the
        #: subscriber, never in serving: exceptions are swallowed into a
        #: warning and the caller still gets its recommendations.
        self.result_sink = None

    def _emit_results(self, requests, recs) -> None:
        if self.result_sink is None:
            return
        import warnings
        for req, rec in zip(requests, recs):
            try:
                self.result_sink(req, rec)
            except Exception as err:  # noqa: BLE001 — see result_sink contract
                warnings.warn(f"result_sink raised {err!r}; recommendation "
                              "delivery is unaffected", RuntimeWarning,
                              stacklevel=3)

    def score(self, cands: CandidateSet, req: ResourceRequest):
        """Return (combined S, availability AS, cost CS) for all candidates."""
        avail = np.asarray(scoring.availability_scores(cands.t3, req.lam))
        cost = np.asarray(scoring.cost_scores(
            cands.prices, req.capacity_of(cands), req.amount))
        comb = np.asarray(scoring.combined_scores(avail, cost, req.weight))
        return comb, avail, cost

    def recommend(self, cands: CandidateSet, req: ResourceRequest) -> Recommendation:
        """One request through filter -> score -> Algorithm 1.

        Raises ``ValueError`` when the filters leave no candidate — the
        same empty-filter contract :meth:`recommend_batch` applies per
        batch row, so the two entry points never disagree on whether a
        request is servable.
        """
        mask = req.filter_mask(cands)
        if not mask.any():
            raise ValueError("no candidates satisfy the request filters")
        sub = cands.take(np.flatnonzero(mask))
        comb, avail, cost = self.score(sub, req)

        if self._use_vectorized:
            form = functools.partial(pool_lib.greedy_pool_vectorized,
                                     impl=self.pool_impl)
        else:
            form = pool_lib.greedy_pool
        result = form(comb, np.asarray(req.capacity_of(sub), np.float64), req.amount)
        idx, counts = _apply_max_types(
            result.indices, result.counts, comb,
            np.asarray(req.capacity_of(sub), np.float64), req.amount,
            req.max_types)
        hourly = float((sub.prices[idx] * counts).sum())
        rec = Recommendation(
            names=sub.names[idx], regions=sub.regions[idx], azs=sub.azs[idx],
            counts=counts, combined=comb[idx], availability=avail[idx],
            cost=cost[idx], hourly_cost=hourly,
            diagnostics={
                "candidates_considered": int(mask.sum()),
                "greedy_iterations": result.iterations,
                "solve_time_s": result.solve_time_s,
            },
        )
        self._emit_results([req], [rec])
        return rec

    def recommend_batch(self, cands: CandidateSet, requests,
                        *, pad_to: int | None = None,
                        archive=None) -> list[Recommendation]:
        """Serve B requests in one fused dispatch; order matches ``requests``.

        Parity with calling :meth:`recommend` per request: the recommended
        pool is bit-identical — same members in the same order, same node
        counts, same hourly cost, same diagnostics — and the reported scores
        agree to the last float32 ulp.  (Exact score bits can differ because
        XLA FMA-contracts the elementwise scoring chains differently for the
        gathered (K_sub,) and the masked (B, K) compilations; the cross-
        candidate reductions themselves — MinMax, C_min, prefix sums — are
        masked, not gathered, precisely so they stay exact.)

        Empty-filter contract (shared with :meth:`recommend`): a request
        whose filters leave **no** candidate raises ``ValueError`` — for a
        batch, naming the offending row — before anything dispatches.  An
        all-masked row must never reach the fused computation: the masked
        Algorithm 1 scan would terminate degenerately at k = 0 and emit a
        single-type pool on a candidate the request explicitly filtered
        out.  Both entry points therefore agree: there is no empty-pool
        ``Recommendation``, only the raise.

        Diagnostics: ``solve_time_s`` is the **whole-batch wall time** —
        batch assembly through device read-back — stamped identically on
        every request in the batch.  It is a batch-throughput figure, not a
        per-request latency; divide by ``diagnostics["batch_size"]`` for a
        per-request amortized cost.

        ``pad_to`` pads the batch axis so the serve layer can bound the set
        of compiled (B, K) shapes; padded rows are computed-and-discarded.
        ``archive`` is an optional :class:`repro.serve.DeviceArchive` whose
        device-resident arrays skip the per-call host->device transfer of
        the candidate set — and, under the tiled scoring stage, whose cached
        per-candidate statistics skip the O(K*T) pass entirely.  A K-sharded
        archive (``repro.shard``, ``is_sharded = True``) routes to the
        per-shard pipeline instead of the single-device fused dispatch; its
        pools are bit-identical to the single-device tiled path.

        Quantised archives (``EngineConfig.archive_precision`` = "bfloat16"
        / "int8", staged via ``DeviceArchive.stage(precision=...)`` or a
        quantised rolling ring) serve through the same paths with one
        semantic difference: their T3 samples carry a bounded storage error
        (at most half the per-candidate quantisation step), so combined
        scores may drift within the budget ``repro.core.quantized``
        derives — and the recommended pool is bit-identical to the float32
        tier's whenever every Algorithm 1 decision margin exceeds that
        budget (ties inside it are flagged by the parity tooling, not
        hidden).  Catalog columns — prices, vcpus, memory — are never
        quantised, so hourly-cost accounting is exact on every tier.
        """
        requests = list(requests)
        if not requests:
            return []
        t0 = time.perf_counter()
        batch = RequestBatch.from_requests(cands, requests, pad_to=pad_to)
        # Defensive re-check of the empty-filter contract: from_requests
        # raises per row, but the invariant is load-bearing enough (see the
        # docstring) to hold against any future batch constructor too.
        empty = ~batch.masks[:batch.n_valid].any(axis=1)
        if empty.any():
            raise ValueError("no candidates satisfy the request filters "
                             f"(batch row {int(np.flatnonzero(empty)[0])})")
        if archive is not None and getattr(archive, "is_sharded", False):
            from .. import shard as shard_lib
            uniq_masks, uniq_inv = _dedup_masks(batch.masks)
            comb, avail, cost, order, counts, k_stop = (
                shard_lib.sharded_batch_arrays(
                    archive, batch.masks, batch.use_cpus, batch.weights,
                    batch.lams, batch.amounts, uniq_masks, uniq_inv,
                    pool_impl=pool_lib.resolve_pool_impl(self.pool_impl,
                                                         len(cands))))
            return self._build_recommendations(
                cands, batch, requests, comb, avail, cost, order, counts,
                k_stop, time.perf_counter() - t0)
        operands, statics = self._fused_operands(cands, batch, archive)
        comb, avail, cost, order, counts, k_stop, _ = jax.device_get(
            _fused_recommend_batch(*operands, **statics))
        return self._build_recommendations(
            cands, batch, requests, comb, avail, cost, order, counts, k_stop,
            time.perf_counter() - t0)

    def _fused_operands(self, cands: CandidateSet, batch: RequestBatch,
                        archive=None):
        """``(operands, static kwargs)`` of the single-device fused dispatch
        :meth:`recommend_batch` makes for ``batch``."""
        impl = pool_lib.resolve_pool_impl(self.pool_impl, len(cands))
        s_impl = scoring.resolve_score_impl(self.score_impl, len(cands))
        if (s_impl == "dense" and archive is not None
                and not getattr(archive, "dense_capable", True)):
            # Version-pinned snapshots carry statistics but no window matrix
            # (repro.stream.ArchiveSnapshot) — they can only feed the tiled
            # stage, whatever the auto threshold says at this K.
            s_impl = "tiled"
        if s_impl == "tiled":
            stats = archive.score_stats() if archive is not None else None
            uniq_masks, uniq_inv = _dedup_masks(batch.masks)
        else:
            stats = uniq_masks = uniq_inv = None
        if archive is not None:
            # With archive-cached stats the fused computation never reads t3
            # (XLA drops the operand), so ask the archive for its cheapest
            # stand-in: rolling/streaming archives hand back an O(K) token
            # instead of materializing their logical window (an O(K*T)
            # gather), which is what keeps per-tick serving O(K).
            t3 = (archive.t3_operand if stats is not None
                  else archive.t3)
            prices, vcpus, memory_gb = (
                archive.prices, archive.vcpus, archive.memory_gb)
        else:
            # Same float32 staging as DeviceArchive so both entry points hit
            # one compiled signature (the kernels cast to float32 regardless).
            t3, prices, vcpus, memory_gb = (
                jnp.asarray(cands.t3, jnp.float32),
                jnp.asarray(cands.prices, jnp.float32),
                jnp.asarray(cands.vcpus, jnp.float32),
                jnp.asarray(cands.memory_gb, jnp.float32))
        operands = (t3, prices, vcpus, memory_gb, batch.masks, batch.use_cpus,
                    batch.weights, batch.lams, batch.amounts, stats,
                    uniq_masks, uniq_inv)
        return operands, {"pool_impl": impl, "score_impl": s_impl}

    def _build_recommendations(self, cands: CandidateSet, batch: RequestBatch,
                               requests, comb, avail, cost, order, counts,
                               k_stop, solve_time: float) -> list[Recommendation]:
        """Materialise :class:`Recommendation`\\ s from the batched arrays.

        Shared tail of the single-device fused dispatch and the sharded
        pipeline — both hand in (B, K) host score rows plus the vmapped
        Algorithm 1 outputs, and this loop applies the ``max_types`` cap,
        exact float64 hourly-cost accounting, and the diagnostics contract
        (``solve_time_s`` is the whole-batch wall time on every row).
        """
        recs = []
        for b, req in enumerate(requests):
            sel = counts[b] > 0
            idx = np.asarray(order[b])[sel].astype(np.int64)
            cnt = np.asarray(counts[b])[sel].astype(np.int64)
            caps = np.asarray(req.capacity_of(cands), np.float64)
            idx, cnt = _apply_max_types(idx, cnt, comb[b], caps, req.amount,
                                        req.max_types)
            hourly = float((cands.prices[idx] * cnt).sum())
            n_real = int(batch.masks[b].sum())
            # Match the sequential path's iteration count: a stop at the first
            # padded lane is the gathered scan running out of candidates, which
            # greedy_pool_vectorized reports as argmax-of-all-false == 0 -> 1.
            # (n_real == 0 cannot reach here — recommend_batch raises on
            # all-masked rows before dispatch, see the empty-filter contract.)
            iters = int(k_stop[b]) + 1 if int(k_stop[b]) < n_real else 1
            recs.append(Recommendation(
                names=cands.names[idx], regions=cands.regions[idx],
                azs=cands.azs[idx], counts=cnt, combined=comb[b][idx],
                availability=avail[b][idx], cost=cost[b][idx],
                hourly_cost=hourly,
                diagnostics={
                    "candidates_considered": n_real,
                    "greedy_iterations": iters,
                    "solve_time_s": solve_time,
                    "batch_size": batch.batch_size,
                },
            ))
        self._emit_results(requests, recs)
        return recs

    def score_archive(self, archive, *, lam: float = scoring.DEFAULT_LAMBDA,
                      weight: float = 0.5, amount: float = 1.0,
                      use_cpus: bool = True):
        """Fresh unfiltered (K,) score rows for an archive's current window.

        One stats-backed tiled dispatch — O(K), never touching the (K, T)
        window — returning ``(combined, availability, cost)`` float32 rows
        over the full candidate axis.  This is the operator's re-scoring
        primitive: as collector ticks roll the archive forward, each
        reconcile cycle reads the per-candidate availability scores its
        tracked pools' members currently have, without paying a full
        recommendation (no Algorithm 1, no per-request masking).

        ``archive`` is any stats-backed operand (``DeviceArchive``, rolling
        archive, version-pinned snapshot).  K-sharded archives route
        through the per-shard pipeline (``repro.shard``): scoring a shard
        in isolation would normalize Eq. 3 against *its own* extrema, so
        the sharded path's exact cross-shard MinMax merge is load-bearing
        here, not an optimisation — the returned rows match the equivalent
        single-device archive's.
        """
        if getattr(archive, "is_sharded", False):
            from .. import shard as shard_lib
            mask = np.ones((1, len(archive.host)), bool)
            impl = pool_lib.resolve_pool_impl(self.pool_impl,
                                              len(archive.host))
            comb, avail, cost, *_ = shard_lib.sharded_batch_arrays(
                archive, mask, np.array([use_cpus]),
                np.array([weight], np.float32),
                np.array([lam], np.float32),
                np.array([amount], np.float32), mask,
                np.zeros(1, np.int32), pool_impl=impl)
            return (np.asarray(comb[0]), np.asarray(avail[0]),
                    np.asarray(cost[0]))
        stats = archive.score_stats()
        mask = np.ones((1, len(archive.host)), bool)
        comb, avail, cost = _batched_scores(
            archive.t3_operand, archive.prices, archive.vcpus,
            archive.memory_gb, mask, np.array([use_cpus]),
            np.array([weight], np.float32), np.array([lam], np.float32),
            np.array([amount], np.float32), stats, mask,
            np.zeros(1, np.int32), score_impl="tiled")
        return (np.asarray(comb[0]), np.asarray(avail[0]),
                np.asarray(cost[0]))
