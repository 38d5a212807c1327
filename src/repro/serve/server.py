"""Batched recommendation server: bucketing + archive cache + stats.

``BatchServer.serve`` is the synchronous core of the paper's web-service
path: it takes whatever number of requests arrived in the current service
interval, splits them into chunks from a fixed ladder of batch sizes
(padding the tail chunk up to the smallest covering bucket), and runs each
chunk through the fused :meth:`RecommendationEngine.recommend_batch`
dispatch against a device-staged archive.

``serve`` is the **single entry point** for every operand the stack knows:
a host :class:`~repro.core.CandidateSet` (staged through the LRU cache), an
already-staged :class:`DeviceArchive`, a live
:class:`~repro.stream.RollingDeviceArchive` or its version-pinned
:class:`~repro.stream.ArchiveSnapshot`, and the K-sharded archives of
``repro.shard`` — callers never branch on archive type (the old
``serve_archive`` name survives as a deprecated alias).

Why bucketing: XLA compiles one program per (B, K) shape.  Serving raw
arrival sizes would compile for every distinct B ever seen; snapping to a
small ladder bounds compilations to ``len(bucket_sizes)`` per archive width
while wasting at most the padding slots (whose rows are computed and
discarded — allocation decisions for real requests are unaffected, see the
RequestBatch padding contract).

Along the candidate axis the engine picks the Algorithm 1 scan per archive
width: dense O(K^2) for small archives, the tiled streaming kernel
(``repro.kernels.pool_scan``) beyond ``POOL_TILED_AUTO_K`` candidates — so a
bucket ladder over a SpotLake-scale multi-region archive (tens of thousands
of (type, AZ) candidates) stays a single dispatch per chunk instead of
splitting the K axis to fit the B x K x K buffer.  Configure with
:class:`~repro.core.EngineConfig`.
"""
from __future__ import annotations

import threading
import warnings
from dataclasses import dataclass, field

from .. import tracing
from ..core.config import (APIDeprecationWarning, EngineConfig,
                           resolve_engine_config)
from ..core.engine import RecommendationEngine
from ..core.types import CandidateSet, Recommendation

DEFAULT_BUCKETS = (1, 8, 64, 256)


@dataclass
class ServeStats:
    """Counters accumulated across ``serve`` calls.

    ``BatchServer`` mutates these under its stats lock: ``serve``
    is reached concurrently by the admission worker thread and direct
    callers, and unsynchronized ``+=`` on the counters would drop updates.

    ``d2h_bytes`` is the bytes of the results read back, summed over
    single-device dispatches (a K-sharded archive's pipeline is not
    counted).
    """

    requests: int = 0
    batches: int = 0
    padded_slots: int = 0
    d2h_bytes: int = 0
    bucket_counts: dict = field(default_factory=dict)   # bucket size -> #batches

    def record(self, n_requests: int, bucket: int) -> None:
        self.requests += n_requests
        self.batches += 1
        self.padded_slots += bucket - n_requests
        self.bucket_counts[bucket] = self.bucket_counts.get(bucket, 0) + 1


def _is_archive(target) -> bool:
    """Anything engine-ready: staged arrays + the host catalog attached."""
    return hasattr(target, "host") and (hasattr(target, "score_stats")
                                        or hasattr(target, "is_sharded"))


class BatchServer:
    """Serve request batches against cached device-staged archives.

    Parameters
    ----------
    engine : RecommendationEngine, optional
        The scoring/pool engine.  Default: one built from ``config`` — pass
        an engine only when it needs knobs the config doesn't carry.
    config : EngineConfig, optional
        The stack's tunables: ``pool_impl`` / ``score_impl`` for the
        default-constructed engine, ``cache_capacity`` / ``cache_max_bytes``
        for the archive LRU.  The per-knob ``pool_impl=`` / ``score_impl=``
        / ``cache_capacity=`` keyword arguments are deprecated shims that
        map onto an equivalent config (``APIDeprecationWarning``).
    bucket_sizes : tuple[int, ...]
        Allowed padded batch sizes, ascending.  Arrivals are chunked
        greedily by the largest bucket, and the remainder is padded up to
        the smallest bucket that covers it.
    """

    def __init__(self, engine: RecommendationEngine | None = None, *,
                 config: EngineConfig | None = None,
                 bucket_sizes: tuple[int, ...] = DEFAULT_BUCKETS,
                 cache_capacity: int | None = None,
                 pool_impl: str | None = None, score_impl: str | None = None):
        if not bucket_sizes or any(b < 1 for b in bucket_sizes):
            raise ValueError("bucket_sizes must be positive")
        self.config = resolve_engine_config(
            config, cache_capacity=cache_capacity, pool_impl=pool_impl,
            score_impl=score_impl)
        self.engine = (engine if engine is not None
                       else RecommendationEngine(config=self.config))
        self.bucket_sizes = tuple(sorted(set(bucket_sizes)))
        self.cache = self.config.build_cache()
        self.stats = ServeStats()
        self._stats_lock = threading.Lock()

    @property
    def result_sink(self):
        """The engine's result hook (see ``RecommendationEngine.result_sink``).

        Delegates to ``self.engine`` — one underlying subscription, so a
        sink set here fires exactly once per recommendation whether the
        caller went through :meth:`serve`, the admission queue, or the
        engine directly.  The closed-loop operator registers issued pools
        through this.
        """
        return self.engine.result_sink

    @result_sink.setter
    def result_sink(self, sink):
        self.engine.result_sink = sink

    def plan_chunks(self, n: int) -> list[tuple[int, int]]:
        """Split ``n`` requests into ``(chunk_len, bucket)`` pieces.

        Pad the remainder up to the smallest covering bucket when at most
        half of that bucket would be padding (padded rows are computed and
        discarded); otherwise emit a full chunk of the largest bucket that
        fits and continue.  Bounds both the dispatch count and the wasted
        compute per serve call.
        """
        chunks = []
        while n > 0:
            cover = next((b for b in self.bucket_sizes if b >= n), None)
            fits = [b for b in self.bucket_sizes if b <= n]
            if cover is not None and (not fits or cover - n <= cover // 2):
                chunks.append((n, cover))
                break
            fit = max(fits)
            chunks.append((fit, fit))
            n -= fit
        return chunks

    def serve(self, target, requests, *,
              archive_key: str | None = None) -> list[Recommendation]:
        """Recommend pools for ``requests``; results align with the input.

        ``target`` is any operand the stack produces — the dispatch is on
        its type, so callers never branch:

        - :class:`~repro.core.CandidateSet` — staged on device through the
          LRU cache (keyed by content fingerprint, or ``archive_key`` when
          provided);
        - :class:`DeviceArchive` — already staged, served directly;
        - a live :class:`~repro.stream.RollingDeviceArchive` or its
          version-pinned :class:`~repro.stream.ArchiveSnapshot` — served
          directly, **bypassing** the LRU: a rolling archive re-keys itself
          every collector tick, so routing it through ``cache.get`` would
          re-hash and re-stage (the ingestor manages cache membership via
          ``put``/``invalidate``);
        - a K-sharded archive/snapshot (``repro.shard``) — served directly;
          the engine routes ``is_sharded`` operands to the per-shard
          pipeline, so sharding is invisible beyond the staging step.

        Bucketing, padding, and stats accounting are identical across all
        operand types; ``archive_key`` is only meaningful for the
        ``CandidateSet`` path (a pre-staged operand already carries its key).
        """
        requests = list(requests)
        if not requests:
            return []
        if isinstance(target, CandidateSet):
            archive = self.cache.get(target, key=archive_key)
        elif _is_archive(target):
            if archive_key is not None:
                raise ValueError(
                    "archive_key only applies when serving a CandidateSet; "
                    f"{type(target).__name__} already carries its key")
            archive = target
        else:
            raise TypeError(
                "serve() target must be a CandidateSet or a staged archive "
                f"(DeviceArchive / rolling / snapshot / sharded), got "
                f"{type(target).__name__}")
        out: list[Recommendation] = []
        pos = 0
        chunks = self.plan_chunks(len(requests))
        with tracing.span("repro.serve", rows=len(requests),
                          chunks=len(chunks)):
            for chunk_len, bucket in chunks:
                chunk = requests[pos:pos + chunk_len]
                pos += chunk_len
                out.extend(self.engine.recommend_batch(
                    archive.host, chunk, pad_to=bucket, archive=archive,
                    on_transfer=self._record_transfer))
                with self._stats_lock:
                    self.stats.record(chunk_len, bucket)
        return out

    def _record_transfer(self, d2h: int) -> None:
        with self._stats_lock:
            self.stats.d2h_bytes += d2h

    def serve_archive(self, archive, requests) -> list[Recommendation]:
        """Deprecated alias: ``serve`` now dispatches on the operand type."""
        warnings.warn(
            "BatchServer.serve_archive is deprecated; serve() dispatches on "
            "the operand type — call serve(archive, requests)",
            APIDeprecationWarning, stacklevel=2)
        return self.serve(archive, requests)
