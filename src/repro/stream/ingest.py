"""Collector -> rolling archive -> versioned cache: the live-ingestion loop.

:class:`LiveIngestor` is the glue of the Fig. 3 pipeline's right half.  It
stages the collector's current scoring window once (:meth:`prime`), then
absorbs each collector tick as a single O(K) column append
(:meth:`poll` / :meth:`ingest_tick`) — never re-staging the (K, T) slice,
never recomputing the O(K*T) statistics — and keeps the serve layer's
:class:`~repro.serve.ArchiveCache` membership honest across versions: the
fresh versioned key is ``put`` and the stale one ``invalidate``\\ d, so a
batch routed through the cache can only ever hit the window it asked for.
"""
from __future__ import annotations

import threading

from .. import tracing
from ..cloudsim.collector import DataCollector
from ..core.config import EngineConfig
from ..parallel import compression
from ..serve.archive import ArchiveCache
from .rolling import RollingDeviceArchive


class LiveIngestor:
    """Incrementally feed a :class:`DataCollector`'s archive to serving.

    Parameters
    ----------
    collector : DataCollector
        The live collection loop.  Configure its host ring
        (``CollectorConfig.ring_capacity``) at least as large as ``window``
        so per-tick column reads stay O(K).
    window : int
        Scoring-window length (columns) the served archive holds.
    cache : ArchiveCache, optional
        When given, the ingestor maintains the rolling archive's cache
        entry: every tick inserts the new version and drops the stale one.
    name : str, optional
        Stable archive identity used in the versioned keys (defaults to the
        staged window's content fingerprint).
    shards : int, optional
        When set (or when ``devices`` is given), :meth:`prime` stages a
        K-sharded rolling archive (``repro.shard.ShardedRollingArchive``)
        instead of a single-device ring: one ring per device, every tick
        split across the shards under one version bump.  The rest of the
        loop — cache membership, versioned keys, ``poll`` — is unchanged.
    devices : sequence, optional
        Explicit device list for the shards (default: ``jax.devices()``).
    config : EngineConfig, optional
        When given (and ``cache`` is not), the ingestor builds its own
        :class:`~repro.serve.ArchiveCache` from the config's
        ``cache_capacity`` / ``cache_max_bytes`` — the same single source
        of truth the engine and server draw from.  Passing both ``cache``
        and ``config`` is an error (two sources of truth).  The config's
        ``archive_precision`` / ``archive_headroom`` also become the
        staged ring's storage tier unless ``precision`` overrides them.
    precision : str, optional
        Storage tier of the rolling ring(s): ``"float32"`` (default) /
        ``"bfloat16"`` / ``"int8"`` — see
        ``repro.parallel.compression.ARCHIVE_PRECISIONS``.  An explicit
        value wins over ``config.archive_precision``.
    headroom : float, optional
        int8 clip slack multiplier (``compression.candidate_scales``);
        defaults to ``config.archive_headroom`` or 1.0.
    shard_bounds : sequence of (start, end), optional
        Explicit contiguous shard partition of the candidate axis
        (``repro.shard.check_bounds``), overriding the balanced split.
        Region-sharded serving pins one shard per region this way.  Implies
        sharded staging even without ``shards`` / ``devices``.
    """

    def __init__(self, collector: DataCollector, *, window: int,
                 cache: ArchiveCache | None = None, name: str | None = None,
                 shards: int | None = None, devices=None,
                 config: EngineConfig | None = None,
                 precision: str | None = None,
                 headroom: float | None = None,
                 shard_bounds=None):
        if window < 1:
            raise ValueError("window must be >= 1")
        if shards is not None and shards < 1:
            raise ValueError("shards must be >= 1")
        if shard_bounds is not None:
            shard_bounds = tuple((int(a), int(b)) for a, b in shard_bounds)
        if config is not None:
            if cache is not None:
                raise TypeError("pass either cache= or config=, not both")
            cache = config.build_cache()
        if precision is None:
            precision = (config.archive_precision if config is not None
                         else "float32")
        if headroom is None:
            headroom = (config.archive_headroom if config is not None
                        else 1.0)
        self.collector = collector
        self.window = window
        self.cache = cache
        self.precision = compression.resolve_precision(precision)
        self.headroom = headroom
        self._name = name
        self._shards = shards
        self._devices = devices
        self._shard_bounds = shard_bounds
        self.archive = None   # RollingDeviceArchive | ShardedRollingArchive
        self._ingested = 0                    # collector ticks absorbed

    def prime(self) -> RollingDeviceArchive:
        """Cold-start: stage the current window as the rolling archive.

        The one place the O(K*T) path runs (device transfer + exact moment
        seeding); every later tick is O(K).  Re-priming replaces the archive
        and its cache entry.
        """
        if self.collector.ticks < 1:
            raise ValueError("collector has no completed ticks to stage")
        old_key = self.archive.key if self.archive is not None else None
        cands = self.collector.to_candidate_set(window=self.window)
        if (self._shards is not None or self._devices is not None
                or self._shard_bounds is not None):
            from ..shard import ShardedRollingArchive
            self.archive = ShardedRollingArchive(
                cands, capacity=self.window, name=self._name,
                n_shards=self._shards, devices=self._devices,
                precision=self.precision, headroom=self.headroom,
                bounds=self._shard_bounds)
        else:
            self.archive = RollingDeviceArchive(
                cands, capacity=self.window, name=self._name,
                precision=self.precision, headroom=self.headroom)
        self._ingested = self.collector.ticks
        if self.cache is not None:
            if old_key is not None:
                self.cache.invalidate(old_key)
            self.cache.put(self.archive)
        return self.archive

    @property
    def version(self) -> int:
        return -1 if self.archive is None else self.archive.version

    @property
    def lag(self) -> int:
        """Collector ticks not yet absorbed into the served archive."""
        return self.collector.ticks - self._ingested

    def ingest_tick(self) -> RollingDeviceArchive:
        """Absorb exactly one pending collector tick (O(K))."""
        if self.archive is None:
            raise RuntimeError("prime() the ingestor before ingesting ticks")
        if self.lag <= 0:
            raise RuntimeError("no pending collector tick to ingest")
        # Invalidate the stale key *before* the in-place append: the cache
        # entry is this same mutable object, so dropping it afterwards would
        # leave a window where a lookup under the old version's key serves
        # the new window — the exact staleness bug versioned keys exist to
        # prevent.
        if self.cache is not None:
            self.cache.invalidate(self.archive.key)
        self.archive.append(self.collector.column(self._ingested))
        self._ingested += 1
        self.archive.stale = False
        if self.cache is not None:
            self.cache.put(self.archive)
        return self.archive

    def poll(self) -> int:
        """Absorb every pending collector tick; return how many."""
        n = self.lag
        with tracing.span("repro.ingest.poll", ticks=n):
            for _ in range(n):
                self.ingest_tick()
        return n

    def mark_stale(self) -> None:
        """Flag the served archive as stale (feed stopped delivering).

        The operator's reconcile loop calls this after its bounded
        collect/ingest retries are exhausted: the archive keeps serving —
        old scores beat no scores — but every snapshot taken from here on
        carries ``stale=True`` and drains stamp a ``stale_archive``
        diagnostic on their recommendations.  The next successful
        :meth:`ingest_tick` (or :meth:`prime`) clears the flag.
        """
        if self.archive is not None:
            self.archive.stale = True


class IngestPump:
    """Daemon thread driving collect -> ``LiveIngestor.poll`` on a cadence.

    The collector-push integration: instead of every caller polling the
    ingestor before serving, one pump per region world runs the collection
    cadence — call the ``collect`` hook (one collector tick + market
    advance), then :meth:`LiveIngestor.poll` so the versioned cache key
    advances — in a daemon thread with clean :meth:`start` / :meth:`stop`.

    ``period`` is the *wall-clock* cadence in seconds (simulated worlds run
    much faster than the simulated ``period_min``); ``0`` pumps as fast as
    the loop allows (tests).  Exceptions from the hook or the poll are
    swallowed and counted (``errors``) — a flaky collector tick must not
    kill the pump, exactly like the operator's bounded-retry stance — and
    the first stored exception is kept in ``last_error`` for diagnosis.
    """

    def __init__(self, ingestor: LiveIngestor, collect, *,
                 period: float = 0.0):
        if period < 0:
            raise ValueError("period must be >= 0")
        self.ingestor = ingestor
        self.collect = collect
        self.period = period
        self.errors = 0
        self.last_error: BaseException | None = None
        self.ticks_pumped = 0
        self._stats_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.collect()
                pumped = self.ingestor.poll()
                with self._stats_lock:
                    self.ticks_pumped += pumped
            except Exception as e:  # flaky tick: count, keep pumping
                with self._stats_lock:
                    self.errors += 1
                    if self.last_error is None:
                        self.last_error = e
            if self._stop.wait(self.period):
                return

    def start(self) -> "IngestPump":
        if self.running:
            raise RuntimeError("pump already running")
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        """Signal the loop and join the thread (no-op if never started)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError("ingest pump failed to stop in time")
            self._thread = None

    def __enter__(self) -> "IngestPump":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
