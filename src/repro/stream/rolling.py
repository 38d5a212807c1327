"""Ring-buffer device archive: one-column appends without re-staging.

``serve.DeviceArchive`` treats an archive slice as immutable — correct for
object-store snapshots, but a live collector changes the archive by exactly
one T3 column per tick, and re-staging a (K, T) slice (host->device transfer
+ fingerprint hash + full O(K*T) statistics recompute) to absorb a (K,)
column is the gap this module closes:

- the T3 window lives on device as a **physical ring** of ``capacity``
  column slots; an append writes one slot in place (``jax.Array.at[...]``
  with buffer donation — no copy of the (K, C) buffer, O(K) bytes move);
- the Eq. 3 statistics ride along via the O(K) rank-1 update kernel
  (``repro.kernels.stats_update``) instead of an O(K*T) recompute, so the
  streaming scoring stage (``score_impl="tiled"``) never touches the window
  matrix at all;
- every append bumps ``version`` and therefore :attr:`key` — the versioned
  fingerprint the :class:`~repro.serve.ArchiveCache` entries are keyed by —
  so a stale cache entry *misses* instead of silently serving a window it no
  longer describes.

The logical window (oldest..newest, the orientation ``candidate_stats`` and
the dense scoring path expect) is a rotation of the physical slots; it is
only materialized (device-side gather, no host transfer) when something
actually asks for :attr:`t3` — the dense scoring path or a parity check —
and the gather is memoised per version.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..core import scoring
from ..core.types import CandidateSet
from ..kernels import stats_update as stats_update_lib
from ..parallel import compression


@jax.jit
def _read_col(buf, slot):
    return jax.lax.dynamic_index_in_dim(buf, slot, axis=1, keepdims=False)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("backend", "interpret"))
def _append_step(buf, moments, col, y_old, slot, new_start, length, evict,
                 *, backend=None, interpret=None):
    """One tick: donated slot write + O(K) moments update.

    ``buf`` (the (K, C) ring) and the moment accumulators are donated — the
    update is genuinely in place, nothing (K, C)-sized is copied or
    transferred.  The evicted column ``y_old`` must be materialized *before*
    this call (:func:`_read_col`): a read of the donated buffer scheduled
    before the in-place write would make XLA fall back to copying the whole
    ring (measured: ~200x the donated cost at K=32768, T=1008 on CPU).
    Reading ``y_first`` out of the post-write buffer is safe.
    """
    new_buf = buf.at[:, slot].set(col)
    y_first = jax.lax.dynamic_index_in_dim(new_buf, new_start, axis=1,
                                           keepdims=False)
    moments, stats = stats_update_lib.stats_update(
        moments, col, y_old, y_first, col, length, evict,
        backend=backend, interpret=interpret)
    return new_buf, moments, stats


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("precision", "backend", "interpret"))
def _append_step_q(buf, moments, col, y_old, scale, slot, new_start, length,
                   evict, *, precision, backend=None, interpret=None):
    """Quantized-tier tick: donated slot write + fused O(K) update.

    ``col`` arrives as the int8 tier stores it: codes made on the host by
    ``compression.quantize_column``, so they equal a staged window's bit
    for bit.  On the bf16 tier it is the float32 column, cast here.  Only
    stored values touch the ring, and the moments are updated with them
    (codes via the fused dequantize-and-update ``scale`` path of the stats
    kernel, bf16 via its exact f32 cast), so the streamed statistics track
    ``candidate_stats`` of the dequantized window, the tier's ground
    truth.  Donation discipline as :func:`_append_step`: ``y_old`` is read
    in a prior dispatch.
    """
    codes = col if precision == "int8" else col.astype(jnp.bfloat16)
    new_buf = buf.at[:, slot].set(codes)
    y_first = jax.lax.dynamic_index_in_dim(new_buf, new_start, axis=1,
                                           keepdims=False)
    moments, stats = stats_update_lib.stats_update(
        moments, codes, y_old, y_first, codes, length, evict,
        scale=scale if precision == "int8" else None,
        backend=backend, interpret=interpret)
    return new_buf, moments, stats


@dataclass(frozen=True)
class ArchiveSnapshot:
    """An immutable, version-pinned view of a :class:`RollingDeviceArchive`.

    This is what the admission queue hands to a drain: the parent archive
    may absorb further collector ticks (donating its ring buffer away) while
    a batch is in flight, but a snapshot only references arrays that are
    never donated — the catalog columns and the already-derived statistics —
    so it stays valid and internally consistent across version bumps.

    Snapshots serve the **tiled** scoring stage (the streaming serve path);
    they deliberately carry no window matrix — the engine's ``auto``/
    ``dense`` resolution falls back to tiled for them
    (``dense_capable = False``), and direct :attr:`t3` access raises rather
    than silently re-staging the O(K*T) materialization the streaming path
    exists to avoid.
    """

    key: str
    version: int
    host: CandidateSet
    prices: jax.Array
    vcpus: jax.Array
    memory_gb: jax.Array
    stats: scoring.CandidateStats
    window_len: int
    #: storage tier of the parent ring ("float32" / "bfloat16" / "int8") —
    #: snapshots carry no window, but parity/error-bound consumers need to
    #: know which tier produced the pinned statistics, and the key suffix
    #: must keep tiers from colliding in the ArchiveCache.
    precision: str = "float32"
    #: the parent's per-candidate quantisation step (None on the float32
    #: tier) — never donated, so the reference stays valid across ticks.
    scale: jax.Array | None = None
    #: True when the parent archive was marked stale at snapshot time (its
    #: feed stopped delivering ticks — see ``LiveIngestor.mark_stale``).
    #: Recommendations served off a stale snapshot carry a
    #: ``stale_archive`` diagnostic so consumers know the scores describe an
    #: old market, not the current one.
    stale: bool = False

    #: tells the engine to keep the scoring stage tiled even when the
    #: auto threshold would pick dense at this K (no window to re-reduce)
    dense_capable = False

    def score_stats(self) -> scoring.CandidateStats:
        return self.stats

    @property
    def t3(self):
        raise RuntimeError(
            "ArchiveSnapshot has no window matrix: it pins a past archive "
            "version for in-flight batches and serves the tiled scoring "
            "stage only (score_impl='tiled'/'auto' at streaming K).")

    @property
    def t3_operand(self):
        # Inert stand-in for the fused dispatch's dead t3 operand (see
        # DeviceArchive.t3_operand): stable (K,) shape, already on device.
        return self.stats.area

    @property
    def nbytes(self) -> int:
        n = sum(int(a.nbytes) for a in
                (self.prices, self.vcpus, self.memory_gb, *self.stats))
        if self.scale is not None:
            n += int(self.scale.nbytes)
        return n

    def __len__(self) -> int:
        return len(self.host)


class RollingDeviceArchive:
    """A device-staged candidate archive that absorbs one-column ticks.

    Drop-in for :class:`~repro.serve.DeviceArchive` everywhere the engine
    and serve layers look (``prices`` / ``vcpus`` / ``memory_gb`` / ``t3`` /
    ``t3_operand`` / ``score_stats()`` / ``key`` / ``host`` / ``nbytes``),
    plus the streaming surface: :meth:`append`, :meth:`snapshot`, and a
    ``version`` that changes with every append.

    ``host`` keeps the *stage-time* :class:`CandidateSet` for filter-mask
    construction and result materialisation — the catalog columns (names,
    regions, vcpus, prices, ...) are exactly what requests consume and they
    do not change per tick; ``host.t3`` is a cold snapshot, use
    :meth:`materialize` for the live window.
    """

    def __init__(self, cands: CandidateSet, *, capacity: int | None = None,
                 name: str | None = None, device=None,
                 precision: str = "float32", headroom: float = 1.0):
        self.precision = compression.resolve_precision(precision)
        t3 = np.asarray(cands.t3)
        K, T = t3.shape
        capacity = T if capacity is None else int(capacity)
        if capacity < T:
            raise ValueError(f"capacity {capacity} < staged window {T}")
        self.host = cands
        self.name = name if name is not None else cands.fingerprint()
        self.capacity = capacity
        # ``device`` pins the ring + catalog columns (and the donated append
        # dispatches that consume them) to one jax device — the K-sharded
        # rolling archive stages one slice per device this way.
        put = lambda a: jax.device_put(jnp.asarray(a, jnp.float32),  # noqa: E731
                                       device)
        self.prices = put(cands.prices)
        self.vcpus = put(cands.vcpus)
        self.memory_gb = put(cands.memory_gb)
        # Quantised tiers: per-candidate step frozen at staging (``headroom``
        # buys clip slack for live columns beyond the seed's range), codes
        # staged chunk-by-chunk — no second full-window host copy at any K.
        host_scale = compression.candidate_scales(
            t3, self.precision, headroom=headroom)
        quantized = self.precision != "float32"
        self.scale = put(host_scale) if quantized else None
        self._host_scale = host_scale       # ticks are encoded on the host
        self._clips = 0
        # physical ring: window in slots [0, T), zero-filled tail, cursor at T
        codes = compression.quantize_window(t3, host_scale, self.precision)
        buf = np.zeros((K, capacity), codes.dtype)
        buf[:, :T] = codes
        self._buf = jax.device_put(
            jnp.asarray(buf),  # spotlint: disable=SPL002 (codes dtype)
            device)
        self._pos = T % capacity
        self._len = T
        self.version = 0
        # Seed the moments from the *stored* window (codes decoded with the
        # exact dequantize multiply / bf16 cast): the tier's ground truth is
        # the dequantized window, and the streamed statistics must track it,
        # not the lossy pre-quantisation seed.
        moments = stats_update_lib.moments_from_window(
            codes, scale=host_scale if self.precision == "int8" else None)
        del codes
        # colocate the accumulators with the ring: the donated append
        # dispatch consumes both, and jit rejects split-device operands
        self._moments = stats_update_lib.StreamMoments(
            *(jax.device_put(m, device) for m in moments))
        self._stats: scoring.CandidateStats | None = None
        self._t3_logical = None
        self.appends = 0
        # ``append`` publishes the cursor, length, statistics and version
        # under this lock, and ``snapshot`` / ``score_stats`` read them
        # under it, so a snapshot's version is always its statistics'.
        self._lock = threading.Lock()
        #: staleness flag, owned by the feed (``LiveIngestor`` sets it when
        #: its collector stops delivering, clears it on the next successful
        #: tick).  Mutating it does **not** bump :attr:`version` — the
        #: window really is unchanged; the flag rides into snapshots and the
        #: serve layer stamps it on recommendation diagnostics.
        self.stale = False

    # -- identity ----------------------------------------------------------

    @property
    def key(self) -> str:
        """Versioned fingerprint: changes with every appended column.

        Quantised tiers get a ``#<precision>`` suffix so two archives staged
        from the same candidate set at different precisions can never
        collide in the :class:`~repro.serve.ArchiveCache`.
        """
        key = f"{self.name}@v{self.version}"
        if self.precision != "float32":
            key += f"#{self.precision}"
        return key

    @property
    def clipped_samples(self) -> int:
        """Samples clipped to the int8 code range since staging (0 on the
        bf16/float32 tiers).  The documented error bound assumes unclipped
        storage; a non-zero count voids it and callers must surface that."""
        return self._clips

    @property
    def window_len(self) -> int:
        return self._len

    @property
    def _start(self) -> int:
        return (self._pos - self._len) % self.capacity

    def __len__(self) -> int:
        return len(self.host)

    # -- streaming ---------------------------------------------------------

    def append(self, column) -> "RollingDeviceArchive":
        """Absorb one collector tick: O(K) work, no (K, T) copy or transfer.

        Encodes ``column`` on the host (int8 codes by
        ``compression.quantize_column``), writes it into the ring slot under
        the cursor (donated in-place update), rank-1-updates the cached
        Eq. 3 statistics, bumps :attr:`version`, and drops the memoised
        logical window.  The new state is published under the lock
        :meth:`snapshot` takes.  Returns ``self`` for chaining.
        """
        with tracing.span("repro.ingest.append") as span:
            col, n_clip = self._encode(column)
            span.set_metadata(clipped=n_clip, h2d_bytes=col.nbytes)
            with self._lock:
                step, operands, statics = self._append_dispatch(col)
                self._buf, self._moments, stats = step(*operands, **statics)
                self._pos = (self._pos + 1) % self.capacity
                self._len = min(self._len + 1, self.capacity)
                self._stats = stats
                self._t3_logical = None
                self._clips += n_clip
                self.version += 1
                self.appends += 1
        return self

    def _encode(self, column):
        """``(column as uploaded, samples clipped)``: int8 codes made on the
        host, the float32 column on the other tiers."""
        col = np.asarray(column, np.float32)
        if col.shape != (len(self.host),):
            raise ValueError(
                f"column shape {col.shape} != ({len(self.host)},)")
        if self.precision == "int8":
            return compression.quantize_column(col, self._host_scale, "int8")
        return col, 0

    def _append_dispatch(self, col):
        """``(jitted tick step, operands, static kwargs)`` that
        :meth:`append` runs to absorb ``col`` (as :meth:`_encode` gives
        it).  Reads the evicted column (its own dispatch) but changes
        nothing."""
        col = jnp.asarray(col)  # spotlint: disable=SPL002 (codes dtype)
        evict = self._len == self.capacity
        new_len = self._len if evict else self._len + 1
        slot = self._pos
        new_start = (slot + 1) % self.capacity if evict else \
            (slot + 1 - new_len) % self.capacity
        y_old = _read_col(self._buf, jnp.int32(slot))
        tail = (jnp.int32(slot), jnp.int32(new_start), jnp.float32(new_len),
                jnp.asarray(evict, bool))
        if self.precision == "float32":
            return (_append_step,
                    (self._buf, self._moments, col, y_old, *tail), {})
        return (_append_step_q,
                (self._buf, self._moments, col, y_old, self.scale, *tail),
                {"precision": self.precision})

    def snapshot(self) -> ArchiveSnapshot:
        """Pin the current version for an in-flight batch (tiled stage).

        Read under the lock :meth:`append` publishes under, so the stamped
        version is always the version of the statistics carried."""
        with self._lock:
            return ArchiveSnapshot(
                key=self.key, version=self.version, host=self.host,
                prices=self.prices, vcpus=self.vcpus,
                memory_gb=self.memory_gb, stats=self._current_stats(),
                window_len=self._len, precision=self.precision,
                scale=self.scale, stale=self.stale)

    # -- engine-facing surface --------------------------------------------

    def score_stats(self) -> scoring.CandidateStats:
        """Eq. 3 statistics of the current window, O(K)-maintained.

        Seeded exactly from the staged window; after that, every value comes
        out of the rank-1 update kernel — ``candidate_stats`` never runs
        again on this archive.
        """
        with self._lock:
            return self._current_stats()

    def _current_stats(self) -> scoring.CandidateStats:
        """:meth:`score_stats` for a caller that holds the lock."""
        if self._stats is None:     # version 0: derive from the seed moments
            m = self._moments
            y_first = self._decode_col(self._buf[:, self._start])
            y_last = self._decode_col(
                self._buf[:, (self._pos - 1) % self.capacity])
            self._stats = scoring.stats_from_moments(
                m.s0 + m.s0c, m.s1 + m.s1c, m.q + m.qc, y_first, y_last,
                jnp.float32(self._len), m.ref)
        return self._stats

    def _decode_col(self, col):
        """Stored ring column -> float32 value (the dequantize multiply on
        the int8 tier, an exact cast on bf16/f32)."""
        col = col.astype(jnp.float32)
        return col * self.scale if self.precision == "int8" else col

    @property
    def t3(self) -> jax.Array:
        """The logical (K, window_len) T3 window, oldest..newest.

        Materialized by a device-side gather (no host round-trip) and
        memoised per version.  Only the dense scoring path and parity
        checks need this — the streaming serve path scores from
        :meth:`score_stats` and never calls it.
        """
        if self._t3_logical is None:
            order = (self._start + np.arange(self._len)) % self.capacity
            stored = jnp.take(self._buf, jnp.asarray(order, jnp.int32), axis=1)
            self._t3_logical = compression.dequantize_window(
                stored, self.scale, self.precision) \
                if self.precision != "float32" else stored
        return self._t3_logical

    @property
    def t3_operand(self):
        """Inert t3 stand-in for stats-backed tiled dispatches (see
        ``DeviceArchive.t3_operand``): a (K,)-shaped statistics array that
        is already on device — never the ring itself, which is donated away
        on every append and must not leak into a dispatch signature."""
        return self.score_stats().area

    def materialize(self) -> np.ndarray:
        """Host copy of the logical window (parity tests, re-staging)."""
        return np.asarray(self.t3)

    @property
    def nbytes(self) -> int:
        """Every resident device byte of this archive: ring + catalog
        columns + moment pairs + scale vector + whatever is memoised right
        now (statistics, logical-window gather) — the number the
        ``ArchiveCache`` budget and the memory benchmark charge for."""
        n = sum(int(a.nbytes) for a in
                (self._buf, self.prices, self.vcpus, self.memory_gb))
        n += self._moments.nbytes
        if self.scale is not None:
            n += int(self.scale.nbytes)
        if self._stats is not None:
            n += sum(int(a.nbytes) for a in self._stats)
        if self._t3_logical is not None:
            n += int(self._t3_logical.nbytes)
        return n
