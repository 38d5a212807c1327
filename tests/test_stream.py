"""Live-ingestion subsystem (``repro.stream``): rolling archives, versioned
cache invalidation, the collector -> engine loop, and async admission.

The load-bearing contract: after any number of streamed ticks,
``recommend_batch`` against the rolling archive returns pools bit-identical
to a cold re-stage of the materialized window — the O(K) incremental path
may not drift the service's decisions, at any version.
"""
import time

import numpy as np
import pytest

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import EngineConfig, RecommendationEngine, ResourceRequest
from repro.core import scoring
from repro.serve import ArchiveCache, BatchServer, DeviceArchive
from repro.stream import (AdmissionQueue, ArchiveSnapshot, IngestPump,
                          LiveIngestor,
                          RollingDeviceArchive)

from test_serve_batch import synth_candidates

RTOL = 1e-5
ATOL = 1e-4
WINDOW = 10


def _requests(cands):
    return [
        ResourceRequest(cpus=128.0),
        ResourceRequest(memory_gb=256.0, weight=0.8),
        ResourceRequest(cpus=96.0, weight=0.3, lam=0.25),
        ResourceRequest(cpus=64.0, regions=[str(cands.regions[0])]),
        ResourceRequest(cpus=200.0, max_types=2),
    ]


def _assert_same_pools(a, b):
    assert list(a.names) == list(b.names)
    assert list(a.regions) == list(b.regions)
    assert list(a.azs) == list(b.azs)
    np.testing.assert_array_equal(a.counts, b.counts)
    assert a.hourly_cost == b.hourly_cost
    np.testing.assert_allclose(a.combined, b.combined, rtol=RTOL, atol=ATOL)


def _collector(seed=3, n_targets=36, cycles=WINDOW, ring=32):
    mkt = SpotMarket(Catalog(seed=seed, n_regions=2), seed=seed)
    svc = SPSQueryService(mkt, n_accounts=3000)
    step = max(len(mkt.pool_keys) // n_targets, 1)
    targets = [(t.name, r, az) for (t, r, az) in mkt.pool_keys[::step]][:n_targets]
    col = DataCollector(svc, targets,
                        CollectorConfig(ring_capacity=ring))
    col.run(cycles)
    return col


# ---------------------------------------------------------------------------
# RollingDeviceArchive
# ---------------------------------------------------------------------------

def test_rolling_window_semantics():
    cands = synth_candidates(seed=1, K=17, T=6)
    arch = RollingDeviceArchive(cands, capacity=6, name="ring")
    rng = np.random.default_rng(0)
    host = np.asarray(cands.t3, np.float32)
    assert arch.key == "ring@v0" and arch.window_len == 6
    for v in range(1, 9):                      # wraps the ring twice
        col = rng.uniform(0, 50, 17).astype(np.float32)
        host = np.concatenate([host[:, 1:], col[:, None]], axis=1)
        arch.append(col)
        assert arch.key == f"ring@v{v}"
        np.testing.assert_array_equal(arch.materialize(), host)


def test_rolling_growing_phase():
    cands = synth_candidates(seed=2, K=9, T=3)
    arch = RollingDeviceArchive(cands, capacity=5)
    host = np.asarray(cands.t3, np.float32)
    for i in range(4):                          # grows 3 -> 5, then slides
        col = np.full(9, float(i), np.float32)
        host = (np.concatenate([host, col[:, None]], axis=1)
                if host.shape[1] < 5 else
                np.concatenate([host[:, 1:], col[:, None]], axis=1))
        arch.append(col)
        assert arch.window_len == host.shape[1]
        np.testing.assert_array_equal(arch.materialize(), host)
        ref = scoring.candidate_stats(host)
        got = arch.score_stats()
        for name, x, y in zip(("area", "slope", "std"), got, ref):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=RTOL, atol=ATOL, err_msg=name)


def test_rolling_validation():
    cands = synth_candidates(seed=3, K=4, T=8)
    with pytest.raises(ValueError, match="capacity"):
        RollingDeviceArchive(cands, capacity=4)
    arch = RollingDeviceArchive(cands)
    with pytest.raises(ValueError, match="column shape"):
        arch.append(np.zeros(5))


def test_rolling_stats_track_recompute():
    cands = synth_candidates(seed=4, K=33, T=12)
    arch = RollingDeviceArchive(cands)
    rng = np.random.default_rng(7)
    for _ in range(30):
        arch.append(rng.uniform(0, 50, 33))
    ref = scoring.candidate_stats(arch.materialize())
    for name, x, y in zip(("area", "slope", "std"), arch.score_stats(), ref):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=RTOL, atol=ATOL, err_msg=name)


@pytest.mark.parametrize("score_impl", ["tiled", "dense"])
def test_rolling_archive_serves_like_cold_restage(score_impl):
    """recommend_batch(rolling archive) == cold re-stage, both impls."""
    cands = synth_candidates(seed=5, K=48, T=WINDOW)
    arch = RollingDeviceArchive(cands)
    engine = RecommendationEngine(EngineConfig(score_impl=score_impl))
    rng = np.random.default_rng(1)
    reqs = _requests(cands)
    for _ in range(5):
        arch.append(rng.uniform(0, 50, 48))
        live = engine.recommend_batch(arch.host, reqs, archive=arch)
        cold_set = synth_candidates(seed=5, K=48, T=WINDOW)
        cold_set.t3 = arch.materialize().astype(np.float64)
        cold = engine.recommend_batch(cold_set, reqs,
                                      archive=DeviceArchive.stage(cold_set))
        for a, b in zip(live, cold):
            _assert_same_pools(a, b)


def test_snapshot_survives_version_bumps():
    """A snapshot pins its version: the parent may absorb further ticks
    (donating its ring away) while the snapshot keeps serving."""
    cands = synth_candidates(seed=6, K=40, T=WINDOW)
    arch = RollingDeviceArchive(cands, name="pin")
    engine = RecommendationEngine(EngineConfig(score_impl="tiled"))
    reqs = _requests(cands)
    rng = np.random.default_rng(2)
    arch.append(rng.uniform(0, 50, 40))
    snap = arch.snapshot()
    want = engine.recommend_batch(snap.host, reqs, archive=snap)
    for _ in range(3):                      # bump versions under the snapshot
        arch.append(rng.uniform(0, 50, 40))
    assert snap.version == 1 and arch.version == 4
    assert snap.key != arch.key
    got = engine.recommend_batch(snap.host, reqs, archive=snap)
    for a, b in zip(got, want):
        _assert_same_pools(a, b)
        np.testing.assert_array_equal(a.combined, b.combined)
    with pytest.raises(RuntimeError, match="tiled scoring stage only"):
        _ = snap.t3
    # an auto-engine at small K (auto -> dense) must fall back to tiled for
    # the window-less snapshot instead of touching .t3
    auto = RecommendationEngine()
    got_auto = auto.recommend_batch(snap.host, reqs, archive=snap)
    for a, b in zip(got_auto, want):
        _assert_same_pools(a, b)


def test_snapshot_is_cheap():
    cands = synth_candidates(seed=7, K=16, T=WINDOW)
    arch = RollingDeviceArchive(cands)
    snap = arch.snapshot()
    assert isinstance(snap, ArchiveSnapshot)
    assert snap.nbytes < arch.nbytes        # no window matrix aboard
    assert len(snap) == len(arch)


# ---------------------------------------------------------------------------
# versioned cache invalidation
# ---------------------------------------------------------------------------

def test_cache_versioned_put_invalidate():
    cands = synth_candidates(seed=8, K=12, T=WINDOW)
    cache = ArchiveCache(capacity=3)
    arch = RollingDeviceArchive(cands, name="live")
    cache.put(arch)
    assert "live@v0" in cache and len(cache) == 1
    stale = arch.key
    arch.append(np.zeros(12))
    # the rolling archive re-keyed itself; the old entry must be droppable
    assert cache.invalidate(stale) and stale not in cache
    cache.put(arch)
    assert "live@v1" in cache
    assert not cache.invalidate("live@v0")   # already gone


# ---------------------------------------------------------------------------
# collector fast path feeds + LiveIngestor
# ---------------------------------------------------------------------------

def test_ingestor_loop_bit_identical_to_cold_restaging():
    """The headline acceptance: run the collector, stream every tick, and at
    every version the served pools match a cold re-stage bit-for-bit."""
    col = _collector()
    cache = ArchiveCache(capacity=4)
    ing = LiveIngestor(col, window=WINDOW, cache=cache, name="live")
    arch = ing.prime()
    engine = RecommendationEngine(EngineConfig(score_impl="tiled"))
    server = BatchServer(engine, bucket_sizes=(1, 4, 8))
    reqs = _requests(col.to_candidate_set(window=WINDOW))
    for cycle in range(6):
        col.run(1)
        stale = arch.key
        assert ing.lag == 1
        ing.poll()
        assert ing.lag == 0
        assert arch.key in cache and stale not in cache
        live = server.serve(arch, reqs)
        cold_set = col.to_candidate_set(window=WINDOW)
        np.testing.assert_array_equal(
            arch.materialize(), np.asarray(cold_set.t3, np.float32))
        cold = engine.recommend_batch(
            cold_set, reqs, archive=DeviceArchive.stage(cold_set))
        for a, b in zip(live, cold):
            _assert_same_pools(a, b)


def test_ingestor_validation():
    col = _collector(cycles=0)
    ing = LiveIngestor(col, window=WINDOW)
    with pytest.raises(ValueError, match="no completed ticks"):
        ing.prime()
    with pytest.raises(RuntimeError, match="prime"):
        ing.ingest_tick()
    col.run(2)
    ing.prime()
    with pytest.raises(RuntimeError, match="no pending"):
        ing.ingest_tick()
    with pytest.raises(ValueError, match="window"):
        LiveIngestor(col, window=0)


def test_ingestor_catches_up_multiple_ticks():
    col = _collector()
    ing = LiveIngestor(col, window=WINDOW, name="burst")
    ing.prime()
    col.run(3)                               # fall behind by three ticks
    assert ing.lag == 3
    assert ing.poll() == 3
    np.testing.assert_array_equal(
        ing.archive.materialize(),
        np.asarray(col.to_candidate_set(window=WINDOW).t3, np.float32))


# ---------------------------------------------------------------------------
# async admission
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture()
def admission():
    col = _collector()
    ing = LiveIngestor(col, window=WINDOW, name="adm")
    ing.prime()
    server = BatchServer(RecommendationEngine(EngineConfig(score_impl="tiled")),
                         bucket_sizes=(1, 4, 8))
    clock = FakeClock()
    q = AdmissionQueue(server, lambda: ing.archive, max_wait_s=1.0,
                       max_pending=4, clock=clock)
    return col, ing, q, clock


def test_admission_batches_by_deadline_not_call_site(admission):
    col, ing, q, clock = admission
    cands = col.to_candidate_set(window=WINDOW)
    t1 = q.submit(ResourceRequest(cpus=64.0))
    clock.now += 0.5
    t2 = q.submit(ResourceRequest(cpus=128.0))
    assert q.pump() == 0 and not t1.done          # nothing due yet
    clock.now += 0.6                              # t1's deadline passes
    assert q.due()
    assert q.pump() == 2                          # t2 coalesces into the drain
    assert t1.done and t2.done
    assert q.stats.drains == 1 and q.stats.coalesced == 1
    # results match the engine run directly
    engine = q.server.engine
    want = engine.recommend_batch(cands, [t1.request, t2.request])
    _assert_same_pools(t1.result(), want[0])
    _assert_same_pools(t2.result(), want[1])
    assert t1.result().diagnostics["archive_version"] == ing.version


def test_admission_full_queue_triggers_immediate_drain(admission):
    _, _, q, clock = admission
    tickets = [q.submit(ResourceRequest(cpus=float(8 * (i + 1))))
               for i in range(4)]                 # max_pending == 4
    assert q.due()                                # no deadline needed
    assert q.pump() == 4
    assert all(t.done for t in tickets)
    assert q.stats.versions == {"adm@v0": 4}


def test_admission_drains_across_version_bumps(admission):
    """Tickets admitted under v0 serve against the drain-time snapshot, and
    a mid-flight collector tick never splits a batch across versions."""
    col, ing, q, clock = admission
    t1 = q.submit(ResourceRequest(cpus=64.0))
    col.run(1)
    ing.poll()                                    # bump to v1 while queued
    clock.now += 2.0
    t2 = q.submit(ResourceRequest(cpus=96.0))     # joins the same drain
    assert q.pump() == 2
    v1 = t1.result().diagnostics["archive_version"]
    v2 = t2.result().diagnostics["archive_version"]
    assert v1 == v2 == 1
    assert t1.result().diagnostics["archive_key"] == "adm@v1"


def test_admission_sync_result_force_drains(admission):
    _, _, q, _ = admission
    t = q.submit(ResourceRequest(cpus=32.0))
    assert not t.done
    rec = t.result()                              # no worker: force drain
    assert t.done and rec.hourly_cost > 0
    assert q.stats.drains == 1


def test_forced_drain_does_not_count_coalesced(admission):
    """A force drain takes everything by definition — its not-yet-due
    tickets must not inflate the arrival-batching ``coalesced`` counter
    (the sync ``Ticket.result`` fallback used to count every ticket)."""
    _, _, q, clock = admission
    t1 = q.submit(ResourceRequest(cpus=32.0))     # deadline = now + 1.0
    t2 = q.submit(ResourceRequest(cpus=64.0))
    t1.result()                                   # sync fallback: force drain
    assert t1.done and t2.done
    assert q.stats.coalesced == 0
    assert q.stats.forced_drains == 1 and q.stats.drains == 1
    # a genuinely due drain with a late arrival still counts coalescing
    t3 = q.submit(ResourceRequest(cpus=16.0))
    clock.now += 0.5
    t4 = q.submit(ResourceRequest(cpus=8.0))
    clock.now += 0.6                              # t3 due, t4 rides along
    assert q.pump() == 2
    assert q.stats.coalesced == 1 and q.stats.forced_drains == 1
    assert q.stats.served == 4 == q.stats.submitted


def test_admission_max_pending_validation(admission):
    col, ing, q, clock = admission
    with pytest.raises(ValueError, match="max_pending"):
        AdmissionQueue(q.server, lambda: ing.archive, max_pending=0)
    with pytest.raises(ValueError, match="max_pending"):
        AdmissionQueue(q.server, lambda: ing.archive, max_pending=-3)
    # default: the server's largest bucket
    q2 = AdmissionQueue(q.server, lambda: ing.archive)
    assert q2.max_pending == max(q.server.bucket_sizes)


def test_admission_error_fails_the_ticket(admission):
    _, _, q, clock = admission
    t = q.submit(ResourceRequest(cpus=8.0, regions=["nowhere-42"]))
    clock.now += 5.0
    # the failing dispatch resolves the ticket and returns normally — the
    # error surfaces on Ticket.result, not out of the drain loop
    assert q.drain() == 1
    with pytest.raises(ValueError, match="no candidates"):
        t.result()
    assert q.stats.failed_drains == 1 and q.stats.failed == 1
    assert q.stats.submitted == q.stats.served + q.stats.shed + q.stats.failed


def test_admission_source_failure_fails_tickets_not_hangs():
    """An archive_source failure mid-drain must resolve the popped tickets
    with the error — not strand them undone forever."""
    server = BatchServer(RecommendationEngine(), bucket_sizes=(1, 4))
    q = AdmissionQueue(server, lambda: None, max_wait_s=0.0)
    t = q.submit(ResourceRequest(cpus=16.0))
    assert q.drain(force=True) == 1
    assert t.done and q.pending == 0
    with pytest.raises(RuntimeError, match="no archive"):
        t.result(timeout=1.0)
    assert q.stats.failed_drains == 1 and q.stats.forced_drains == 1


def test_ingestor_invalidates_stale_key_before_mutating():
    """The cache must never map an old version's key to the already-advanced
    archive object, even transiently: the stale key goes before append."""
    col = _collector()

    class TracingCache(ArchiveCache):
        def invalidate(self, key):
            trace.append(("invalidate", key))
            return super().invalidate(key)

        def put(self, entry):
            trace.append(("put", entry.key))
            super().put(entry)

    trace = []
    cache = TracingCache(capacity=4)
    ing = LiveIngestor(col, window=WINDOW, cache=cache, name="order")
    ing.prime()
    col.run(1)
    trace.clear()
    ing.poll()
    assert trace == [("invalidate", "order@v0"), ("put", "order@v1")]


def test_threaded_admission_resolves_every_ticket_exactly_once(monkeypatch,
                                                               racecheck):
    """Wall-clock worker + concurrent submitters: every ticket resolves
    exactly once, and the stats ledgers balance across the admission queue
    and the (now lock-guarded) BatchServer counters."""
    import threading

    from repro.stream.admission import Ticket

    resolve_counts: dict[int, int] = {}
    count_lock = threading.Lock()
    orig_resolve = Ticket._resolve

    def counting_resolve(self, result=None, error=None):
        with count_lock:
            resolve_counts[id(self)] = resolve_counts.get(id(self), 0) + 1
        orig_resolve(self, result=result, error=error)

    monkeypatch.setattr(Ticket, "_resolve", counting_resolve)

    col = _collector()
    ing = LiveIngestor(col, window=WINDOW, name="mt")
    ing.prime()
    server = BatchServer(RecommendationEngine(EngineConfig(score_impl="tiled")),
                         bucket_sizes=(1, 4, 8))
    from repro.analysis.racecheck import (instrument_admission_queue,
                                          instrument_server)
    q = AdmissionQueue(server, lambda: ing.archive, max_wait_s=0.005)
    instrument_server(racecheck, server)
    instrument_admission_queue(racecheck, q)
    q.start()
    n_threads, per_thread = 4, 6
    tickets: list = []
    tickets_lock = threading.Lock()

    def submitter(i):
        for j in range(per_thread):
            t = q.submit(ResourceRequest(cpus=float(8 * (i + j + 1))))
            with tickets_lock:
                tickets.append(t)

    try:
        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        recs = [t.result(timeout=60.0) for t in tickets]
    finally:
        q.stop()
    n = n_threads * per_thread
    assert len(tickets) == n
    assert all(t.done for t in tickets)
    assert all(r.hourly_cost > 0 for r in recs)
    # exactly-once resolution, no lost or double drains
    assert len(resolve_counts) == n
    assert all(c == 1 for c in resolve_counts.values())
    # ledgers balance: queue stats vs server stats
    assert q.stats.submitted == n and q.stats.served == n
    assert sum(q.stats.versions.values()) == n
    assert server.stats.requests == n
    assert sum(server.stats.bucket_counts.values()) == server.stats.batches
    assert q.pending == 0 and not q.running


def test_snapshot_carries_the_statistics_of_its_version(racecheck):
    """One thread appends while another takes snapshots: every snapshot's
    statistics are those its version published.  Publishing is slowed
    between the statistics and the version bump, so a snapshot read
    outside the append's lock would be caught between the two."""
    import threading

    from repro.analysis.racecheck import instrument_rolling_archive

    class SlowPublish(RollingDeviceArchive):
        def __setattr__(self, attr, value):
            super().__setattr__(attr, value)
            if attr == "_stats" and value is not None:
                time.sleep(0.002)

    rng = np.random.default_rng(4)
    arch = SlowPublish(synth_candidates(seed=9, K=32, T=8), name="race")
    instrument_rolling_archive(racecheck, arch)
    published = {0: arch.score_stats()}
    snaps: list = []
    done = threading.Event()

    def snapshotter():
        while not done.is_set():
            snaps.append(arch.snapshot())

    th = threading.Thread(target=snapshotter)
    th.start()
    try:
        for v in range(1, 31):
            arch.append(rng.uniform(0.0, 50.0, 32))
            published[v] = arch.score_stats()
    finally:
        done.set()
        th.join(timeout=30.0)
    assert not th.is_alive()
    assert len({s.version for s in snaps}) > 10
    for snap in snaps:
        assert snap.key == f"race@v{snap.version}"
        for got, want in zip(snap.stats, published[snap.version]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_admission_background_worker_smoke():
    """Wall-clock mode: the daemon thread drains on its own."""
    col = _collector()
    ing = LiveIngestor(col, window=WINDOW, name="bg")
    ing.prime()
    server = BatchServer(RecommendationEngine(EngineConfig(score_impl="tiled")),
                         bucket_sizes=(1, 4, 8))
    q = AdmissionQueue(server, lambda: ing.archive, max_wait_s=0.01).start()
    try:
        tickets = [q.submit(ResourceRequest(cpus=float(16 * (i + 1))))
                   for i in range(3)]
        recs = [t.result(timeout=30.0) for t in tickets]
        assert all(r.hourly_cost > 0 for r in recs)
        assert q.stats.served == 3
    finally:
        q.stop()
    assert not q.running


# ---------------------------------------------------------------------------
# IngestPump: collector-push, no caller polling
# ---------------------------------------------------------------------------

def _pump_world(cycles=WINDOW):
    col = _collector(cycles=cycles)
    cache = ArchiveCache(capacity=4)
    ing = LiveIngestor(col, window=WINDOW, cache=cache, name="pumped")
    ing.prime()

    def collect():
        col.collect_once()
        col.market.advance(col.market.now + col.cfg.period_min)

    return col, cache, ing, collect


def test_ingest_pump_advances_versions_without_polling():
    """Versioned cache keys advance on the collector cadence — the caller
    never touches ``poll``."""
    col, cache, ing, collect = _pump_world()
    v0, key0 = ing.version, ing.archive.key
    pump = IngestPump(ing, collect)
    with pump:
        deadline = time.monotonic() + 30.0
        while ing.version < v0 + 5 and time.monotonic() < deadline:
            time.sleep(0.01)
    assert not pump.running                  # context exit stopped it
    assert ing.version >= v0 + 5
    assert pump.ticks_pumped == ing.version - v0
    assert pump.errors == 0
    assert ing.archive.key in cache and key0 not in cache
    assert ing.lag == 0                      # pump left nothing pending
    # the pumped archive serves exactly like a cold re-stage
    engine = RecommendationEngine(EngineConfig(score_impl="tiled"))
    reqs = _requests(col.to_candidate_set(window=WINDOW))
    live = engine.recommend_batch(ing.archive.host, reqs,
                                  archive=ing.archive)
    cold_set = col.to_candidate_set(window=WINDOW)
    cold = engine.recommend_batch(cold_set, reqs,
                                  archive=DeviceArchive.stage(cold_set))
    for a, b in zip(live, cold):
        _assert_same_pools(a, b)


def test_ingest_pump_clean_start_stop():
    _, _, ing, collect = _pump_world()
    pump = IngestPump(ing, collect, period=0.005)
    assert not pump.running
    pump.stop()                              # stop before start is a no-op
    pump.start()
    assert pump.running
    with pytest.raises(RuntimeError, match="already running"):
        pump.start()
    pump.stop()
    assert not pump.running
    pump.start()                             # restartable after a stop
    pump.stop()
    assert not pump.running
    with pytest.raises(ValueError):
        IngestPump(ing, collect, period=-1.0)


def test_ingest_pump_swallows_flaky_ticks():
    """A raising collect hook is counted, kept, and never kills the pump."""
    _, _, ing, collect = _pump_world()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] % 2:
            raise RuntimeError("flaky tick")
        collect()

    pump = IngestPump(ing, flaky)
    with pump:
        deadline = time.monotonic() + 30.0
        while (pump.errors < 2 or pump.ticks_pumped < 2) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pump.running                  # still alive through raises
    assert pump.errors >= 2
    assert pump.ticks_pumped >= 2
    assert isinstance(pump.last_error, RuntimeError)
