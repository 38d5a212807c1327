"""Parity + serve-layer tests for the fused batched recommendation path.

The contract under test (see ``RecommendationEngine.recommend_batch``):
against per-request ``recommend``, the recommended pool is bit-identical —
members, order, counts, hourly cost, diagnostics — and the reported scores
agree to the last float32 ulp (XLA FMA-contracts the elementwise scoring
chains shape-dependently; the cross-candidate reductions are masked, not
gathered, so pool decisions stay exact).  Batch composition — padding,
bucketing, batch size — must never change any result bit.
"""
import numpy as np
import pytest

from repro.core import (EngineConfig, RecommendationEngine, RequestBatch,
                        ResourceRequest)
from repro.core.types import CandidateSet
from repro.serve import ArchiveCache, BatchServer, DeviceArchive

# one ulp of float32 around 1.0 is ~1.2e-7; allow a few ulp at score scale
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-4


def synth_candidates(seed: int, K: int, T: int = 24) -> CandidateSet:
    rng = np.random.default_rng(seed)
    fams = rng.choice(["m5", "c5", "r5", "t3"], K)
    return CandidateSet(
        names=np.array([f"{fams[i]}.x{i}" for i in range(K)]),
        regions=rng.choice(["us-east-1", "eu-west-1", "ap-north-1"], K),
        azs=rng.choice(["a", "b", "c"], K),
        families=fams,
        categories=rng.choice(["general", "compute", "memory"], K),
        vcpus=rng.choice([2, 4, 8, 16, 32, 64, 96], K).astype(np.float64),
        memory_gb=rng.choice([4, 8, 16, 64, 128, 384], K).astype(np.float64),
        prices=rng.uniform(0.01, 5.0, K),
        t3=rng.uniform(0.0, 50.0, (K, T)),
    )


def assert_equivalent(seq, bat):
    """Pool bit-identical; scores ulp-tight."""
    assert list(seq.names) == list(bat.names)
    assert list(seq.regions) == list(bat.regions)
    assert list(seq.azs) == list(bat.azs)
    np.testing.assert_array_equal(seq.counts, bat.counts)
    assert seq.hourly_cost == bat.hourly_cost
    assert (seq.diagnostics["candidates_considered"]
            == bat.diagnostics["candidates_considered"])
    assert (seq.diagnostics["greedy_iterations"]
            == bat.diagnostics["greedy_iterations"])
    for a, b in ((seq.combined, bat.combined),
                 (seq.availability, bat.availability),
                 (seq.cost, bat.cost)):
        np.testing.assert_allclose(a, b, rtol=SCORE_RTOL, atol=SCORE_ATOL)


@pytest.fixture(scope="module")
def cands():
    return synth_candidates(seed=11, K=72)


@pytest.fixture(scope="module")
def engine():
    return RecommendationEngine()


def heterogeneous_requests(cands):
    """Mixed targets, weights, lambdas, filters, and max_types caps."""
    return [
        ResourceRequest(cpus=128.0),
        ResourceRequest(memory_gb=256.0, weight=0.8),
        ResourceRequest(cpus=96.0, weight=0.0, lam=0.3),
        ResourceRequest(cpus=64.0, regions=[str(cands.regions[0])]),
        ResourceRequest(cpus=200.0, max_types=2),
        ResourceRequest(cpus=32.0, types=[str(cands.names[5])]),
        ResourceRequest(cpus=500.0, weight=1.0),
        ResourceRequest(cpus=77.0, weight=0.37, lam=0.21),
        ResourceRequest(memory_gb=48.0, weight=0.9,
                        families=["c5", "r5"]),
        ResourceRequest(cpus=1000.0, weight=0.25, lam=0.05,
                        categories=["general", "memory"]),
    ]


def test_batch_matches_sequential(cands, engine):
    reqs = heterogeneous_requests(cands)
    batch = engine.recommend_batch(cands, reqs)
    assert len(batch) == len(reqs)
    for req, bat in zip(reqs, batch):
        assert_equivalent(engine.recommend(cands, req), bat)


def test_batch_matches_sequential_randomized(engine):
    rng = np.random.default_rng(7)
    for trial in range(6):
        c = synth_candidates(seed=100 + trial, K=int(rng.integers(3, 90)))
        reqs = []
        for _ in range(int(rng.integers(1, 9))):
            kw = ({"cpus": float(rng.integers(8, 1500))} if rng.random() < 0.5
                  else {"memory_gb": float(rng.integers(16, 3000))})
            if rng.random() < 0.4:
                kw["regions"] = [str(rng.choice(c.regions))]
            if rng.random() < 0.3:
                kw["families"] = [str(f) for f in rng.choice(c.families, 2)]
            if rng.random() < 0.2:
                kw["max_types"] = int(rng.integers(1, 5))
            reqs.append(ResourceRequest(weight=float(np.round(rng.random(), 3)),
                                        lam=float(np.round(rng.random() * 0.5, 3)),
                                        **kw))
        for req, bat in zip(reqs, engine.recommend_batch(c, reqs)):
            assert_equivalent(engine.recommend(c, req), bat)


def test_padding_is_bit_invariant(cands, engine):
    """Padded dummy rows must not perturb any real row's result bits."""
    reqs = heterogeneous_requests(cands)
    plain = engine.recommend_batch(cands, reqs)
    padded = engine.recommend_batch(cands, reqs, pad_to=16)
    for a, b in zip(plain, padded):
        assert list(a.names) == list(b.names)
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.combined, b.combined)
        np.testing.assert_array_equal(a.availability, b.availability)
        np.testing.assert_array_equal(a.cost, b.cost)
        assert a.hourly_cost == b.hourly_cost


def test_single_candidate_filter(cands, engine):
    """A filter surviving exactly one candidate -> single-type pool."""
    req = ResourceRequest(cpus=64.0, types=[str(cands.names[3])])
    bat = engine.recommend_batch(cands, [req])[0]
    seq = engine.recommend(cands, req)
    assert_equivalent(seq, bat)
    assert bat.num_types == 1
    assert bat.counts[0] == int(np.ceil(64.0 / cands.vcpus[3]))


def test_degenerate_zero_score_fallback(engine):
    """All-zero combined scores (W=1, constant T3) -> Algorithm 1's
    degenerate guard: a single-type pool sized to the requirement."""
    c = synth_candidates(seed=21, K=12)
    c.t3[:] = 7.0                     # constant rows: every AS_i == 0
    req = ResourceRequest(cpus=100.0, weight=1.0)
    bat = engine.recommend_batch(c, [req])[0]
    seq = engine.recommend(c, req)
    assert_equivalent(seq, bat)
    assert bat.num_types == 1
    assert (bat.counts[0] * c.vcpus[list(c.names).index(bat.names[0])]
            >= req.cpus)


def test_empty_filter_raises(cands, engine):
    reqs = [ResourceRequest(cpus=8.0),
            ResourceRequest(cpus=8.0, regions=["nowhere-9"])]
    with pytest.raises(ValueError, match="batch row 1"):
        engine.recommend_batch(cands, reqs)


def test_empty_filter_contract_agrees_across_entry_points(cands, engine):
    """Both entry points raise on a filter that matches nothing — there is
    no silent empty-pool Recommendation from either path."""
    req = ResourceRequest(cpus=8.0, regions=["nowhere-9"])
    with pytest.raises(ValueError,
                       match="no candidates satisfy the request filters"):
        engine.recommend(cands, req)
    with pytest.raises(ValueError,
                       match="no candidates satisfy the request filters"):
        engine.recommend_batch(cands, [req])
    # inside a mixed batch the raise names the offending row
    good = ResourceRequest(cpus=8.0)
    with pytest.raises(ValueError, match="batch row 2"):
        engine.recommend_batch(cands, [good, good, req, good])


def test_all_masked_row_never_reaches_dispatch(cands, engine, monkeypatch):
    """Defense in depth: even if a batch constructor leaks an all-masked
    row, recommend_batch re-checks before dispatch — the masked Algorithm 1
    scan would otherwise terminate degenerately at k = 0 and emit a
    single-type pool on a candidate the request filtered out."""
    real = RequestBatch.from_requests

    def leaky(cands_, requests, pad_to=None):
        rb = real(cands_, requests, pad_to=pad_to)
        rb.masks[1] = False           # the row the constructor failed to reject
        return rb

    monkeypatch.setattr(RequestBatch, "from_requests", leaky)
    reqs = [ResourceRequest(cpus=8.0)] * 3
    with pytest.raises(ValueError, match="batch row 1"):
        engine.recommend_batch(cands, reqs)


def test_empty_batch(cands, engine):
    assert engine.recommend_batch(cands, []) == []


def test_solve_time_is_whole_batch_wall_time(cands, engine):
    """Diagnostics contract of the batch path: every row carries the
    padded ``batch_size`` it rode in and no wall time (the batch path is
    timed by its ``repro.engine.*`` spans, not by a stamp on each row)."""
    reqs = heterogeneous_requests(cands)[:4]
    for pad_to, size in ((None, 4), (8, 8)):
        recs = engine.recommend_batch(cands, reqs, pad_to=pad_to)
        assert all(r.diagnostics["batch_size"] == size for r in recs)
        assert all("solve_time_s" not in r.diagnostics for r in recs)


def test_request_batch_padding_shape(cands):
    reqs = [ResourceRequest(cpus=16.0)]
    rb = RequestBatch.from_requests(cands, reqs, pad_to=8)
    assert rb.batch_size == 8 and rb.n_valid == 1
    assert rb.masks.shape == (8, len(cands))
    # pad_to smaller than the batch is ignored, not an error
    rb2 = RequestBatch.from_requests(cands, reqs * 3, pad_to=2)
    assert rb2.batch_size == 3


# ---------------------------------------------------------------------------
# serve layer
# ---------------------------------------------------------------------------

def test_batch_server_matches_engine(cands, engine):
    srv = BatchServer(engine, bucket_sizes=(1, 8, 64),
                      config=EngineConfig(cache_capacity=2))
    rng = np.random.default_rng(5)
    reqs = [ResourceRequest(cpus=float(rng.integers(8, 800)),
                            weight=float(np.round(rng.random(), 2)))
            for _ in range(20)]
    res = srv.serve(cands, reqs)
    assert len(res) == len(reqs)
    for req, bat in zip(reqs, res):
        assert_equivalent(engine.recommend(cands, req), bat)
    assert srv.stats.requests == 20
    assert sum(srv.stats.bucket_counts.values()) == srv.stats.batches


def test_batch_server_bucketing_bounds_shapes():
    srv = BatchServer(bucket_sizes=(1, 8, 64, 256))
    for n, want in ((1, [(1, 1)]), (5, [(5, 8)]), (64, [(64, 64)]),
                    (100, [(64, 64), (36, 64)]),
                    (300, [(256, 256), (44, 64)])):
        got = srv.plan_chunks(n)
        assert got == want, (n, got)
        assert sum(c for c, _ in got) == n


def test_archive_cache_lru(cands):
    cache = ArchiveCache(capacity=2)
    a1 = cache.get(cands)
    assert cache.misses == 1
    # same content, different object -> content-keyed hit
    clone = cands.take(np.arange(len(cands)))
    assert cache.get(clone) is a1
    assert cache.hits == 1
    c2, c3 = synth_candidates(31, 10), synth_candidates(32, 10)
    cache.get(c2)
    cache.get(c3)                      # evicts a1 (capacity 2)
    assert cache.evictions == 1 and len(cache) == 2
    cache.get(cands)                   # re-staged
    assert cache.misses == 4


def test_device_archive_nbytes_counts_materialized_stats(cands):
    """`nbytes` must grow when the memoised score_stats materialize — they
    are device-resident exactly as long as the entry is."""
    arch = DeviceArchive.stage(cands)
    base = arch.nbytes
    stats = arch.score_stats()
    grown = arch.nbytes
    assert grown == base + sum(int(a.nbytes) for a in stats)
    assert arch.score_stats() is stats          # memoised, not recomputed
    assert arch.nbytes == grown                 # and counted exactly once


def test_archive_cache_byte_budget_eviction_order():
    """Byte-budget eviction must see lazily-materialized stats: scoring a
    cached archive can push the cache over budget, and the next insertion
    then evicts LRU-first."""
    c1, c2, c3 = (synth_candidates(40 + i, K=24, T=16) for i in range(3))
    probe = DeviceArchive.stage(c1)
    plain = probe.nbytes
    stats_bytes = sum(int(a.nbytes) for a in probe.score_stats())
    # budget: three plain archives plus one stats set fit — three archives
    # with *two* stats sets do not
    cache = ArchiveCache(capacity=8, max_bytes=3 * plain + stats_bytes)
    a1 = cache.get(c1)
    a2 = cache.get(c2)
    assert len(cache) == 2 and cache.evictions == 0
    a1.score_stats()                    # a1 fattens past the plain estimate
    a2.score_stats()                    # over budget now, visible at next put
    cache.get(c3)                       # insertion enforces the budget
    # eviction is LRU-order: a1 (oldest) goes, a2 + the new entry then fit
    assert cache.evictions == 1
    assert c1.fingerprint() not in cache
    assert c2.fingerprint() in cache and c3.fingerprint() in cache
    # with the stats bytes invisible (the old bug) nothing would have been
    # evicted: three plain archives fit the budget
    assert 3 * plain <= cache.max_bytes


def test_archive_cache_byte_budget_keeps_most_recent():
    """The newest entry always survives, even when alone over budget."""
    c = synth_candidates(50, K=40, T=64)
    cache = ArchiveCache(capacity=4, max_bytes=1)     # absurdly tight
    a = cache.get(c)
    assert len(cache) == 1 and a.nbytes > 1           # kept regardless


def test_device_archive_roundtrip(cands, engine):
    arch = DeviceArchive.stage(cands)
    req = ResourceRequest(cpus=96.0, weight=0.6)
    with_arch = engine.recommend_batch(cands, [req], archive=arch)[0]
    without = engine.recommend_batch(cands, [req])[0]
    assert list(with_arch.names) == list(without.names)
    np.testing.assert_array_equal(with_arch.counts, without.counts)
    np.testing.assert_array_equal(with_arch.combined, without.combined)
    assert with_arch.hourly_cost == without.hourly_cost
    assert arch.nbytes > 0 and len(arch) == len(cands)
