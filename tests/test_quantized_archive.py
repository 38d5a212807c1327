"""Quantized archive tier: staged/rolling/sharded parity, the documented
error-bound contract, cache tier separation, and nbytes accounting.

The tier's ground truth is the **dequantized stored window**: every surface
(streamed statistics, materialize, score_stats) must agree with
``candidate_stats`` of that window at the usual float32-ulp budget, and the
recommendation pools must be bit-identical to the float32 tier's whenever
every Algorithm 1 decision margin exceeds the score bound derived in
``repro.core.quantized`` — divergences inside the bound are flagged ties.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import (EngineConfig, RecommendationEngine, ResourceRequest,
                        quantized as qz, scoring)
from repro.core.pool import greedy_pool
from repro.core.types import RequestBatch
from repro.parallel import compression as comp
from repro.serve import (ArchiveCache, BatchServer, DeviceArchive,
                         QuantizedDeviceArchive)
from repro.shard import ShardedArchive, ShardedRollingArchive
from repro.stream import LiveIngestor, RollingDeviceArchive

from test_compression import tie_window
from test_serve_batch import synth_candidates
from test_stream import _collector

RTOL = 1e-5
ATOL = 1e-4

QUANT = ["bfloat16", "int8"]
TIERS = ["float32"] + QUANT


def _assert_stats_close(got, want):
    for name, a, b in zip(("area", "slope", "std"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL, err_msg=name)


# ---------------------------------------------------------------------------
# staged archives (DeviceArchive.stage(precision=...))
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", QUANT)
def test_staged_quantized_archive_surface(precision):
    cands = synth_candidates(1, K=97)
    arch = DeviceArchive.stage(cands, precision=precision)
    assert isinstance(arch, QuantizedDeviceArchive)
    assert arch.key.endswith(f"#{precision}")
    assert getattr(arch, "dense_capable", True)   # decodes for dense parity
    # t3 decodes to exactly what the host-side decode of the stored codes is
    want = np.asarray(comp.dequantize_window(
        np.asarray(arch.t3_q), np.asarray(arch.scale), precision))
    np.testing.assert_array_equal(np.asarray(arch.t3), want)
    # statistics are the dequantized window's, not the float32 source's
    _assert_stats_close(arch.score_stats(),
                        scoring.candidate_stats(jnp.asarray(want)))
    # catalog columns are never quantised
    np.testing.assert_allclose(np.asarray(arch.prices),
                               cands.prices.astype(np.float32))


def test_staged_tiers_never_share_cache_keys():
    cands = synth_candidates(2, K=33)
    keys = {DeviceArchive.stage(cands, precision=p).key for p in TIERS}
    assert len(keys) == 3
    # one cache can hold all three tiers of the same candidate set at once
    cache = ArchiveCache(capacity=4)
    for p in TIERS:
        cache.put(DeviceArchive.stage(cands, precision=p))
    assert len(cache) == 3


def test_cache_precision_stages_and_keys_that_tier():
    cands = synth_candidates(3, K=41)
    f32_cache = ArchiveCache(capacity=2)
    q_cache = ArchiveCache(capacity=2, precision="int8", headroom=1.5)
    a = f32_cache.get(cands)
    b = q_cache.get(cands)
    assert isinstance(a, DeviceArchive) and isinstance(b, QuantizedDeviceArchive)
    assert b.key == f"{a.key}#int8"
    assert q_cache.get(cands) is b and q_cache.hits == 1


def test_engine_config_threads_precision():
    cfg = EngineConfig(archive_precision="int8", archive_headroom=1.25)
    cache = cfg.build_cache()
    assert cache.precision == "int8" and cache.headroom == 1.25
    with pytest.raises(ValueError, match="precision"):
        EngineConfig(archive_precision="int4")
    with pytest.raises(ValueError, match="headroom"):
        EngineConfig(archive_headroom=0.9)


# ---------------------------------------------------------------------------
# rolling rings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", QUANT)
def test_rolling_quantized_tracks_dequantized_window(precision):
    rng = np.random.default_rng(5)
    cands = synth_candidates(5, K=64, T=12)
    arch = RollingDeviceArchive(cands, capacity=12, precision=precision,
                                headroom=1.5)
    assert arch.key.endswith(f"#{precision}")
    for i in range(20):
        arch.append(rng.uniform(0.0, 50.0, 64))
        win = arch.materialize()            # the dequantized stored window
        _assert_stats_close(arch.score_stats(),
                            scoring.candidate_stats(jnp.asarray(win)))
    assert arch.clipped_samples == 0        # headroom covered the draws
    # stored content matches a host-side re-quantisation of the raw history
    # bit for bit (same scale, same round/clip sequence)
    snap = arch.snapshot()
    assert snap.precision == precision and snap.key == arch.key
    _assert_stats_close(snap.stats, arch.score_stats())


def test_rolling_int8_clipping_is_surfaced():
    cands = synth_candidates(6, K=16, T=8)
    arch = RollingDeviceArchive(cands, capacity=8, precision="int8")
    arch.append(np.full(16, 1e4))           # far outside every clip range
    assert arch.clipped_samples == 16


def test_rolling_quantized_append_matches_staged_codes():
    """A ring that absorbed N ticks stores the same codes a cold staging of
    the final logical window would — streamed and staged quantisation agree
    bit for bit (clip-free regime)."""
    rng = np.random.default_rng(7)
    K, T = 32, 10
    cands = synth_candidates(7, K=K, T=T)
    arch = RollingDeviceArchive(cands, capacity=T, precision="int8",
                                headroom=2.0)
    history = np.asarray(cands.t3, np.float64)
    for _ in range(2 * T):
        col = rng.uniform(0.0, 25.0, K)
        arch.append(col)
        history = np.concatenate([history, col[:, None]], axis=1)
    scale = np.asarray(arch.scale)
    want = comp.quantize_window(history[:, -T:], scale, "int8")
    got = np.asarray(arch.materialize())
    np.testing.assert_array_equal(
        got, np.asarray(comp.dequantize_window(want, scale, "int8")))


def test_rolling_int8_tick_codes_at_exact_ties():
    """A tick full of exact ties of ``y / step`` and clipped samples is
    stored as ``quantize_window`` encodes it: the ring receives int8 codes
    made on the host (K bytes a tick), not a float column to divide on the
    device, whose divide need not round exactly at a tie."""
    window, tick = tie_window()
    K, T = window.shape
    cands = dataclasses.replace(synth_candidates(8, K=K, T=T), t3=window)
    arch = RollingDeviceArchive(cands, capacity=T, precision="int8")
    scale = comp.candidate_scales(window, "int8")
    np.testing.assert_array_equal(np.asarray(arch.scale), scale)
    sent, clipped = arch._encode(tick)
    _, operands, _ = arch._append_dispatch(sent)
    assert operands[2].dtype == jnp.int8 and sent.nbytes == K
    slot = arch._pos
    arch.append(tick)
    want = comp.quantize_window(tick[:, None], scale, "int8")[:, 0]
    np.testing.assert_array_equal(np.asarray(arch._buf)[:, slot], want)
    assert arch.clipped_samples == clipped == 5


class WalkCollector:
    """What ``LiveIngestor`` reads of a collector, over one walk of T3
    counts: the first ``window`` columns are complete, ``run(n)`` completes
    ``n`` more."""

    def __init__(self, cands, walk, window):
        self.cands, self.walk, self.ticks = cands, walk, window

    def run(self, n: int) -> None:
        self.ticks += n

    def column(self, i: int) -> np.ndarray:
        return self.walk[:, i]

    def to_candidate_set(self, window: int):
        return dataclasses.replace(
            self.cands, t3=self.walk[:, self.ticks - window:self.ticks])


def _stats64(window):
    """Eq. 3 statistics of a window in float64: trapezoid area,
    least-squares slope, population std."""
    y = np.asarray(window, np.float64)
    t = np.arange(y.shape[1]) - (y.shape[1] - 1) / 2.0
    d = y - y.mean(axis=1, keepdims=True)
    return (y.sum(axis=1) - 0.5 * (y[:, 0] + y[:, -1]), d @ t / (t @ t),
            np.sqrt((d * d).mean(axis=1)))


def _greedy_reference(req, cands, window):
    """The pool Algorithm 1 forms from float64 Eq. 2-4 scores over the
    request's candidates: ``(candidate indices, counts)``."""
    idx = np.flatnonzero(req.filter_mask(cands))
    caps = np.asarray(req.capacity_of(cands), np.float64)[idx]
    avail = scoring.availability_scores_ref(window[idx], req.lam)
    cost = scoring.cost_scores_ref(cands.prices[idx], caps, req.amount)
    res = greedy_pool(req.weight * avail + (1 - req.weight) * cost, caps,
                      req.amount)
    return idx[res.indices], res.counts


def test_ingestor_int8_ring_matches_references_over_60_ticks():
    """An int8 ring primed by ``LiveIngestor`` and fed 60 ticks of a walk of
    T3 counts (exact ties and clipped samples among them): its stored codes
    equal ``quantize_window`` of the walk's window, its statistics float64
    statistics of the dequantized window, and its served pools Algorithm 1
    over float64 scores, bit for bit."""
    rng = np.random.default_rng(16)
    K, T, N = 96, 24, 60
    steps = rng.choice([-5.0, 0.0, 0.0, 5.0], (K, T + N))
    walk = np.clip(rng.integers(0, 11, (K, 1)) * 5.0
                   + np.cumsum(steps, axis=1), 0.0, 50.0)
    cands = synth_candidates(16, K=K, T=T)
    col = WalkCollector(cands, walk, T)
    ing = LiveIngestor(col, window=T, precision="int8")
    arch = ing.prime()
    scale = comp.candidate_scales(walk[:, :T], "int8")
    server = BatchServer(config=EngineConfig(score_impl="tiled"),
                         bucket_sizes=(1, 4, 8))
    reqs = [ResourceRequest(cpus=128.0),
            ResourceRequest(memory_gb=256.0, weight=0.8),
            ResourceRequest(cpus=96.0, weight=0.3, lam=0.25),
            ResourceRequest(cpus=64.0, regions=[str(cands.regions[0])])]
    ties = 0
    for tick in range(1, N + 1):
        col.run(1)
        assert ing.poll() == 1 and arch.version == tick
        q = walk[:, T + tick - 1].astype(np.float32) / scale
        ties += int((q - np.floor(q) == np.float32(0.5)).sum())
        if tick % 20:
            continue
        order = (arch._start + np.arange(T)) % arch.capacity
        codes = comp.quantize_window(walk[:, tick:tick + T], scale, "int8")
        np.testing.assert_array_equal(np.asarray(arch._buf)[:, order], codes)
        stored = np.asarray(comp.dequantize_window(codes, scale, "int8"),
                            np.float64)
        area, slope, std = (np.asarray(x, np.float64)
                            for x in arch.score_stats())
        want = _stats64(stored)
        _assert_stats_close((area, slope), want[:2])
        # the streamed std is the root of a difference of float32 moments
        # of size ~max(y)**2: compare variances, to a few of their ulps
        np.testing.assert_allclose(std ** 2, want[2] ** 2, rtol=RTOL,
                                   atol=1e-6 * 50.0 ** 2)
        for req, rec in zip(reqs, server.serve(arch.snapshot(), reqs)):
            members, counts = _greedy_reference(req, cands, stored)
            assert list(rec.names) == list(cands.names[members])
            assert list(rec.azs) == list(cands.azs[members])
            np.testing.assert_array_equal(rec.counts, counts)
    assert ties > 0
    clip = np.abs(np.round(walk[:, T:].astype(np.float32)
                           / scale[:, None])) > 127
    assert arch.clipped_samples == int(clip.sum()) > 0


@pytest.mark.parametrize("precision", TIERS)
def test_rolling_nbytes_sums_components(precision):
    """nbytes == ring + catalog columns + moment pairs + scale + memoised
    state — the satellite regression for cache-budget accounting."""
    cands = synth_candidates(8, K=50, T=16)
    arch = RollingDeviceArchive(cands, capacity=16, precision=precision)
    parts = [arch._buf, arch.prices, arch.vcpus, arch.memory_gb,
             *arch._moments]
    if arch.scale is not None:
        parts.append(arch.scale)
    assert arch.nbytes == sum(int(a.nbytes) for a in parts)
    stats = arch.score_stats()              # memoise, must now be counted
    assert arch.nbytes == sum(int(a.nbytes) for a in parts) \
        + sum(int(a.nbytes) for a in stats)
    _ = arch.t3                             # memoised gather counts too
    assert arch.nbytes == sum(int(a.nbytes) for a in parts) \
        + sum(int(a.nbytes) for a in stats) + int(arch._t3_logical.nbytes)
    # snapshot: catalog + stats + scale, nothing donated
    snap = arch.snapshot()
    want = sum(int(a.nbytes) for a in
               (snap.prices, snap.vcpus, snap.memory_gb, *snap.stats))
    if snap.scale is not None:
        want += int(snap.scale.nbytes)
    assert snap.nbytes == want


@pytest.mark.parametrize("precision", TIERS)
def test_staged_nbytes_sums_components(precision):
    cands = synth_candidates(9, K=40, T=16)
    arch = DeviceArchive.stage(cands, precision=precision)
    if precision == "float32":
        parts = [arch.t3, arch.prices, arch.vcpus, arch.memory_gb]
    else:
        parts = [arch.t3_q, arch.scale, arch.prices, arch.vcpus,
                 arch.memory_gb]
    assert arch.nbytes == sum(int(a.nbytes) for a in parts)
    stats = arch.score_stats()
    assert arch.nbytes == sum(int(a.nbytes) for a in parts) \
        + sum(int(a.nbytes) for a in stats)


def test_int8_ring_is_roughly_4x_smaller():
    cands = synth_candidates(10, K=256, T=64)
    f32 = RollingDeviceArchive(cands, capacity=64)
    q = RollingDeviceArchive(cands, capacity=64, precision="int8")
    assert int(f32._buf.nbytes) == 4 * int(q._buf.nbytes)


# ---------------------------------------------------------------------------
# sharded archives
# ---------------------------------------------------------------------------

def test_sharded_quantized_matches_single_ring():
    rng = np.random.default_rng(11)
    K, T = 48, 9
    cands = synth_candidates(11, K=K, T=T)
    single = RollingDeviceArchive(cands, capacity=T, precision="int8",
                                  name="arch", headroom=3.0)
    sharded = ShardedRollingArchive(cands, capacity=T, n_shards=3,
                                    name="arch", precision="int8",
                                    headroom=3.0)
    assert sharded.key.endswith("#int8")
    for _ in range(2 * T):
        col = rng.uniform(0.0, 50.0, K)
        single.append(col)
        sharded.append(col)
    # per-candidate quantisation: row-sliced shards store and decode exactly
    # the rows of the single-device ring
    np.testing.assert_array_equal(sharded.materialize(), single.materialize())
    assert sharded.clipped_samples == single.clipped_samples == 0
    got = np.concatenate(
        [np.asarray(s.score_stats().area) for s in sharded.shards])
    np.testing.assert_array_equal(got, np.asarray(single.score_stats().area))


def test_sharded_stage_threads_precision():
    cands = synth_candidates(12, K=30, T=8)
    arch = ShardedArchive.stage(cands, n_shards=2, precision="int8")
    assert arch.key.endswith("#int8")
    for shard in arch.shards:
        assert isinstance(shard, QuantizedDeviceArchive)
        assert shard.key.endswith("#int8")
    # nbytes sums shard components + full-width merge columns
    want = sum(s.nbytes for s in arch.shards) + sum(
        int(a.nbytes) for a in (arch.prices, arch.vcpus, arch.memory_gb))
    assert arch.nbytes == want


# ---------------------------------------------------------------------------
# live ingestion + collector ring dtype
# ---------------------------------------------------------------------------

def test_ingestor_precision_from_config():
    col = _collector()
    cfg = EngineConfig(archive_precision="int8", archive_headroom=1.5)
    ing = LiveIngestor(col, window=8, config=cfg)
    arch = ing.prime()
    assert arch.precision == "int8" and arch.key.endswith("#int8")
    assert ing.cache is not None and arch.key in ing.cache
    col.run(2)
    ing.poll()
    assert ing.archive.key in ing.cache and ing.archive.version == 2
    # explicit precision= wins over the config
    ing2 = LiveIngestor(col, window=8, precision="bfloat16")
    assert ing2.prime().precision == "bfloat16"


def test_collector_ring_dtype_is_value_transparent():
    """float32 / int16 host rings reproduce the float64 ring bit for bit —
    T3 values are small integer node counts."""
    cols = {}
    for dtype in ("float64", "float32", "int16"):
        c = _collector(ring=32)
        assert c._ring.dtype == np.float64      # default unchanged
        c2 = DataCollector(
            SPSQueryService(SpotMarket(Catalog(seed=3, n_regions=2), seed=3),
                            n_accounts=3000),
            c.targets, CollectorConfig(ring_capacity=32, ring_dtype=dtype))
        c2.run(10)
        cols[dtype] = c2
    base = cols["float64"]
    for dtype in ("float32", "int16"):
        other = cols[dtype]
        assert other._ring.dtype == np.dtype(dtype)
        for i in range(10):
            got = other.column(i)
            assert got.dtype == np.float64
            np.testing.assert_array_equal(got, base.column(i))
        a = base.to_candidate_set(window=8)
        b = other.to_candidate_set(window=8)
        assert b.t3.dtype == np.float64
        np.testing.assert_array_equal(a.t3, b.t3)


# ---------------------------------------------------------------------------
# the error-bound / pool-parity contract
# ---------------------------------------------------------------------------

def _parity_case(cands, requests, precision="int8"):
    """recommend_batch on the float32 vs quantized tier + the per-request
    bound/margin replay of ``repro.core.quantized``."""
    engine = RecommendationEngine()
    f32 = DeviceArchive.stage(cands)
    q = DeviceArchive.stage(cands, precision=precision)
    recs_f = engine.recommend_batch(cands, requests, archive=f32)
    recs_q = engine.recommend_batch(cands, requests, archive=q)
    t3f = jnp.asarray(cands.t3, jnp.float32)
    stats = scoring.candidate_stats(t3f)
    T = cands.t3.shape[1]
    bounds = qz.stat_bounds(np.asarray(q.scale), T)
    masks = RequestBatch.from_requests(cands, requests).masks
    out = []
    for req, rec_f, rec_q, mask in zip(requests, recs_f, recs_q, masks):
        avail = scoring.availability_scores_masked(t3f, req.lam,
                                                   jnp.asarray(mask))
        caps = req.capacity_of(cands)
        cost = scoring.cost_scores_masked(cands.prices, caps, req.amount,
                                          jnp.asarray(mask))
        comb = np.asarray(
            scoring.combined_scores(avail, cost, req.weight), np.float64)
        bound = qz.score_bound(
            scoring.CandidateStats(*(np.asarray(s) for s in stats)),
            bounds, mask, req.lam, req.weight)
        out.append(qz.check_pool_parity(rec_f, rec_q, comb, caps,
                                        req.amount, mask, bound))
    return out


def test_parity_contract_random_catalog():
    """Random catalog: every request either matches bit for bit or is a
    flagged tie — never an unexplained divergence."""
    cands = synth_candidates(21, K=96, T=24)
    requests = [
        ResourceRequest(cpus=128.0),
        ResourceRequest(memory_gb=256.0, weight=0.8),
        ResourceRequest(cpus=96.0, weight=0.3, lam=0.25),
        ResourceRequest(cpus=64.0, regions=[str(cands.regions[0])]),
    ]
    parities = [p for prec in QUANT
                for p in _parity_case(cands, requests, prec)]
    for p in parities:
        assert p.ok, p
        if p.margin > 1.0:
            assert p.identical, p


def test_parity_contract_separated_catalog_is_bit_identical():
    """Well-separated candidates: the *measured* quantized score drift stays
    inside the documented budget, every adjacent masked score gap exceeds
    twice the bound (the ordering provably cannot flip), and the pools come
    out bit-identical.

    Note the all-prefix ceil replay still reports margin <= 1 here — and on
    essentially any realistic catalog: Algorithm 1's allocation boundary
    ``R / c_0`` lands on an exact integer whenever the requested amount
    divides the top scorer's vcpus, which honestly *is* a tie (a one-ulp
    drift flips the ceil even though the real-number pool is unchanged).
    The margin > 1 certification semantics are therefore unit-tested with
    controlled operands in ``test_tie_is_flagged_not_hidden``; this test
    pins the score-drift budget and the ordering gap end to end."""
    rng = np.random.default_rng(23)
    K, T = 12, 24
    cands = synth_candidates(25, K=K, T=T)
    # Candidates separated in *every* Eq. 3 statistic by much more than the
    # int8 step (~maxabs / 127): levels 4 apart, slopes 0.05 apart, noise
    # amplitudes 0.8 apart.  The masked MinMax ranges then dwarf the
    # quantisation drift, keeping the score bound finite and small.
    i = np.arange(K)[:, None]
    t = np.arange(T)[None, :]
    t3 = (8.0 + 4.0 * i) + (0.05 * i - 0.3) * (t - T / 2) \
        + (0.5 + 0.8 * i) * rng.uniform(-1.0, 1.0, (K, T))
    cands = type(cands)(
        names=cands.names, regions=cands.regions, azs=cands.azs,
        families=cands.families, categories=cands.categories,
        vcpus=cands.vcpus, memory_gb=cands.memory_gb, prices=cands.prices,
        t3=t3)
    # weight=1.0: the combined score is pure availability, so the evenly
    # spaced normalised areas give ~100/(K-1) point gaps between adjacent
    # candidates — far outside twice the quantisation score bound.  (Any
    # weight < 1 mixes in cost gaps that can nearly cancel an availability
    # gap for some adjacent pair.)
    requests = [ResourceRequest(cpus=63.0, weight=1.0, lam=0.01),
                ResourceRequest(cpus=127.0, weight=1.0, lam=0.01)]
    q = DeviceArchive.stage(cands, precision="int8")
    t3f = jnp.asarray(cands.t3, jnp.float32)
    t3q = jnp.asarray(q.t3)                     # decoded stored window
    masks = RequestBatch.from_requests(cands, requests).masks
    parities = _parity_case(cands, requests, "int8")
    for req, mask, p in zip(requests, masks, parities):
        assert p.identical and p.ok, p
        assert np.isfinite(p.bound) and p.bound > 0.0, p
        caps = req.capacity_of(cands)
        cost = scoring.cost_scores_masked(cands.prices, caps, req.amount,
                                          jnp.asarray(mask))
        combs = []
        for win in (t3f, t3q):
            avail = scoring.availability_scores_masked(
                win, req.lam, jnp.asarray(mask))
            combs.append(np.asarray(
                scoring.combined_scores(avail, cost, req.weight),
                np.float64))
        drift = np.abs(combs[1] - combs[0])[mask].max()
        assert drift <= p.bound, (drift, p.bound)
        s = np.sort(combs[0][mask])[::-1]
        gaps = s[:-1] - s[1:]
        assert (gaps > 2.0 * p.bound).all(), (gaps.min(), p.bound)


def test_tie_is_flagged_not_hidden():
    """A divergence inside the bound reports ok (tie=True); the same
    divergence outside the bound is the hard failure the suite must catch.

    The operands are picked so every ceil boundary the replay checks sits
    mid-interval (fracs 0.33-0.8): with R=50 the scan's allocations are
    ``s0*R/(S_k*c0)`` in {16.67, 9.80, 8.33} and ``s_k*R/(S_k*c_k)`` in
    {16.67, 2.94, 0.58}, and the count row at the chosen prefix adds
    {8.33, 2.5, 0.58} — so a tight bound certifies the pool (margin > 1)
    and only a bound comparable to the score gaps turns it into a tie."""
    comb = np.array([10.0, 7.0, 3.0])
    caps = np.array([3.0, 7.0, 13.0])
    mask = np.ones(3, bool)
    tight = qz.pool_decision_margin(comb, caps, 50.0, mask, bound=0.01)
    wide = qz.pool_decision_margin(comb, caps, 50.0, mask, bound=2.0)
    assert tight > 1.0 and wide <= 1.0
    diverged = qz.QuantizedParity(identical=False, tie=True,
                                  margin=wide, bound=2.0)
    assert diverged.ok
    unexplained = qz.QuantizedParity(identical=False, tie=False,
                                     margin=tight, bound=0.01)
    assert not unexplained.ok
    # zero bound (float32 tier against itself): margins are infinite
    assert qz.pool_decision_margin(comb, caps, 50.0, mask, 0.0) == np.inf
    # an exact-integer ceil operand is a genuine tie however tight the
    # bound: R/c0 = 48/4 lands on 12.0, and a one-ulp drift flips it
    exact = qz.pool_decision_margin(comb, np.array([4.0, 7.0, 13.0]),
                                    48.0, mask, bound=1e-9)
    assert exact == 0.0


def test_max_types_margin_is_refused_not_silently_wrong():
    """``max_types`` re-allocation boundaries are not modelled by the
    decision-margin replay — asking for a margin there must raise, not
    certify a pool the cap's proportional refill could flip."""
    comb = np.array([10.0, 7.0, 3.0])
    caps = np.array([3.0, 7.0, 13.0])
    mask = np.ones(3, bool)
    with pytest.raises(NotImplementedError, match="max_types"):
        qz.pool_decision_margin(comb, caps, 50.0, mask, 0.5, max_types=2)
    with pytest.raises(NotImplementedError, match="max_types"):
        qz.check_pool_parity(None, None, comb, caps, 50.0, mask, 0.5,
                             max_types=2)
    # the default path is unchanged
    assert qz.pool_decision_margin(comb, caps, 50.0, mask, 0.01,
                                   max_types=None) > 1.0
