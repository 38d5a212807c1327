"""Streaming masked-scoring kernel parity: ``repro.kernels.score_fuse``
vs the gathered per-request oracle and the dense masked path.

The contract (see the kernel module docstring): on valid lanes the tiled
combined / availability / cost rows agree with the gathered
``availability_scores`` / ``cost_scores`` / ``combined_scores`` oracle to
float32-ulp level (XLA contracts the elementwise chains shape-dependently;
the cross-candidate reductions — MinMax bounds, C_min — are exact), and the
pools formed from them are bit-identical to the per-request path.
Deterministic surface here: tile-boundary K, all-masked and single-lane
masks, constant statistics (the MinMax rng == 0 branch), the precomputed-
extrema short-circuit, Pallas interpret mode, vmap, and ``jax_enable_x64``.
The hypothesis adversarial sweep (duplicate stats, random masks) lives in
``test_scoring.py`` behind its importorskip guard.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as engine_lib
from repro.core import scoring
from repro.core.types import CandidateSet, ResourceRequest
from repro.kernels import score_fuse as sf

from _score_helpers import (ATOL, KW, PALLAS_TILE, RTOL, TILE,
                            assert_matches_oracle, instance, kernel_args)


@pytest.mark.parametrize("k", [1, 2, TILE - 1, TILE, TILE + 1, 2 * TILE, KW])
def test_tile_boundary_matches_oracle(k):
    rng = np.random.default_rng(k)
    t3, prices, vcpus, mems = instance(k, k)
    mask = rng.random(k) < 0.8
    mask[rng.integers(0, k)] = True                # at least one valid lane
    for use_cpus, req in ((True, 129.25), (False, 640.0)):
        outs = sf.score_fuse(*kernel_args(t3, prices, vcpus, mems, mask,
                                          use_cpus, req, 0.1, 0.5),
                             tile=TILE, backend="lax")
        assert_matches_oracle(outs, t3, prices, vcpus, mems, mask, use_cpus,
                              req, 0.1, 0.5)


def test_single_valid_lane():
    t3, prices, vcpus, mems = instance(3)
    mask = np.zeros(KW, bool)
    mask[7] = True
    outs = sf.score_fuse(*kernel_args(t3, prices, vcpus, mems, mask, True,
                                      64.0, 0.1, 0.5), tile=TILE, backend="lax")
    assert_matches_oracle(outs, t3, prices, vcpus, mems, mask, True,
                          64.0, 0.1, 0.5)
    # single lane: every stat rng is 0 -> avail 0, cost exactly 100
    idx = np.flatnonzero(mask)
    assert np.asarray(outs[1])[idx] == 0.0
    assert np.asarray(outs[2])[idx] == 100.0


def test_all_masked_pins_documented_garbage():
    """An empty mask never reaches the kernel from the engine (RequestBatch
    rejects it); pin the documented direct-call behaviour: availability 0
    (every MinMax range is -inf), cost +inf (C_min over no lanes), combined
    finite for weight < 1 and NaN only in the weight == 1 corner."""
    t3, prices, vcpus, mems = instance(4)
    args = (t3, prices, vcpus, mems, np.zeros(KW, bool), True, 64.0, 0.1)
    comb, avail, cost = sf.score_fuse(*kernel_args(*args, 0.5),
                                      tile=TILE, backend="lax")
    np.testing.assert_array_equal(np.asarray(avail), np.zeros(KW))
    assert np.isinf(np.asarray(cost)).all()
    assert np.isinf(np.asarray(comb)).all()        # 0.5*0 + 0.5*inf
    comb1, _, _ = sf.score_fuse(*kernel_args(*args, 1.0),
                                tile=TILE, backend="lax")
    assert np.isnan(np.asarray(comb1)).all()       # 1*0 + 0*inf


def test_constant_stats_hit_rng_zero_branch():
    """Flat T3 rows everywhere -> every MinMax rng is 0 -> avail all 0."""
    t3, prices, vcpus, mems = instance(5)
    t3[:] = t3[:1]                                  # identical rows
    mask = np.ones(KW, bool)
    outs = sf.score_fuse(*kernel_args(t3, prices, vcpus, mems, mask, True,
                                      64.0, 0.1, 0.5), tile=TILE, backend="lax")
    np.testing.assert_array_equal(np.asarray(outs[1]), np.zeros(KW))
    assert_matches_oracle(outs, t3, prices, vcpus, mems, mask, True,
                          64.0, 0.1, 0.5)


def test_extrema_short_circuit_is_bitwise():
    """Phase 0 with precomputed bounds must not perturb a single bit."""
    t3, prices, vcpus, mems = instance(6)
    rng = np.random.default_rng(6)
    mask = rng.random(KW) < 0.6
    mask[0] = True
    args = kernel_args(t3, prices, vcpus, mems, mask, True, 200.0, 0.15, 0.4)
    lo, hi = sf.stat_extrema(args[0], args[1], args[2], args[6], tile=TILE)
    for backend, interpret, tile in (("lax", None, TILE),
                                     ("pallas", True, PALLAS_TILE)):
        full = sf.score_fuse(*args, tile=tile, backend=backend,
                             interpret=interpret)
        short = sf.score_fuse(*args, extrema=(lo, hi), tile=tile,
                              backend=backend, interpret=interpret)
        for a, b in zip(full, short):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cost_floor_short_circuit_is_bitwise():
    """Phase 0 with a precomputed C_min (the sharded merge's carry) must
    reproduce the in-kernel masked min bit for bit — and a sharded
    min-merge of per-slice ``cost_min`` calls must equal the full-axis
    scalar exactly (min is associative and rounding-free)."""
    t3, prices, vcpus, mems = instance(9)
    rng = np.random.default_rng(9)
    mask = rng.random(KW) < 0.6
    mask[0] = True
    args = kernel_args(t3, prices, vcpus, mems, mask, True, 200.0, 0.15, 0.4)
    floor = sf.cost_min(args[3], args[4], args[5], args[6], True, 200.0)
    # per-slice mins merged == full-axis min, bitwise
    cut = KW // 3
    merged = np.minimum(
        np.asarray(sf.cost_min(args[3][:cut], args[4][:cut], args[5][:cut],
                               args[6][:cut], True, 200.0)),
        np.asarray(sf.cost_min(args[3][cut:], args[4][cut:], args[5][cut:],
                               args[6][cut:], True, 200.0)))
    np.testing.assert_array_equal(np.asarray(floor), merged)
    lo, hi = sf.stat_extrema(args[0], args[1], args[2], args[6], tile=TILE)
    for backend, interpret, tile in (("lax", None, TILE),
                                     ("pallas", True, PALLAS_TILE)):
        full = sf.score_fuse(*args, tile=tile, backend=backend,
                             interpret=interpret)
        short = sf.score_fuse(*args, extrema=(lo, hi), cost_floor=floor,
                              tile=tile, backend=backend, interpret=interpret)
        for a, b in zip(full, short):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# K = 8 * PALLAS_TILE + 3: not a multiple of the block, 9 row blocks.
@pytest.mark.parametrize("k,seed", [(7, 0), (PALLAS_TILE, 1),
                                    (PALLAS_TILE + 5, 2), (2 * PALLAS_TILE, 3),
                                    (8 * PALLAS_TILE + 3, 4)])
def test_pallas_interpret_matches_lax(k, seed):
    rng = np.random.default_rng(seed)
    t3, prices, vcpus, mems = instance(seed, k)
    mask = rng.random(k) < 0.7
    mask[0] = True
    args = kernel_args(t3, prices, vcpus, mems, mask, bool(seed % 2),
                       96.0, 0.1, 0.5)
    lax_out = sf.score_fuse(*args, tile=TILE, backend="lax")
    pal_out = sf.score_fuse(*args, tile=PALLAS_TILE, backend="pallas",
                            interpret=True)
    for a, b in zip(lax_out, pal_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL)
    assert_matches_oracle(pal_out, t3, prices, vcpus, mems, mask,
                          bool(seed % 2), 96.0, 0.1, 0.5)


def test_vmapped_matches_per_lane():
    rng = np.random.default_rng(9)
    B = 5
    t3, prices, vcpus, mems = instance(9)
    masks = rng.random((B, KW)) < 0.7
    masks[:, 0] = True
    ucs = rng.random(B) < 0.5
    reqs = rng.uniform(32, 512, B).astype(np.float32)
    lams = rng.uniform(0.05, 0.3, B).astype(np.float32)
    wts = rng.uniform(0.1, 0.9, B).astype(np.float32)
    area, slope, std = scoring.candidate_stats(jnp.asarray(t3))
    shared = (jnp.asarray(prices, jnp.float32),
              jnp.asarray(vcpus, jnp.float32),
              jnp.asarray(mems, jnp.float32))
    fn = functools.partial(sf.score_fuse, tile=TILE, backend="lax")
    batched = jax.jit(jax.vmap(
        lambda m, uc, r, l, w: fn(area, slope, std, *shared, m, uc, r, l, w)
    ))(jnp.asarray(masks), jnp.asarray(ucs), jnp.asarray(reqs),
       jnp.asarray(lams), jnp.asarray(wts))
    for b in range(B):
        single = fn(area, slope, std, *shared, jnp.asarray(masks[b]),
                    jnp.asarray(ucs[b]), jnp.float32(reqs[b]),
                    jnp.float32(lams[b]), jnp.float32(wts[b]))
        # vmapped and single-lane compilations FMA-contract the emission
        # chain differently; agreement is ulp-level, not bitwise.
        for x, y in zip(batched, single):
            np.testing.assert_allclose(np.asarray(x)[b], np.asarray(y),
                                       rtol=RTOL, atol=ATOL)


def test_pallas_interpret_vmapped_resets_carry_per_row():
    """Batching the kernel adds a grid axis; the phase-0 SMEM carry (three
    stat extrema + C_min) must restart on every row.  Row 0 sees every
    candidate, rows 1-2 narrow masks: extrema or a C_min leaking from row 0
    would renormalise rows 1-2 far outside the ulp budget."""
    k = 3 * PALLAS_TILE + 11
    rng = np.random.default_rng(12)
    t3, prices, vcpus, mems = instance(12, k)
    masks = np.ones((3, k), bool)
    masks[1] = rng.random(k) < 0.2
    masks[2, : k // 2] = False
    masks[1, 5] = True
    ucs = np.array([True, False, True])
    reqs = np.array([96.0, 640.0, 32.0], np.float32)
    area, slope, std = scoring.candidate_stats(jnp.asarray(t3))
    shared = (jnp.asarray(prices, jnp.float32),
              jnp.asarray(vcpus, jnp.float32), jnp.asarray(mems, jnp.float32))
    fn = functools.partial(sf.score_fuse, tile=PALLAS_TILE, backend="pallas",
                           interpret=True)
    batched = jax.vmap(
        lambda m, uc, r: fn(area, slope, std, *shared, m, uc, r,
                            jnp.float32(0.1), jnp.float32(0.5))
    )(jnp.asarray(masks), jnp.asarray(ucs), jnp.asarray(reqs))
    for b in range(3):
        assert_matches_oracle([np.asarray(x)[b] for x in batched], t3,
                              prices, vcpus, mems, masks[b], bool(ucs[b]),
                              float(reqs[b]), 0.1, 0.5)


def test_x64_pins_float32():
    """Like the dense scoring path, the kernel stays float32 under x64."""
    t3, prices, vcpus, mems = instance(10)
    mask = np.ones(KW, bool)
    args = (t3, prices, vcpus, mems, mask, True, 64.0, 0.1, 0.5)
    base = sf.score_fuse(*kernel_args(*args), tile=TILE, backend="lax")
    with jax.enable_x64():
        x64 = sf.score_fuse(*kernel_args(*args), tile=TILE, backend="lax")
    for a, b in zip(base, x64):
        assert np.asarray(b).dtype == np.float32
        # the x64 flag recompiles the same float32 program; agreement is
        # ulp-level (FMA contraction), the dtype pin is the real contract
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL)


def test_resolve_score_impl():
    assert scoring.resolve_score_impl("dense", 10 ** 6) == "dense"
    assert scoring.resolve_score_impl("tiled", 2) == "tiled"
    auto_k = scoring.SCORE_TILED_AUTO_K
    assert scoring.resolve_score_impl("auto", auto_k - 1) == "dense"
    assert scoring.resolve_score_impl("auto", auto_k) == "tiled"
    with pytest.raises(ValueError, match="score_impl"):
        scoring.resolve_score_impl("sparse", 8)


def test_dedup_masks():
    masks = np.array([[1, 1, 0], [0, 1, 1], [1, 1, 0], [1, 1, 1]], bool)
    uniq, inv = engine_lib._dedup_masks(masks)
    assert uniq.shape[0] == 4                      # 3 unique, padded to 4
    np.testing.assert_array_equal(inv, [0, 1, 0, 2])
    for b in range(4):
        np.testing.assert_array_equal(uniq[inv[b]], masks[b])
    uniq1, inv1 = engine_lib._dedup_masks(np.ones((8, 5), bool))
    assert uniq1.shape[0] == 1 and (inv1 == 0).all()


# ---------------------------------------------------------------------------
# engine-level equivalence: tiled scoring stage vs the per-request path
# ---------------------------------------------------------------------------

def _synth_candidates(seed: int, K: int, T: int = 24) -> CandidateSet:
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


def test_engine_tiled_matches_sequential():
    """Pool bit-identical, scores ulp-tight — the recommend_batch contract,
    now under ``score_impl="tiled"`` with mixed filters (dedup exercised)."""
    cands = _synth_candidates(23, K=70)
    eng = engine_lib.RecommendationEngine(engine_lib.EngineConfig(score_impl="tiled"))
    reqs = [ResourceRequest(cpus=128.0),
            ResourceRequest(memory_gb=256.0, weight=0.8),
            ResourceRequest(cpus=96.0, weight=0.0, lam=0.3),
            ResourceRequest(cpus=64.0, regions=[str(cands.regions[0])]),
            ResourceRequest(cpus=200.0, max_types=2),
            ResourceRequest(cpus=500.0, weight=1.0),
            ResourceRequest(memory_gb=48.0, weight=0.9, families=["c5", "r5"])]
    for req, bat in zip(reqs, eng.recommend_batch(cands, reqs)):
        seq = eng.recommend(cands, req)
        assert list(seq.names) == list(bat.names)
        np.testing.assert_array_equal(seq.counts, bat.counts)
        assert seq.hourly_cost == bat.hourly_cost
        assert (seq.diagnostics["greedy_iterations"]
                == bat.diagnostics["greedy_iterations"])
        for a, b in ((seq.combined, bat.combined),
                     (seq.availability, bat.availability),
                     (seq.cost, bat.cost)):
            np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)


def test_engine_archive_stats_cache_is_bitwise():
    """Cached-stats batches must equal inline-stats batches bit-for-bit."""
    from repro.serve import DeviceArchive
    cands = _synth_candidates(29, K=40)
    eng = engine_lib.RecommendationEngine(engine_lib.EngineConfig(score_impl="tiled"))
    reqs = [ResourceRequest(cpus=100.0), ResourceRequest(memory_gb=64.0)]
    arch = DeviceArchive.stage(cands)
    plain = eng.recommend_batch(cands, reqs)
    cached = eng.recommend_batch(cands, reqs, archive=arch)
    again = eng.recommend_batch(cands, reqs, archive=arch)   # memoised stats
    for a, b in zip(plain, cached):
        assert list(a.names) == list(b.names)
        np.testing.assert_array_equal(a.counts, b.counts)
        np.testing.assert_array_equal(a.combined, b.combined)
        np.testing.assert_array_equal(a.availability, b.availability)
        np.testing.assert_array_equal(a.cost, b.cost)
    for a, b in zip(cached, again):
        np.testing.assert_array_equal(a.combined, b.combined)


def test_apply_max_types_zero_scores_equal_allocation():
    """All-zero kept scores: equal split instead of 0/0 NaN counts."""
    idx = np.array([4, 1, 7])
    counts = np.array([3, 2, 1])
    comb = np.zeros(10)
    caps = np.full(10, 8.0)
    keep, cnt = engine_lib._apply_max_types(idx, counts, comb, caps,
                                            amount=96.0, max_types=2)
    np.testing.assert_array_equal(keep, [4, 1])
    np.testing.assert_array_equal(cnt, [6, 6])     # ceil(48 / 8) each
    assert not np.isnan(cnt).any()


def test_availability_single_sample_no_nan():
    """T == 1: the regression-slope denominator is 0; slope must be 0."""
    t3 = np.array([[5.0], [10.0], [0.0]])
    s = np.asarray(scoring.availability_scores(t3))
    assert np.isfinite(s).all()
    comp = scoring.availability_scores(t3, return_components=True)
    np.testing.assert_array_equal(np.asarray(comp.slope), np.zeros(3))
    ref = scoring.availability_scores_ref(t3)
    assert np.isfinite(ref).all()
    stats = scoring.candidate_stats(t3)
    np.testing.assert_array_equal(np.asarray(stats.slope), np.zeros(3))
