"""Unit + property tests for the paper's scoring math (Eq. 2-4)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install the [test] extra for property tests")
from hypothesis import example, given, settings, strategies as st  # noqa: E402
import hypothesis.extra.numpy as hnp  # noqa: E402

from repro.core import scoring


def test_fig2_patterns():
    """Figure 2: consistently-high ≈ 100, consistently-low = 0, periodic ≈ 45."""
    T = 64
    t = np.arange(T)
    t3 = np.stack([
        np.full(T, 50.0),                 # (a) consistently high
        np.zeros(T),                      # (b) consistently low
        np.linspace(0, 50, T),            # (c) positive slope
        25 + 25 * np.sin(t),              # (d) periodic
    ])
    s = np.asarray(scoring.availability_scores(t3))
    assert s[0] == pytest.approx(100.0, abs=2.0)
    assert s[1] == 0.0
    assert 40 <= s[3] <= 50                # paper: 45
    assert s[2] > s[3]                     # positive slope beats periodic


def test_availability_bounds_and_order():
    rng = np.random.default_rng(1)
    t3 = rng.uniform(0, 50, size=(32, 100))
    s = np.asarray(scoring.availability_scores(t3))
    assert (s >= 0).all() and (s <= 110.0 + 1e-3).all()


def test_cost_score_inverse_min_scaling():
    prices = np.array([1.0, 2.0, 4.0])
    cpus = np.array([8.0, 8.0, 8.0])
    cs = np.asarray(scoring.cost_scores(prices, cpus, 64.0))
    assert cs[0] == pytest.approx(100.0)
    assert cs[1] == pytest.approx(50.0)
    assert cs[2] == pytest.approx(25.0)


def test_cost_score_ceil_node_count():
    # 100 cores on 16-core boxes needs 7 nodes, on 48-core boxes 3 nodes
    prices = np.array([1.0, 3.2])
    cpus = np.array([16.0, 48.0])
    cs = np.asarray(scoring.cost_scores(prices, cpus, 100.0))
    # costs: 7*1=7 vs 3*3.2=9.6 -> first is cheapest
    assert cs[0] == pytest.approx(100.0)
    assert cs[1] == pytest.approx(100.0 * 7 / 9.6, rel=1e-5)


def test_combined_weight_extremes():
    av = np.array([10.0, 90.0])
    co = np.array([100.0, 20.0])
    assert np.allclose(scoring.combined_scores(av, co, 0.0), co)
    assert np.allclose(scoring.combined_scores(av, co, 1.0), av)


def _minmax_atol(t3, lam=scoring.DEFAULT_LAMBDA, base=2e-3):
    """Absolute score tolerance of the float32 path against the float64
    reference.

    Eq. 3 MinMax-normalises each statistic across candidates, mapping any
    non-zero range onto [0, 1].  Where a statistic's range is no larger
    than its rounding error, the normalised component is rounding noise
    blown up to full scale, in either precision: the float32 path rounds
    its inputs (and flushes those below the float32 normal range to 0),
    the float64 reference normalises its own ulp-level noise (two flat rows
    have slopes that differ by ~1e-17).  The std squares its deviations,
    which underflow in float32 below the square root of its smallest normal
    number.  So each component may be off by its statistic's float32
    rounding error over the statistic's range, capped at the whole [0, 1],
    and the score by those errors weighted as Eq. 3 weighs them.  Where that
    bound is below ``base`` (well-conditioned archives) the tolerance stays
    ``base``.
    """
    t3 = np.asarray(t3, np.float64)
    T = t3.shape[-1]
    f32 = np.finfo(np.float32)
    # worst-case float32 error of a T-term sum of samples up to max|t3|
    unit = T * (f32.eps * np.abs(t3).max() + np.sqrt(f32.tiny))
    t = np.arange(T) - (T - 1) / 2.0
    stats = (np.trapezoid(t3, axis=-1),
             (t3 - t3.mean(-1, keepdims=True)) @ t / max(t @ t, 1.0),
             t3.std(-1))
    frac = [min(1.0, err / r) if r > err else 1.0
            for err, r in zip((T * unit, unit, unit),
                              (np.ptp(x) for x in stats))]
    return max(base,
               100.0 * ((1 + lam) * frac[0] + lam * (frac[1] + frac[2])))


@settings(max_examples=50, deadline=None)
@given(hnp.arrays(np.float64, hnp.array_shapes(min_dims=2, max_dims=2,
                                               min_side=2, max_side=16),
                  elements=st.floats(0, 50)))
# underflow: every sample is below the float32 range, so the device holds
# an all-zero (flat) archive while the reference normalises the tiny values
@example(np.array([[0.0, 7.44872264e-203], [7.44872264e-203, 7.44872264e-203]]))
# flat rows: the reference's float64 slope/std noise gets MinMax-normalised
@example(np.array([[0.7] * 7, [1.1] * 7]))
def test_jax_matches_numpy_reference(t3):
    got = np.asarray(scoring.availability_scores(t3))
    want = scoring.availability_scores_ref(t3)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=_minmax_atol(t3))


def test_minmax_atol_is_tight_on_resolved_ranges():
    """The conditioning term vanishes where every range is resolved: a
    well-spread archive of the property's size is held to the base
    tolerance."""
    rng = np.random.default_rng(4)
    t3 = rng.uniform(0.0, 50.0, (16, 16))
    assert _minmax_atol(t3) < 2.1e-3
    np.testing.assert_allclose(scoring.availability_scores(t3),
                               scoring.availability_scores_ref(t3),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=50, deadline=None)
@given(st.integers(2, 20), st.floats(1, 2000), st.integers(0, 2 ** 31))
def test_cost_ref_property(k, req, seed):
    rng = np.random.default_rng(seed)
    prices = rng.uniform(0.01, 10, k)
    cpus = rng.choice([2, 4, 8, 16, 32, 48, 64, 96], k).astype(float)
    got = np.asarray(scoring.cost_scores(prices, cpus, req))
    want = scoring.cost_scores_ref(prices, cpus, req)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert got.max() == pytest.approx(100.0, rel=1e-5)  # cheapest gets 100


def test_lambda_bounds_adjustment():
    """λ bounds trend/volatility influence to ±λ·100% (§4.2)."""
    rng = np.random.default_rng(2)
    t3 = rng.uniform(0, 50, (16, 50))
    comp = scoring.availability_scores(t3, lam=0.1, return_components=True)
    base = np.asarray(100.0 * comp.a3)
    adj = np.asarray(comp.score)
    assert (np.abs(adj - base) <= 0.1 * base + 1e-4).all()


# ---------------------------------------------------------------------------
# Streaming masked-scoring kernel: adversarial parity with the gathered
# per-request oracle (see repro.kernels.score_fuse; helpers shared with
# test_score_fuse.py via _score_helpers).
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import score_fuse as sf  # noqa: E402

from _score_helpers import (KW as _KW, TILE as _TILE,  # noqa: E402
                            assert_matches_oracle, gathered_oracle, instance,
                            kernel_args)


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2 ** 31), mask_seed=st.integers(0, 2 ** 31),
       n_valid=st.integers(1, _KW), dup_rows=st.integers(0, _KW),
       const_rows=st.integers(0, _KW), use_cpus=st.booleans(),
       req=st.integers(32, 6000).map(lambda x: x / 4),
       lam=st.integers(0, 50).map(lambda x: x / 100),
       wt=st.integers(0, 100).map(lambda x: x / 100))
def test_masked_tiled_matches_gathered_oracle(seed, mask_seed, n_valid,
                                              dup_rows, const_rows, use_cpus,
                                              req, lam, wt):
    # req on quarter-integers: floats sitting exactly on a ceil() boundary
    # can legitimately round differently between float64 and float32 paths.
    # Duplicate and constant T3 rows produce duplicate / degenerate stats
    # (MinMax ties and the rng == 0 branch); n_valid == 1 exercises the
    # all-stats-degenerate single-lane case.
    t3, prices, vcpus, mems = instance(seed, dup_rows=dup_rows,
                                       const_rows=const_rows)
    rng = np.random.default_rng(mask_seed)
    mask = np.zeros(_KW, bool)
    mask[rng.choice(_KW, size=n_valid, replace=False)] = True
    outs = sf.score_fuse(*kernel_args(t3, prices, vcpus, mems, mask,
                                      use_cpus, req, lam, wt),
                         tile=_TILE, backend="lax")
    assert_matches_oracle(outs, t3, prices, vcpus, mems, mask, use_cpus,
                          req, lam, wt)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 31), mask_seed=st.integers(0, 2 ** 31),
       n_valid=st.integers(1, _KW),
       req=st.integers(32, 6000).map(lambda x: x / 4))
def test_tiled_pools_bit_identical_to_oracle(seed, mask_seed, n_valid, req):
    """Pools formed from the streamed combined scores must match the
    gathered-subset Algorithm 1 loop oracle exactly."""
    from repro.core import pool as pool_lib
    t3, prices, vcpus, mems = instance(seed)
    rng = np.random.default_rng(mask_seed)
    mask = np.zeros(_KW, bool)
    mask[rng.choice(_KW, size=n_valid, replace=False)] = True
    comb, _, _ = sf.score_fuse(*kernel_args(t3, prices, vcpus, mems, mask,
                                            True, req, 0.1, 0.5),
                               tile=_TILE, backend="lax")
    order, counts, _, _ = jax.device_get(pool_lib.greedy_pool_masked(
        jnp.asarray(comb), jnp.asarray(vcpus, jnp.float32),
        jnp.float32(req), jnp.asarray(mask), impl="tiled", tile=_TILE))
    sel = counts > 0
    valid = np.flatnonzero(mask)
    comb_g, _, _ = gathered_oracle(t3, prices, vcpus, mems, mask, True,
                                   req, 0.1, 0.5)
    oracle = pool_lib.greedy_pool(comb_g, vcpus[valid], req)
    assert list(valid[oracle.indices]) == list(np.asarray(order)[sel])
    assert list(oracle.counts) == list(np.asarray(counts)[sel])
