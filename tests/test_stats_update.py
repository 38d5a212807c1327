"""Incremental candidate-statistics kernel (``repro.kernels.stats_update``).

Contract: after any sequence of append/evict ticks, the rank-1-updated
moments derive :class:`CandidateStats` matching ``scoring.candidate_stats``
of the materialized window at float32-ulp tolerance — and keep matching over
long streams (the compensated accumulators bound the drift).  The Pallas
kernel and the vectorized fallback share the tile math; their resolved
moments and derived statistics agree to the same budget (XLA FMA-contracts
the compensation chains differently per compilation, so bitwise equality is
only guaranteed for the primary sums).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import scoring
from repro.kernels import stats_update as su
from repro.parallel import compression as comp

RTOL = 1e-5
ATOL = 1e-4


def _assert_stats_close(got, want):
    for name, a, b in zip(("area", "slope", "std"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=RTOL, atol=ATOL, err_msg=name)


def _slide(win, col):
    return np.concatenate([win[:, 1:], np.asarray(col)[:, None]], axis=1)


@pytest.mark.parametrize("K", [1, 3, 127, 1024, 1030])
def test_slide_matches_recompute(K):
    rng = np.random.default_rng(K)
    T = 29
    win = rng.uniform(0.0, 50.0, (K, T))
    m = su.moments_from_window(win)
    for i in range(7):
        col = rng.uniform(0.0, 50.0, K)
        y_old = win[:, 0]
        win = _slide(win, col)
        m, stats = su.stats_update(m, col, y_old, win[:, 0], win[:, -1],
                                   T, True)
        _assert_stats_close(stats, scoring.candidate_stats(win))


def test_growing_window_matches_recompute():
    rng = np.random.default_rng(0)
    K = 64
    series = rng.uniform(0.0, 50.0, (K, 24))
    win = series[:, :1]
    m = su.moments_from_window(win)
    for t in range(1, 24):
        col = series[:, t]
        win = np.concatenate([win, col[:, None]], axis=1)
        # y_old must be ignored when evict=False: pass garbage to prove it
        m, stats = su.stats_update(m, col, col * 17.0 + 3.0,
                                   win[:, 0], win[:, -1], t + 1, False)
        _assert_stats_close(stats, scoring.candidate_stats(win))


def test_long_stream_no_drift():
    """2000 sliding ticks: compensated moments keep ulp-level agreement."""
    rng = np.random.default_rng(5)
    K, T = 37, 101
    win = rng.uniform(0.0, 50.0, (K, T))
    m = su.moments_from_window(win)
    for i in range(2000):
        col = rng.uniform(0.0, 50.0, K)
        y_old = win[:, 0]
        win = _slide(win, col)
        m, stats = su.stats_update(m, col, y_old, win[:, 0], win[:, -1],
                                   T, True)
    _assert_stats_close(stats, scoring.candidate_stats(win))
    # the resolved moments themselves are still tight against exact float64
    win64 = win.astype(np.float64)
    idx = np.arange(T, dtype=np.float64)
    d64 = win64 - np.asarray(m.ref, np.float64)[:, None]
    for got, want in ((m.s0 + m.s0c, win64.sum(-1)),
                      (m.s1 + m.s1c, win64 @ idx),
                      (m.q + m.qc, (d64 * d64).sum(-1))):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.parametrize("backend,ticks,kwargs", [
    ("vec", 2000, {}),
    ("pallas", 50, {"interpret": True, "tile": 128}),
])
def test_quantized_long_stream_no_drift(backend, ticks, kwargs):
    """Quantized tier over a long sliding stream: the fused
    dequantize-and-update path keeps tracking ``candidate_stats`` of the
    *dequantized* stored window at the float32 tier's ulp budget — on both
    the vectorized lane (2000 ticks) and the Pallas kernel in interpret
    mode (50 ticks — the tile math is shared, interpret is just slow)."""
    rng = np.random.default_rng(6)
    K, T = 37, 101
    # A fixed scale derived from the value ceiling: U(0, 50) draws can
    # never clip, so every tick stays inside the error-bound contract.
    scale = comp.candidate_scales(np.full((K, 1), 50.0), "int8")
    win = rng.uniform(0.0, 50.0, (K, T))
    codes = comp.quantize_window(win, scale, "int8")
    m = su.moments_from_window(codes, scale=scale)
    for _ in range(ticks):
        new, n_clip = comp.quantize_column(rng.uniform(0.0, 50.0, K), scale,
                                           "int8")
        y_old = codes[:, 0]
        codes = _slide(codes, new)
        m, stats = su.stats_update(m, new, y_old, codes[:, 0], codes[:, -1],
                                   T, True, scale=scale, backend=backend,
                                   **kwargs)
    assert n_clip == 0
    deq = np.asarray(comp.dequantize_window(codes, scale, "int8"))
    _assert_stats_close(stats, scoring.candidate_stats(deq))
    # and against exact float64 reductions of the decoded window
    win64 = deq.astype(np.float64)
    idx = np.arange(T, dtype=np.float64)
    d64 = win64 - np.asarray(m.ref, np.float64)[:, None]
    for got, want in ((m.s0 + m.s0c, win64.sum(-1)),
                      (m.s1 + m.s1c, win64 @ idx),
                      (m.q + m.qc, (d64 * d64).sum(-1))):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_integer_valued_t3_is_near_exact():
    """Collector T3 series are small ints — sums stay exactly representable."""
    rng = np.random.default_rng(9)
    K, T = 50, 40
    win = rng.integers(0, 51, (K, T)).astype(np.float64)
    m = su.moments_from_window(win)
    for _ in range(50):
        col = rng.integers(0, 51, K).astype(np.float64)
        y_old = win[:, 0]
        win = _slide(win, col)
        m, stats = su.stats_update(m, col, y_old, win[:, 0], win[:, -1],
                                   T, True)
    ref = scoring.candidate_stats(win)
    np.testing.assert_array_equal(np.asarray(stats.area), np.asarray(ref.area))
    _assert_stats_close(stats, ref)


def test_flat_rows_keep_exact_zero_std():
    """A constant T3 row must report std == 0.0 exactly through any number
    of ticks — the ref-centered second moment never leaves zero, so the
    MinMax across candidates can't be polluted by cancellation noise."""
    K, T = 8, 50
    win = np.full((K, T), 7.0)
    m = su.moments_from_window(win)
    for _ in range(25):
        m, stats = su.stats_update(m, win[:, 0], win[:, 0], win[:, 0],
                                   win[:, 0], T, True)
        np.testing.assert_array_equal(np.asarray(stats.std), np.zeros(K))
        np.testing.assert_array_equal(np.asarray(stats.slope), np.zeros(K))


# Pallas blocks are (tile // 128, 128) rows: with tile=128, K=256 is two
# whole blocks and K=300 three with a ragged last one.
@pytest.mark.parametrize("K", [5, 256, 300])
def test_pallas_interpret_matches_vec(K):
    rng = np.random.default_rng(K + 1)
    T = 17
    win = rng.uniform(0.0, 50.0, (K, T))
    m = su.moments_from_window(win)
    col = rng.uniform(0.0, 50.0, K)
    slid = _slide(win, col)
    args = (m, col, win[:, 0], slid[:, 0], slid[:, -1], T, True)
    mv, sv = su.stats_update(*args, backend="vec")
    mp, sp = su.stats_update(*args, backend="pallas", interpret=True,
                             tile=128)
    # primary sums are bitwise; compensations differ by FMA contraction only
    for a, b in ((mv.s0, mp.s0), (mv.s1, mp.s1), (mv.q, mp.q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in ((mv.s0 + mv.s0c, mp.s0 + mp.s0c),
                 (mv.s1 + mv.s1c, mp.s1 + mp.s1c),
                 (mv.q + mv.qc, mp.q + mp.qc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-4)
    _assert_stats_close(sp, sv)
    _assert_stats_close(sp, scoring.candidate_stats(slid))


def test_single_column_window_conventions():
    """T == 1: area is the half-weighted sample, slope 0, std 0."""
    y = np.array([[4.0], [0.0], [36.0]])
    m = su.moments_from_window(y)
    col = np.array([8.0, 2.0, 6.0])
    win = np.concatenate([y, col[:, None]], axis=1)
    m, stats = su.stats_update(m, col, col, win[:, 0], win[:, -1], 2, False)
    _assert_stats_close(stats, scoring.candidate_stats(win))
    # and the derivation helper alone honors the T == 1 half-weight
    one = scoring.stats_from_moments(
        jnp.asarray(y[:, 0]), jnp.zeros(3), jnp.asarray(y[:, 0] ** 2),
        jnp.asarray(y[:, 0]), jnp.asarray(y[:, 0]), 1.0)
    np.testing.assert_allclose(np.asarray(one.area), 0.5 * y[:, 0])
    np.testing.assert_array_equal(np.asarray(one.slope), np.zeros(3))
    np.testing.assert_array_equal(np.asarray(one.std), np.zeros(3))


def test_float32_pin_under_x64():
    """Like the scoring path, the kernel stays float32 under x64 mode."""
    rng = np.random.default_rng(2)
    win = rng.uniform(0.0, 50.0, (9, 11))
    col = rng.uniform(0.0, 50.0, 9)
    slid = _slide(win, col)
    jax.config.update("jax_enable_x64", True)
    try:
        m = su.moments_from_window(win)
        m, stats = su.stats_update(m, col, win[:, 0], slid[:, 0],
                                   slid[:, -1], 11, True)
        assert all(a.dtype == jnp.float32 for a in m)
        assert all(a.dtype == jnp.float32 for a in stats)
    finally:
        jax.config.update("jax_enable_x64", False)
    _assert_stats_close(stats, scoring.candidate_stats(slid))


def test_jit_traceable():
    rng = np.random.default_rng(3)
    K, T = 33, 13
    win = rng.uniform(0.0, 50.0, (K, T))
    m = su.moments_from_window(win)
    col = jnp.asarray(rng.uniform(0.0, 50.0, K), jnp.float32)
    slid = _slide(win, np.asarray(col))

    @jax.jit
    def step(m, col, y_old, y_first, y_last):
        return su.stats_update(m, col, y_old, y_first, y_last,
                               jnp.float32(T), jnp.asarray(True))

    m2, stats = step(m, col, jnp.asarray(win[:, 0], jnp.float32),
                     jnp.asarray(slid[:, 0], jnp.float32), col)
    _assert_stats_close(stats, scoring.candidate_stats(slid))
