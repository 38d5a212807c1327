"""Tiled pool-scan parity: the streaming kernel vs the greedy_pool oracle
and the dense all-prefix scan, on deterministic adversarial cases.

The contract (see ``repro.kernels.pool_scan``): for every implementation
switch — dense, lax-tiled, Pallas-interpret — the *pool output* (member
order, node counts, termination index/flag) is identical.  Deterministic
surface here: all-masked and single-candidate lanes, K exactly on a tile
boundary, vmapped lanes, and the x64 dtype path.  The hypothesis-driven
adversarial sweep (duplicate scores, zero/negative tails, random masks)
lives in ``test_pool.py`` behind its importorskip guard.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pool as pool_lib
from repro.kernels import pool_scan as pool_scan_lib

from _pool_helpers import (KW, PALLAS_TILE, TILE, adversarial_instance,
                           as_jax, gathered_pool, masked_pool)


def test_all_masked_row_matches_dense():
    scores, cpus = adversarial_instance(0, 0, 0)
    args = as_jax(scores, cpus, 64.0, np.zeros(KW, bool))
    dense = jax.device_get(masked_pool(*args, impl="dense"))
    tiled = jax.device_get(masked_pool(*args, impl="tiled", tile=TILE))
    for a, b in zip(dense, tiled):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the one sort carries scores, capacities and mask: same pools as gathering
# them by the argsort's order, and no gather left in the batched program
# ---------------------------------------------------------------------------

def _sort_case(name):
    """(scores, cpus, mask) of one ordering edge case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    k = 2 * PALLAS_TILE if name == "tile_boundary" else KW
    scores = rng.uniform(0.1, 100.0, k)
    cpus = rng.choice([2, 4, 8, 16, 32, 48, 64, 96], k).astype(float)
    mask = rng.random(k) < 0.7
    if name == "heavy_ties":
        scores = rng.choice([1.0, 2.5, 4.0], k)
    elif name == "masked_tail":
        mask[k // 2:] = False
        scores[k // 2:] = 1e3              # would lead the order unmasked
    elif name == "single_valid":
        mask = np.zeros(k, bool)
        mask[k // 3] = True
    elif name == "nan_score":
        mask[5] = True
        scores[5] = np.nan
    return scores, cpus, mask


SORT_CASES = ("heavy_ties", "masked_tail", "single_valid", "tile_boundary",
              "nan_score")


@pytest.mark.parametrize("impl", ["dense", "tiled"])
@pytest.mark.parametrize("case", SORT_CASES)
def test_sort_payloads_match_argsort_gather(case, impl):
    scores, cpus, mask = _sort_case(case)
    args = as_jax(scores, cpus, 129.25, mask)
    got = jax.device_get(masked_pool(*args, impl=impl))
    want = jax.device_get(gathered_pool(*args, impl=impl))
    for x, y in zip(got, want):             # order, counts, k_stop, any_term
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(x, y)
    order, s, c, mask_sorted = jax.device_get(
        jax.jit(pool_lib._sort_candidates)(*args[:2], args[3]))
    np.testing.assert_array_equal(order, got[0])
    np.testing.assert_array_equal(mask_sorted, mask[order])
    np.testing.assert_array_equal(
        s, np.where(mask[order], np.float32(scores)[order], 0.0))
    np.testing.assert_array_equal(
        c, np.where(mask[order], np.float32(cpus)[order], 1.0))


@pytest.mark.parametrize("impl", ["dense", "tiled"])
def test_batched_pool_lowers_without_gather(impl):
    """A gather by per-row sort order under vmap was ~96% of the served
    program's device time; none may come back into the batched pool."""
    B, k = 3, KW
    fn = jax.vmap(functools.partial(pool_lib.greedy_pool_masked, impl=impl,
                                    tile=TILE))
    text = jax.jit(fn).lower(
        jnp.zeros((B, k), jnp.float32), jnp.ones((B, k), jnp.float32),
        jnp.ones((B,), jnp.float32), jnp.ones((B, k), bool)).as_text()
    assert "gather" not in text


# ---------------------------------------------------------------------------
# unmasked entry points: tile boundaries, single candidate, vectorized facade
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, TILE - 1, TILE, TILE + 1, 2 * TILE, KW])
def test_tile_boundary_matches_oracle(k):
    rng = np.random.default_rng(k)
    scores = rng.uniform(0.1, 100.0, k)
    cpus = rng.choice([2, 4, 8, 16, 32], k).astype(float)
    for req in (4.0, 129.25, 1000.0):
        oracle = pool_lib.greedy_pool(scores, cpus, req)
        res = pool_lib.greedy_pool_vectorized(scores, cpus, req, impl="tiled")
        dense = pool_lib.greedy_pool_vectorized(scores, cpus, req, impl="dense")
        assert list(oracle.indices) == list(res.indices)
        assert list(oracle.counts) == list(res.counts)
        # iterations match the dense scan exactly (the oracle's count differs
        # by design when the scan never terminates — argmax of all-False)
        assert dense.iterations == res.iterations


def test_vmapped_tiled_matches_per_lane():
    rng = np.random.default_rng(3)
    B = 5
    S = jnp.asarray(rng.uniform(0.0, 50.0, (B, KW)), jnp.float32)
    C = jnp.asarray(rng.choice([2, 4, 8, 16], (B, KW)).astype(np.float32))
    R = jnp.asarray(rng.uniform(50, 500, B), jnp.float32)
    M = jnp.asarray(rng.random((B, KW)) < 0.7)
    fn = functools.partial(pool_lib.greedy_pool_masked, impl="tiled", tile=TILE)
    batched = jax.device_get(jax.jit(jax.vmap(fn))(S, C, R, M))
    for b in range(B):
        single = jax.device_get(masked_pool(S[b], C[b], R[b], M[b],
                                            impl="tiled", tile=TILE))
        for x, y in zip(batched, single):
            np.testing.assert_array_equal(np.asarray(x)[b], y)


def test_resolve_pool_impl():
    assert pool_lib.resolve_pool_impl("dense", 10 ** 6) == "dense"
    assert pool_lib.resolve_pool_impl("tiled", 2) == "tiled"
    auto_k = pool_lib.POOL_TILED_AUTO_K
    assert pool_lib.resolve_pool_impl("auto", auto_k - 1) == "dense"
    assert pool_lib.resolve_pool_impl("auto", auto_k) == "tiled"
    with pytest.raises(ValueError, match="pool_impl"):
        pool_lib.resolve_pool_impl("sparse", 8)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode) against the dense scan
# ---------------------------------------------------------------------------

def _sorted_instance(k, seed):
    rng = np.random.default_rng(seed)
    s = np.sort(rng.uniform(0.0, 50.0, k))[::-1].copy()
    if k > 4:
        s[-2:] = 0.0                           # zero tail after sorting
    c = rng.choice([2, 4, 8, 16], k).astype(float)
    req = float(rng.integers(16, 2000)) / 4
    return jnp.asarray(s, jnp.float32), jnp.asarray(c, jnp.float32), req


# K = 8 * PALLAS_TILE + 3 is not a multiple of the block: the last row block
# is mostly padding, and with 9 blocks the hit can land anywhere.
@pytest.mark.parametrize("k,seed", [(7, 0), (PALLAS_TILE, 1),
                                    (PALLAS_TILE + 5, 2), (2 * PALLAS_TILE, 3),
                                    (8 * PALLAS_TILE + 3, 4)])
def test_pallas_interpret_matches_dense(k, seed):
    sj, cj, req = _sorted_instance(k, seed)
    dense = jax.device_get(pool_lib._prefix_allocations(
        sj, cj, jnp.float32(req)))
    pallas = jax.device_get(pool_scan_lib._pool_scan_pallas(
        sj, cj, jnp.float32(req), tile=PALLAS_TILE, interpret=True))
    np.testing.assert_array_equal(dense[0], pallas[0])
    assert int(dense[1]) == int(pallas[1])
    assert bool(dense[2]) == bool(pallas[2])


def test_pallas_interpret_vmapped_resets_carry_per_row():
    """Batching the kernel adds a grid axis; the SMEM carry (first
    terminating index, winning prefix sum) must restart on every row.  Row 1
    never terminates and row 2 terminates late, after row 0 terminated early:
    a carry leaking across rows would hand rows 1 and 2 row 0's hit."""
    k = 3 * PALLAS_TILE + 11
    rows = [_sorted_instance(k, seed) for seed in (5, 6, 7)]
    s = jnp.stack([r[0] for r in rows])
    c = jnp.stack([r[1] for r in rows])
    req = jnp.asarray([4.0, 1e9, 3000.0], jnp.float32)
    s = s.at[1].set(jnp.linspace(50.0, 1.0, k, dtype=jnp.float32))
    c = c.at[1].set(1.0)
    fn = functools.partial(pool_scan_lib._pool_scan_pallas, tile=PALLAS_TILE,
                           interpret=True)
    batched = jax.device_get(jax.vmap(fn)(s, c, req))
    stops = []
    for b in range(3):
        dense = jax.device_get(pool_lib._prefix_allocations(s[b], c[b],
                                                            req[b]))
        for x, y in zip(batched, dense):
            np.testing.assert_array_equal(np.asarray(x)[b], y)
        stops.append((int(dense[1]), bool(dense[2])))
    assert not stops[1][1] and stops[0][1]
    assert stops[2][0] > stops[0][0]


# ---------------------------------------------------------------------------
# dtype handling: the vectorized facade must honor jax_enable_x64
# ---------------------------------------------------------------------------

def test_vectorized_honors_x64(monkeypatch):
    seen = {}
    orig = pool_lib._greedy_pool_core

    def spy(scores, cpus, required, **kw):
        seen["dtypes"] = (scores.dtype, cpus.dtype, required.dtype)
        return orig(scores, cpus, required, **kw)

    monkeypatch.setattr(pool_lib, "_greedy_pool_core", spy)
    scores, cpus = np.array([30.0, 20.0, 10.0]), np.array([4.0, 8.0, 16.0])
    oracle = pool_lib.greedy_pool(scores, cpus, 64.0)
    with jax.enable_x64():
        for impl in ("dense", "tiled"):    # both scans must run in float64
            res = pool_lib.greedy_pool_vectorized(scores, cpus, 64.0,
                                                  impl=impl)
            assert seen["dtypes"] == (jnp.float64, jnp.float64, jnp.float64)
            assert list(res.indices) == list(oracle.indices)
            assert list(res.counts) == list(oracle.counts)

    pool_lib.greedy_pool_vectorized(scores, cpus, 64.0)   # default: float32
    assert seen["dtypes"] == (jnp.float32, jnp.float32, jnp.float32)
