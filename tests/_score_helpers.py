"""Shared helpers for the masked-scoring parity suites (test_score_fuse.py /
test_scoring.py): one synthetic-archive generator and the gathered
per-request oracle, so both files exercise identical inputs."""
import numpy as np

import jax.numpy as jnp

from repro.core import scoring

TILE = 16          # small test tile: the fixed lane width spans several tiles
KW = 3 * TILE      # fixed width -> one compiled shape for every example
# Pallas blocks are (tile // 128, 128) row views, so the smallest Pallas
# tile is one row of 128 lanes.
PALLAS_TILE = 128

# scores live at O(100); a float32 ulp there is ~7.6e-6.  Allow a few ulp of
# shape-dependent FMA contraction, same budget as tests/test_serve_batch.py.
RTOL = 1e-5
ATOL = 1e-4


def instance(seed: int, k: int = KW, T: int = 24, *, const_rows: int = 0,
             dup_rows: int = 0):
    """Synthetic archive columns; optionally constant / duplicated T3 rows."""
    rng = np.random.default_rng(seed)
    t3 = rng.uniform(0.0, 50.0, (k, T))
    for _ in range(dup_rows):
        i, j = rng.integers(0, k, 2)
        t3[i] = t3[j]
    if const_rows:
        t3[:const_rows] = t3[:const_rows, :1]      # flat rows: sigma == 0
    prices = rng.uniform(0.01, 5.0, k)
    vcpus = rng.choice([2, 4, 8, 16, 32, 48, 64, 96], k).astype(float)
    mems = rng.choice([4, 8, 16, 64, 128, 384], k).astype(float)
    return t3, prices, vcpus, mems


def kernel_args(t3, prices, vcpus, mems, mask, use_cpus, req, lam, wt):
    area, slope, std = scoring.candidate_stats(jnp.asarray(t3))
    return (area, slope, std, jnp.asarray(prices, jnp.float32),
            jnp.asarray(vcpus, jnp.float32), jnp.asarray(mems, jnp.float32),
            jnp.asarray(mask), jnp.asarray(use_cpus), jnp.float32(req),
            jnp.float32(lam), jnp.float32(wt))


def gathered_oracle(t3, prices, vcpus, mems, mask, use_cpus, req, lam, wt):
    """Per-request scoring of the gathered valid subset (the ``recommend``
    path), returned as (comb, avail, cost) over the valid lanes only."""
    idx = np.flatnonzero(mask)
    caps = (vcpus if use_cpus else mems)[idx]
    avail = np.asarray(scoring.availability_scores(t3[idx], lam))
    cost = np.asarray(scoring.cost_scores(prices[idx], caps, req))
    comb = np.asarray(scoring.combined_scores(avail, cost, wt))
    return comb, avail, cost


def assert_matches_oracle(outs, t3, prices, vcpus, mems, mask, use_cpus,
                          req, lam, wt):
    want = gathered_oracle(t3, prices, vcpus, mems, mask, use_cpus, req,
                           lam, wt)
    idx = np.flatnonzero(mask)
    for got, ref in zip(outs, want):
        np.testing.assert_allclose(np.asarray(got)[idx], ref,
                                   rtol=RTOL, atol=ATOL)
