"""Shared helpers for the pool-scan parity suites (test_pool.py /
test_pool_scan.py): one jitted masked entry point and one adversarial
instance generator, so both files exercise identical inputs."""
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import pool as pool_lib

TILE = 16          # small test tile: the fixed lane width spans several tiles
KW = 3 * TILE      # fixed width -> one compiled shape for every example
# Pallas blocks are (tile // 128, 128) row views, so the smallest Pallas
# tile is one row of 128 lanes.
PALLAS_TILE = 128


@functools.partial(jax.jit, static_argnames=("impl", "tile"))
def masked_pool(scores, cpus, required, mask, *, impl, tile=None):
    return pool_lib.greedy_pool_masked(scores, cpus, required, mask,
                                       impl=impl, tile=tile)


def adversarial_instance(seed: int, n_dup: int, zero_tail: int,
                         neg_tail: int = 0):
    """Full-width (KW,) arrays: duplicate scores, zero/negative tails."""
    rng = np.random.default_rng(seed)
    scores = rng.uniform(0.1, 100.0, KW)
    for _ in range(n_dup):
        i, j = rng.integers(0, KW, 2)
        scores[i] = scores[j]
    if zero_tail:
        scores[KW - zero_tail:] = 0.0
    if neg_tail:
        scores[KW - neg_tail:] = -rng.uniform(0.1, 10.0, neg_tail)
    cpus = rng.choice([2, 4, 8, 16, 32, 48, 64, 96], KW).astype(float)
    return scores, cpus


def random_mask(seed: int, n_valid: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    mask = np.zeros(KW, bool)
    mask[rng.choice(KW, size=n_valid, replace=False)] = True
    return mask


def as_jax(scores, cpus, required, mask):
    return (jnp.asarray(scores, jnp.float32), jnp.asarray(cpus, jnp.float32),
            jnp.float32(required), jnp.asarray(mask))
