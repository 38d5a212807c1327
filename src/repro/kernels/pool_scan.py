"""Tiled pool-scan kernel: Algorithm 1's all-prefix termination scan in
O(K + TILE) memory instead of the dense K x K allocation matrix.

The dense production path (``core.pool._prefix_allocations``) materializes

    X[k, j] = ceil( s_j * R / (cumsum(s)[k] * c_j) )        for j <= k

for every prefix k at once — an O(K^2) buffer (B x K x K under the batched
engine's vmap), which caps the candidate fan-out per dispatch.  But the two
termination statistics Algorithm 1 actually inspects are one column and the
diagonal of X::

    top[k]    = X[k, 0]   — depends only on s_0, c_0 and cumsum(s)[k]
    newest[k] = X[k, k]   — depends only on s_k, c_k and cumsum(s)[k]

so the scan needs the (K,) prefix-sum vector, not the matrix: compute it
once with the *same* ``jnp.cumsum`` (and <=0 clamp) the dense path uses,
stream the termination statistics over K_tile-sized blocks of it, and emit
only the winning prefix's allocation row.  Nothing K x K ever exists;
compute drops from O(K^2) to O(K).  Because every statistic is derived from
the identical prefix-sum values with the identical multiply/divide order,
the pool output is bit-identical to the dense scan by construction — not
merely up to float reassociation.

``top[k - 1]`` is the same expression on the previous prefix's sum, which
the XLA prologue stages as a lagged copy of the prefix sums; so every
statistic of a lane is elementwise and a tile may have any shape.  Two
implementations share that tile math (``_tile_stats``):

- ``_pool_scan_lax``    : the whole row as one tile — a fused elementwise
                          pass plus a min-reduction for the first
                          terminating index, then the winning row.  The
                          CPU/GPU path, vmap-friendly for the batched engine.
- ``_pool_scan_pallas`` : a Pallas TPU kernel, grid ``(2, nt)`` (phase 0:
                          stats scan, phase 1: tiled row emission), carry in
                          SMEM scratch.  Each tile is a (tile // 128, 128)
                          row block of the candidate axis, the layout Mosaic
                          accepts; "first" is the smallest global index, so
                          row-major order is candidate order.  Under ``vmap``
                          the batch becomes the outermost grid axis and the
                          carry restarts at ``(p, t) == (0, 0)`` of each row.

Both return ``(counts_sorted, k_stop, any_term)`` with semantics identical
to the dense scan, so ``core.pool`` can switch implementations behind
``pool_impl`` without perturbing any caller.

K-axis sharding note (``repro.shard``): unlike the scoring stage's phase-0
carries (min/max — associative, rounding-free, mergeable across shards bit
for bit), this scan's carry rides on ``cumsum`` over the *score-descending*
order, which (a) interleaves shards arbitrarily and (b) is float addition —
not associative — so per-shard prefix sums plus an exclusive-scan offset
over shard totals would change the summation order and break the
bit-identical-pool contract every parity suite enforces.  The sharded serve
path therefore gathers the per-shard score rows (O(B·K) scalars — nothing
(K, T)-sized moves) onto one merge device and runs this same scan there on
the same bits; see ``repro.shard.compute`` for the full argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 1024
#: lane width of a TPU vector register: Pallas blocks are (tile // LANES,
#: LANES) row-major views of the candidate axis, so every block's last two
#: dimensions sit on the (8, 128) f32 tiling Mosaic requires.
LANES = 128

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _clamped_prefix_sums(s: jax.Array) -> jax.Array:
    """Exactly the dense scan's prefix-sum staging (op-for-op)."""
    s_tot = jnp.cumsum(s)
    return jnp.where(s_tot > 0, s_tot, 1.0)


def _lagged(csc: jax.Array) -> jax.Array:
    """``csc[max(k - 1, 0)]``: the prefix sum of the previous prefix.

    It feeds both ``top[k - 1]`` (recomputed elementwise from the same
    bits, so no cross-lane shift is needed inside a tile) and the winning
    prefix's sum at ``k_best = max(k_stop - 1, 0)``.
    """
    return jnp.concatenate([csc[:1], csc[:-1]])


def _pad_tiles(arrs, tile: int, pad_values):
    """Reshape (K,) arrays to (nt, tile).  Padded lanes mimic masked
    candidates (score 0, cpu 1, prefix sum 1) and the stats pass excludes
    them from the termination vote, so padding never changes the result."""
    K = arrs[0].shape[0]
    nt = -(-K // tile)
    pad = nt * tile - K
    return [jnp.pad(a, (0, pad), constant_values=v).reshape(nt, tile)
            for a, v in zip(arrs, pad_values)] + [nt]


def _pad_rows(arrs, tile: int, pad_values):
    """Pallas layout: (K,) arrays padded as :func:`_pad_tiles` and viewed as
    (nt * tile // LANES, LANES), so tile ``t`` is the row block ``t`` of
    ``tile // LANES`` rows and row-major order is candidate order."""
    if tile % LANES:
        raise ValueError(f"Pallas tile must be a multiple of {LANES}, "
                         f"got {tile}")
    *tiles, nt = _pad_tiles(arrs, tile, pad_values)
    return [a.reshape(nt * tile // LANES, LANES) for a in tiles] + [nt]


def _block_index(t, tile: int):
    """Global candidate index of every lane of row block ``t``."""
    shape = (tile // LANES, LANES)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return t * tile + row * LANES + lane


def _tile_stats(s_t, c_t, csc_t, lag_t, idx, s0, c0, required, k_total):
    """First terminating prefix index in one tile (``INT32_MAX`` if none).

    Float op order matches the dense scan exactly — ``(s * R) / (s_tot * c)``
    on the shared clamped-cumsum values — which is what makes the streamed
    pool output bit-identical to the dense one.  ``top[k - 1]`` is the same
    expression on ``lag_t`` (the previous prefix's sum), so the statistic
    needs no lane shift and the tile may have any shape: "first" is the
    smallest global index ``idx``, i.e. row-major candidate order.
    """
    top = jnp.ceil(s0 * required / (csc_t * c0)).astype(jnp.int32)
    prev = jnp.ceil(s0 * required / (lag_t * c0)).astype(jnp.int32)
    newest = jnp.ceil(s_t * required / (csc_t * c_t)).astype(jnp.int32)
    # x_prev_top = inf at k = 0, and padded lanes never vote (plain mask
    # logic: Mosaic cannot select between boolean vectors)
    term = ((newest == 0) | ((idx > 0) & (top >= prev))) & (idx < k_total)
    return jnp.min(jnp.where(term, idx, _INT32_MAX))


def _finalize(first, k_total):
    """Dense-scan semantics for the reduction outputs."""
    any_term = first < _INT32_MAX
    k_stop = jnp.where(any_term, first, 0)                # argmax of all-False
    k_best = jnp.where(any_term, jnp.maximum(k_stop - 1, 0), k_total - 1)
    return any_term, k_stop, k_best


def _emit_row(s, c, required, stot_best, k_best, deg, c0, lane):
    row = jnp.ceil(s * required / (stot_best * c)).astype(jnp.int32)
    row = jnp.where(lane <= k_best, row, 0)
    # Degenerate guard (termination at k=0): single-type pool on the leader.
    fb0 = jnp.ceil(required / c0).astype(jnp.int32)
    return jnp.where(deg, jnp.where(lane == 0, fb0, 0), row)


def _pool_scan_lax(s: jax.Array, c: jax.Array, required: jax.Array):
    """``jax.lax`` path: the tile statistics over the whole row at once (one
    fused elementwise pass plus a min-reduction), then the winning row."""
    K = s.shape[0]
    csc = _clamped_prefix_sums(s)
    s0, c0 = s[0], c[0]
    lane = jnp.arange(K, dtype=jnp.int32)
    first = _tile_stats(s, c, csc, _lagged(csc), lane, s0, c0, required, K)
    any_term, k_stop, k_best = _finalize(first, K)
    deg = any_term & (k_stop == 0)
    counts = _emit_row(s, c, required, csc[k_best], k_best, deg, c0, lane)
    return counts, k_stop, any_term


# ---------------------------------------------------------------------------
# Pallas TPU kernel: same tile math, carry in SMEM scratch.
# ---------------------------------------------------------------------------

def _pool_scan_kernel(params_ref, s_ref, c_ref, csc_ref, lag_ref, counts_ref,
                      stats_ref, first_scr, stot_scr, *, tile: int,
                      k_total: int, nt: int):
    p = pl.program_id(0)                                  # 0: stats, 1: emit
    t = pl.program_id(1)
    s0 = params_ref[0, 0]
    c0 = params_ref[0, 1]
    required = params_ref[0, 2]
    idx = _block_index(t, tile)

    @pl.when((p == 0) & (t == 0))
    def _init():
        first_scr[0] = jnp.int32(_INT32_MAX)
        # not-found: the winning prefix is the full set, csc[K - 1]
        stot_scr[0] = params_ref[0, 3]

    @pl.when(p == 0)
    def _stats():
        first = _tile_stats(s_ref[...], c_ref[...], csc_ref[...],
                            lag_ref[...], idx, s0, c0, required, k_total)

        @pl.when((first < _INT32_MAX) & (first_scr[0] == _INT32_MAX))
        def _take():
            first_scr[0] = first
            # csc[k_best] at k_stop = first: the lagged sum on that lane
            # (masked reduce: Mosaic has no dynamic vector indexing).
            stot_scr[0] = jnp.sum(jnp.where(idx == first, lag_ref[...], 0.0))

    @pl.when((p == 0) & (t == nt - 1))
    def _finish():
        any_term, k_stop, _ = _finalize(first_scr[0], k_total)
        stats_ref[0, 0] = k_stop
        stats_ref[0, 1] = any_term.astype(jnp.int32)

    @pl.when(p == 1)
    def _emit():
        any_term, k_stop, k_best = _finalize(first_scr[0], k_total)
        counts_ref[...] = _emit_row(
            s_ref[...], c_ref[...], required, stot_scr[0], k_best,
            any_term & (k_stop == 0), c0, idx)


def _pool_scan_pallas(s: jax.Array, c: jax.Array, required: jax.Array,
                      *, tile: int = DEFAULT_TILE, interpret: bool = False):
    K = s.shape[0]
    csc = _clamped_prefix_sums(s)        # O(K) XLA op, shared with dense
    s_r, c_r, csc_r, lag_r, nt = _pad_rows(
        (s, c, csc, _lagged(csc)), tile, (0, 1, 1, 1))
    params = jnp.stack([s[0], c[0], jnp.asarray(required, s.dtype),
                        csc[K - 1]]).reshape(1, 4)
    rows = tile // LANES
    block = pl.BlockSpec((rows, LANES), lambda p, t: (t, 0))
    counts, stats = pl.pallas_call(
        functools.partial(_pool_scan_kernel, tile=tile, k_total=K, nt=nt),
        grid=(2, nt),
        in_specs=[pl.BlockSpec((1, 4), lambda p, t: (0, 0),
                               memory_space=pltpu.SMEM)] + [block] * 4,
        out_specs=[
            # phase 0 parks on block 0 so nothing unwritten is flushed
            pl.BlockSpec((rows, LANES), lambda p, t: (t * p, 0)),
            pl.BlockSpec((1, 2), lambda p, t: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, 2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1,), jnp.int32),    # first terminating index so far
            pltpu.SMEM((1,), s.dtype),      # prefix sum of winning prefix
        ],
        interpret=interpret,
        name="pool_scan",
    )(params, s_r, c_r, csc_r, lag_r)
    return counts.reshape(nt * tile)[:K], stats[0, 0], stats[0, 1].astype(bool)


def pool_scan(s: jax.Array, c: jax.Array, required, *, tile: int | None = None,
              backend: str | None = None, interpret: bool | None = None):
    """Tiled all-prefix Algorithm 1 scan over pre-sorted ``(s, c)``.

    Drop-in for the dense scan: returns ``(counts_sorted, k_stop, any_term)``
    with identical semantics and bit-identical pool output.  ``backend=None``
    picks the Pallas kernel on TPU and the ``lax`` pass elsewhere;
    ``interpret`` forces the Pallas interpreter (tests).  ``tile`` is the
    Pallas block in candidates, a multiple of 128.  Traceable under
    ``jit`` / ``vmap``.
    """
    tile = DEFAULT_TILE if tile is None else tile
    required = jnp.asarray(required, s.dtype)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "lax"
    if backend == "pallas":
        interp = (jax.default_backend() != "tpu") if interpret is None \
            else interpret
        return _pool_scan_pallas(s, c, required, tile=tile, interpret=interp)
    if backend != "lax":
        raise ValueError(f"unknown pool_scan backend: {backend!r}")
    return _pool_scan_lax(s, c, required)
