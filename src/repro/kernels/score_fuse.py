"""Streaming masked-scoring kernel: the per-request O(K) remainder of
Eq. 2-4 over archive-cached per-candidate statistics.

The batched engine's scoring stage used to evaluate the full Eq. 3 chain
under ``vmap`` for every request.  The (K, T) reductions inside it — raw
trapezoid area, regression slope, std of the T3 series — do not depend on
the request at all, so ``core.scoring.candidate_stats`` now computes them
once per archive (O(K*T)) and the serve layer caches them on the staged
``DeviceArchive``.  What genuinely varies per request is O(K):

    phase 0:  masked min/max of the three statistics (the Eq. 3 MinMax
              bounds) and the masked C_min of Eq. 2 — seven scalars;
    phase 1:  the normalized combined / availability / cost rows (Eq. 4).

This module streams exactly that in K_tile-sized blocks with the same
two-phase schedule as ``pool_scan``:

- ``_score_fuse_lax``    : ``jax.lax.scan`` over (nt, TILE) blocks for the
                           phase-0 extrema (seven scalars of carry), then
                           one fused full-width emission — the CPU/GPU
                           fallback, vmap-friendly for the batched engine.
- ``_score_fuse_pallas`` : a Pallas TPU kernel with the same per-tile math,
                           grid ``(2, nt)`` (phase 0: extrema scan, phase 1:
                           tiled row emission), carry in SMEM scratch, tiles
                           laid out as (tile // 128, 128) row blocks — the
                           ``pool_scan`` idiom.

Both share ``_tile_extrema`` / ``_emit_rows``, whose float op order matches
the dense masked path (``scoring._masked_minmax`` etc.) exactly: min/max
are associative, so the streamed extrema equal the one-shot reductions
bitwise, and the emission is the same elementwise chain — outputs agree
with the gathered per-request oracle to float32-ulp level on valid lanes
(XLA contracts elementwise chains shape-dependently; the cross-candidate
reductions themselves are exact).

``extrema``: the three stat extrema depend only on ``(stats, mask)`` — not
on the request scalars — so the engine deduplicates identical filter masks
across a batch (``stat_extrema`` once per *unique* mask) and passes the
bounds in; the kernel then only streams the masked C_min in phase 0.  A
batch of filterless requests collapses to a single extrema scan.

``cost_floor``: the same exposure for the remaining phase-0 scalar.  Every
carry this kernel accumulates — three stat minima, three maxima, the masked
C_min — is an associative min/max reduction, so a candidate axis split into
S shards can run phase 0 per shard and merge the seven scalars exactly
(bitwise, not merely to tolerance).  The K-sharded serve path
(``repro.shard``) does exactly that: :func:`stat_extrema` + :func:`cost_min`
per shard, an elementwise min/max merge on the host, then per-shard phase-1
emission via ``extrema=`` + ``cost_floor=`` — against merged scalars the
emission is purely elementwise, so each shard's rows equal the
corresponding slice of a single-device dispatch bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pool_scan import LANES, _pad_rows, _pad_tiles

DEFAULT_TILE = 1024


def _masked_min(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.min(jnp.where(mask, x, jnp.inf))


def _masked_max(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.max(jnp.where(mask, x, -jnp.inf))


def _tile_total(prices_t, vcpus_t, mem_t, use_cpus, required):
    """Eq. 2 cost basis C_i = p_i * ceil(R / cap_i) for one tile.

    Same float op order as ``scoring.cost_scores_masked`` (exact division
    inside the ceil — a reciprocal would flip ceil at exact multiples).
    """
    caps = jnp.where(use_cpus, vcpus_t, mem_t)
    return prices_t * jnp.ceil(required / caps)


def _tile_extrema(area_t, slope_t, std_t, mask_t):
    """Masked per-tile (min, max) of the three availability statistics, as
    two tuples of scalars (the kernel keeps each in its own SMEM slot)."""
    stats = (area_t, slope_t, std_t)
    return (tuple(_masked_min(x, mask_t) for x in stats),
            tuple(_masked_max(x, mask_t) for x in stats))


def _minmax_norm(x, lo, hi):
    """Elementwise tail of ``scoring._masked_minmax`` (op-for-op)."""
    rng = hi - lo
    return jnp.where(rng > 0, (x - lo) / jnp.where(rng > 0, rng, 1.0),
                     jnp.zeros_like(x))


def _emit_rows(area, slope, std, total, lo_a, hi_a, lo_m, hi_m, lo_s, hi_s,
               c_min, lam, weight):
    """Phase 1: Eq. 3 normalisation + Eq. 2 scaling + Eq. 4 combine.

    Identical elementwise chains to ``availability_scores_masked`` /
    ``cost_scores_masked`` / ``combined_scores`` on the same scalars.
    """
    a3 = _minmax_norm(area, lo_a, hi_a)
    slope_n = _minmax_norm(slope, lo_m, hi_m)
    sigma_n = _minmax_norm(std, lo_s, hi_s)
    avail = jnp.clip(100.0 * a3 * (1.0 + lam * (slope_n - sigma_n)), 0.0, None)
    cost = 100.0 * c_min / total
    comb = weight * avail + (1.0 - weight) * cost
    return comb, avail, cost


# ---------------------------------------------------------------------------
# lax fallback: tiled phase-0 scan, fused full-width emission.
# ---------------------------------------------------------------------------

def stat_extrema(area: jax.Array, slope: jax.Array, std: jax.Array,
                 mask: jax.Array, *, tile: int | None = None):
    """Masked (min, max) of the three stats, streamed in K-tiles.

    Returns ``(lo, hi)`` of shape (3,) each, ordered (area, slope, std).
    This is phase 0 minus the cost term — the piece the engine computes once
    per *unique* filter mask and shares across the requests that carry it.
    Bitwise equal to the one-shot ``jnp.min/max`` reductions (min/max are
    associative).  Traceable under ``jit`` / ``vmap``.
    """
    tile = DEFAULT_TILE if tile is None else tile
    area = jnp.asarray(area, jnp.float32)
    a_t, m_t, s_t, k_t, nt = _pad_tiles(
        (area, jnp.asarray(slope, jnp.float32), jnp.asarray(std, jnp.float32),
         mask), tile, (0, 0, 0, False))

    def step(carry, xs):
        lo, hi = carry
        a, m, s, k = xs
        t_lo, t_hi = _tile_extrema(a, m, s, k)
        return (jnp.minimum(lo, jnp.stack(t_lo)),
                jnp.maximum(hi, jnp.stack(t_hi))), None

    init = (jnp.full(3, jnp.inf, jnp.float32),
            jnp.full(3, -jnp.inf, jnp.float32))
    (lo, hi), _ = jax.lax.scan(step, init, (a_t, m_t, s_t, k_t))
    return lo, hi


def cost_min(prices, vcpus, memory_gb, mask, use_cpus, required,
             *, tile: int | None = None):
    """Masked Eq. 2 C_min — the request-dependent half of the phase-0 carry.

    Exposed for the K-sharded serve path (``repro.shard``): each shard takes
    the masked min over its local candidates and the merge reduces across
    shards.  Min is associative and rounding-free, so the merged scalar is
    bitwise identical to the single-device masked min — which is what lets
    phase 1 emit per shard (``cost_floor=``) without perturbing a bit.
    Traceable under ``jit`` / ``vmap``; float32-pinned like the kernel.
    """
    del tile  # one-shot reduction; kept for signature symmetry
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    total = _tile_total(f32(prices), f32(vcpus), f32(memory_gb),
                        jnp.asarray(use_cpus, bool), f32(required))
    return _masked_min(total, jnp.asarray(mask, bool))


def _score_fuse_lax(area, slope, std, prices, vcpus, memory_gb, mask,
                    use_cpus, required, lam, weight, extrema=None,
                    cost_floor=None, *, tile: int = DEFAULT_TILE):
    """Streamed scoring for one request: tiled stat scan, fused emission.

    Unlike the Pallas kernel, emission here is one fused full-width pass, so
    the Eq. 2 cost basis is materialised anyway — C_min is a flat masked min
    over it (bit-identical to the tiled scan: min is associative) rather
    than a second pass through the tiles.
    """
    if extrema is None:
        lo, hi = stat_extrema(area, slope, std, mask, tile=tile)
    else:
        lo, hi = extrema
    total = _tile_total(prices, vcpus, memory_gb, use_cpus, required)
    c_min = _masked_min(total, mask) if cost_floor is None else cost_floor
    return _emit_rows(area, slope, std, total, lo[0], hi[0], lo[1], hi[1],
                      lo[2], hi[2], c_min, lam, weight)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: same schedule, extrema carry in SMEM scratch.
# ---------------------------------------------------------------------------

def _score_fuse_kernel(params_ref, a_ref, m_ref, s_ref, p_ref, v_ref, g_ref,
                       k_ref, comb_ref, avail_ref, cost_ref, ext_scr,
                       *, has_extrema: bool, has_cost_floor: bool):
    p = pl.program_id(0)                                 # 0: extrema, 1: emit
    t = pl.program_id(1)
    use_cpus = params_ref[0, 0] > 0
    required = params_ref[0, 1]
    lam = params_ref[0, 2]
    weight = params_ref[0, 3]

    @pl.when((p == 0) & (t == 0))
    def _init():
        # stat extrema slots: precomputed bounds, or +-inf scan sentinels;
        # C_min carry: precomputed floor, or the +inf scan sentinel
        for i in range(7):
            ext_scr[i] = params_ref[0, 4 + i]

    @pl.when(p == 0)
    def _extrema():
        mask_t = k_ref[...] > 0
        if not has_cost_floor:
            total_t = _tile_total(p_ref[...], v_ref[...], g_ref[...],
                                  use_cpus, required)
            ext_scr[6] = jnp.minimum(ext_scr[6], _masked_min(total_t, mask_t))
        if not has_extrema:
            lo, hi = _tile_extrema(a_ref[...], m_ref[...], s_ref[...],
                                   mask_t)
            for i in range(3):
                ext_scr[2 * i] = jnp.minimum(ext_scr[2 * i], lo[i])
                ext_scr[2 * i + 1] = jnp.maximum(ext_scr[2 * i + 1], hi[i])

    @pl.when(p == 1)
    def _emit():
        total_t = _tile_total(p_ref[...], v_ref[...], g_ref[...],
                              use_cpus, required)
        comb, avail, cost = _emit_rows(
            a_ref[...], m_ref[...], s_ref[...], total_t,
            ext_scr[0], ext_scr[1], ext_scr[2], ext_scr[3], ext_scr[4],
            ext_scr[5], ext_scr[6], lam, weight)
        comb_ref[...] = comb
        avail_ref[...] = avail
        cost_ref[...] = cost


def _score_fuse_pallas(area, slope, std, prices, vcpus, memory_gb, mask,
                       use_cpus, required, lam, weight, extrema=None,
                       cost_floor=None, *, tile: int = DEFAULT_TILE,
                       interpret: bool = False):
    K = area.shape[0]
    *cols, nt = _pad_rows(
        (area, slope, std, prices, vcpus, memory_gb,
         mask.astype(jnp.float32)), tile, (0, 0, 0, 1, 1, 1, 0))
    inf = jnp.asarray(jnp.inf, jnp.float32)
    if extrema is None:
        lo, hi = jnp.full(3, inf, jnp.float32), jnp.full(3, -inf, jnp.float32)
    else:
        lo, hi = extrema
    floor = inf if cost_floor is None else jnp.asarray(cost_floor, jnp.float32)
    params = jnp.stack([
        jnp.where(use_cpus, 1.0, 0.0).astype(jnp.float32),
        jnp.asarray(required, jnp.float32), jnp.asarray(lam, jnp.float32),
        jnp.asarray(weight, jnp.float32),
        lo[0], hi[0], lo[1], hi[1], lo[2], hi[2], floor]).reshape(1, 11)
    rows = tile // LANES
    in_block = pl.BlockSpec((rows, LANES), lambda p, t: (t, 0))
    # phase 0 parks the outputs on block 0 so nothing unwritten is flushed
    out_block = pl.BlockSpec((rows, LANES), lambda p, t: (t * p, 0))
    comb, avail, cost = pl.pallas_call(
        functools.partial(_score_fuse_kernel, has_extrema=extrema is not None,
                          has_cost_floor=cost_floor is not None),
        grid=(2, nt),
        in_specs=[pl.BlockSpec((1, 11), lambda p, t: (0, 0),
                               memory_space=pltpu.SMEM)] + [in_block] * 7,
        out_specs=[out_block] * 3,
        out_shape=[jax.ShapeDtypeStruct((nt * rows, LANES), jnp.float32)] * 3,
        scratch_shapes=[pltpu.SMEM((8,), jnp.float32)],
        interpret=interpret,
        name="score_fuse",
    )(params, *cols)
    unpad = lambda x: x.reshape(nt * tile)[:K]  # noqa: E731
    return unpad(comb), unpad(avail), unpad(cost)


def score_fuse(area, slope, std, prices, vcpus, memory_gb, mask, use_cpus,
               required, lam, weight, extrema=None, cost_floor=None,
               *, tile: int | None = None,
               backend: str | None = None, interpret: bool | None = None):
    """Masked Eq. 2-4 for one request from per-candidate raw statistics.

    Returns ``(combined, availability, cost)`` rows of shape (K,) — on valid
    lanes equal to the gathered per-request oracle to float32-ulp level;
    masked-out lanes hold garbage the engine discards downstream.  A mask
    with no valid lane (which the engine rejects before dispatch) yields
    ``cost = +inf`` everywhere and ``combined = NaN`` when ``weight == 1``
    (``1*avail + 0*inf``) — callers invoking the kernel directly must filter
    empty masks themselves.
    ``extrema=(lo, hi)`` short-circuits the stat half of phase 0 with
    precomputed masked bounds (see :func:`stat_extrema`); they must have been
    taken over exactly this ``mask``.  ``cost_floor`` short-circuits the
    remaining phase-0 scalar the same way: a precomputed masked C_min (see
    :func:`cost_min`) used verbatim by the emission.  In the K-sharded path
    it is the min-merge across shards, whose bounds may be *wider* than this
    call's local mask — that is the point: every shard then emits against
    the same global scalars.  ``backend=None`` picks the Pallas
    kernel on TPU and the ``lax.scan`` tiling elsewhere; ``interpret`` forces
    the Pallas interpreter (tests).  Pinned to float32 like the dense scoring
    path, including under ``jax_enable_x64``.  Traceable under ``jit``/``vmap``.
    """
    tile = DEFAULT_TILE if tile is None else tile
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    args = (f32(area), f32(slope), f32(std), f32(prices), f32(vcpus),
            f32(memory_gb), jnp.asarray(mask, bool),
            jnp.asarray(use_cpus, bool),
            f32(required), f32(lam), f32(weight),
            None if extrema is None else (f32(extrema[0]), f32(extrema[1])),
            None if cost_floor is None else f32(cost_floor))
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "lax"
    if backend == "pallas":
        interp = (jax.default_backend() != "tpu") if interpret is None \
            else interpret
        return _score_fuse_pallas(*args, tile=tile, interpret=interp)
    if backend != "lax":
        raise ValueError(f"unknown score_fuse backend: {backend!r}")
    return _score_fuse_lax(*args, tile=tile)
