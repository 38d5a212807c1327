"""Incremental candidate-statistics kernel: O(K) rank-1 update of the Eq. 3
reductions when the live collector appends (and possibly evicts) one T3 column.

``core.scoring.candidate_stats`` is the O(K*T) pass the serve layer caches per
staged archive.  Under live ingestion the archive changes by exactly one
column per collector tick, so recomputing the full reductions — let alone
re-staging the whole (K, T) slice — is pure waste: every statistic of Eq. 3
is a function of three streaming moments per candidate,

    S0 = sum(y_i),   S1 = sum(i * y_i),   Q = sum((y_i - ref)^2)

(``i`` the position inside the window, oldest first; ``ref`` a per-candidate
frozen centering point — see ``scoring.stats_from_moments`` for why the
second moment must not be a raw power sum), and a sliding window updates
each of them with O(1) work per candidate:

    append y_new (window grows to length L):
        S0 += y_new;  S1 += (L - 1) * y_new;  Q += (y_new - ref)^2
    evict y_old (window slides, length stays L):
        S0 -= y_old
        S1  = S1 - S0_pre + y_old            (every survivor's index drops 1)
        Q  -= (y_old - ref)^2

The moments are held as float32 Neumaier pairs ``(sum, compensation)`` so a
week-long stream of ticks cannot drift the accumulators: each add captures
its own rounding error, keeping the resolved ``sum + comp`` within a few
float32 ulp of the exact value regardless of tick count — which is what
keeps the derived statistics inside the same float32-ulp budget the scoring
suites use against ``candidate_stats`` of the materialized window
(``scoring.stats_from_moments`` is the shared derivation tail).

Everything is elementwise over the candidate axis, so the kernel streams K
in the (tile // 128, 128) row blocks of ``pool_scan`` / ``score_fuse`` but
needs no cross-tile carry — the grid is ``(nt,)``, one phase, update +
derivation fused per tile:

- ``_stats_update_vec``    : the vectorized jnp fallback (CPU/GPU), a single
                             fused elementwise pass (jit/vmap friendly).
- ``_stats_update_pallas`` : the Pallas TPU kernel, identical tile math,
                             scalar params (window length, evict flag) in
                             SMEM.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import scoring
from .pool_scan import LANES, _pad_rows

#: 32 rows of 128 lanes: the native (32, 128) tile of the int8 code rows the
#: quantized tier streams (and four f32 (8, 128) tiles).
DEFAULT_TILE = 4096


class StreamMoments(NamedTuple):
    """Float32 Neumaier pairs of the three streaming moments, each (K,).

    The resolved value of each moment is ``sum + comp``; the compensation
    terms carry the rounding error of every add/subtract so the pairs stay
    exact to a few ulp across unbounded tick counts.  ``ref`` is the frozen
    per-candidate centering point of the second moment — a constant, not an
    accumulator (re-priming the archive is the only thing that moves it).
    """

    s0: jax.Array       # sum(y)
    s0c: jax.Array
    s1: jax.Array       # sum(i * y), window-relative index, oldest first
    s1c: jax.Array
    q: jax.Array        # sum((y - ref)^2)
    qc: jax.Array
    ref: jax.Array      # frozen centering point (seed window's mean)

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self)


def moments_from_window(t3, *, scale=None, chunk: int = 65536) -> StreamMoments:
    """Exact cold-start moments of a host (K, T) window.

    The float64 host reductions are split into float32 ``(hi, lo)`` pairs, so
    the seeded accumulators represent the exact sums to double precision —
    the same invariant the compensated updates maintain afterwards.  The
    centering point ``ref`` is frozen at the (float32-rounded) seed-window
    mean, which keeps both operands of the variance subtraction O(var).

    ``scale`` seeds an int8 archive tier: ``t3`` holds stored codes and each
    chunk is decoded ``code.astype(f32) * scale`` — bitwise the
    ``compression.dequantize_window`` multiply — before the reductions, so
    the seeded moments are exact over the tier's ground truth (the
    dequantized window).  bf16 windows need no scale: the bf16 -> float64
    cast is exact.

    Rows are reduced in ``chunk``-sized blocks (per-row math — block size
    cannot change any value), so seeding a K=10^6 archive allocates an
    O(chunk * T) float64 temporary instead of a second full-window copy.
    """
    t3 = np.asarray(t3)
    if scale is not None:
        scale = np.asarray(scale, np.float32)
    K, T = t3.shape
    idx = np.arange(T, dtype=np.float64)
    s0 = np.empty(K, np.float64)
    s1 = np.empty(K, np.float64)
    q = np.empty(K, np.float64)
    ref32 = np.empty(K, np.float32)
    for a in range(0, K, chunk):
        b = min(a + chunk, K)
        if scale is not None:
            blk = (t3[a:b].astype(np.float32)
                   * scale[a:b, None]).astype(np.float64)
        else:
            blk = t3[a:b].astype(np.float64)
        ref32[a:b] = blk.mean(-1).astype(np.float32)
        d = blk - ref32[a:b].astype(np.float64)[:, None]
        s0[a:b] = blk.sum(-1)
        s1[a:b] = blk @ idx
        q[a:b] = (d * d).sum(-1)

    def pair(x64):
        hi = x64.astype(np.float32)
        lo = (x64 - hi.astype(np.float64)).astype(np.float32)
        return jnp.asarray(hi, jnp.float32), jnp.asarray(lo, jnp.float32)

    s0, s0c = pair(s0)
    s1, s1c = pair(s1)
    q, qc = pair(q)
    return StreamMoments(s0, s0c, s1, s1c, q, qc,
                         jnp.asarray(ref32, jnp.float32))


def _cadd(s, c, x):
    """One Neumaier-compensated add: ``(s, c) += x`` exactly to a few ulp."""
    t = s + x
    c = c + jnp.where(jnp.abs(s) >= jnp.abs(x), (s - t) + x, (x - t) + s)
    return t, c


def _update_tile(s0, s0c, s1, s1c, q, qc, ref, y_new, y_old, y_first, y_last,
                 length, evict, scale=None):
    """The fused per-tile rank-1 update + Eq. 3 derivation (elementwise).

    ``length`` is the window length *after* the append; ``evict`` gates the
    subtraction terms (a gated addend of exactly 0.0 is inert under the
    compensated add, so grow and slide share one op sequence).  The S1 shift
    term uses the *pre-update* S0 pair — the survivors' index drop happens
    before the new column joins the sum.

    ``scale`` enables the fused dequantize-and-update path of the quantized
    archive tier: the four column operands arrive as stored codes (int8, or
    bf16 with ``scale`` ignored by the caller passing float32-castable
    values) and are decoded in-register — ``code * scale`` per candidate,
    the exact multiply ``compression.dequantize_window`` uses — before the
    identical compensated update.  Nothing float32-and-column-shaped ever
    moves through memory, which is the ~4x bandwidth saving of the tier.
    """
    if scale is not None:
        deq = lambda y: y.astype(jnp.float32) * scale  # noqa: E731
        y_new, y_old = deq(y_new), deq(y_old)
        y_first, y_last = deq(y_first), deq(y_last)
    zero = jnp.zeros_like(y_new)
    gate = lambda x: jnp.where(evict, x, zero)  # noqa: E731
    s0_pre, s0c_pre = s0, s0c
    # S1 first: needs pre-update S0 (subtract both halves of the pair so the
    # compensation survives the hand-off).
    s1, s1c = _cadd(s1, s1c, (length - 1.0) * y_new)
    s1, s1c = _cadd(s1, s1c, gate(y_old))
    s1, s1c = _cadd(s1, s1c, gate(-s0_pre))
    s1, s1c = _cadd(s1, s1c, gate(-s0c_pre))
    s0, s0c = _cadd(s0, s0c, y_new)
    s0, s0c = _cadd(s0, s0c, gate(-y_old))
    d_new = y_new - ref
    d_old = y_old - ref
    q, qc = _cadd(q, qc, d_new * d_new)
    q, qc = _cadd(q, qc, gate(-(d_old * d_old)))
    stats = scoring.stats_from_moments(
        s0 + s0c, s1 + s1c, q + qc, y_first, y_last, length, ref)
    return (s0, s0c, s1, s1c, q, qc, ref), stats


# ---------------------------------------------------------------------------
# vectorized fallback: one fused elementwise pass.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _stats_update_vec(moments: StreamMoments, y_new, y_old, y_first, y_last,
                      length, evict, scale=None):
    out, stats = _update_tile(*moments, y_new, y_old, y_first, y_last,
                              length, evict, scale)
    return StreamMoments(*out), stats


# ---------------------------------------------------------------------------
# Pallas TPU kernel: same tile math, scalars in SMEM, grid (nt,).
# ---------------------------------------------------------------------------

def _stats_update_kernel(quantized, params_ref, *refs):
    """Shared kernel body; ``quantized`` adds a trailing scale-row input
    feeding the in-register dequantize of the four column operands."""
    n_in = 12 if quantized else 11
    ins = [r[...] for r in refs[:n_in]]
    (os0_ref, os0c_ref, os1_ref, os1c_ref, oq_ref, oqc_ref, area_ref,
     slope_ref, std_ref) = refs[n_in:]
    length = params_ref[0, 0]
    evict = params_ref[0, 1] > 0
    scale = ins[11] if quantized else None
    (s0, s0c, s1, s1c, q, qc, _), stats = _update_tile(
        *ins[:11], length, evict, scale)
    os0_ref[...] = s0
    os0c_ref[...] = s0c
    os1_ref[...] = s1
    os1c_ref[...] = s1c
    oq_ref[...] = q
    oqc_ref[...] = qc
    area_ref[...] = stats.area
    slope_ref[...] = stats.slope
    std_ref[...] = stats.std


def _stats_update_pallas(moments: StreamMoments, y_new, y_old, y_first,
                         y_last, length, evict, scale=None, *,
                         tile: int = DEFAULT_TILE, interpret: bool = False):
    K = y_new.shape[0]
    quantized = scale is not None
    arrs = (*moments, y_new, y_old, y_first, y_last) \
        + ((scale,) if quantized else ())
    *tiles, nt = _pad_rows(arrs, tile, (0,) * len(arrs))
    params = jnp.stack([jnp.asarray(length, jnp.float32),
                        jnp.where(evict, 1.0, 0.0).astype(jnp.float32)]
                       ).reshape(1, 2)
    rows = tile // LANES
    row_spec = pl.BlockSpec((rows, LANES), lambda t: (t, 0))
    out = pl.pallas_call(
        functools.partial(_stats_update_kernel, quantized),
        grid=(nt,),
        in_specs=[pl.BlockSpec((1, 2), lambda t: (0, 0),
                               memory_space=pltpu.SMEM)]
        + [row_spec] * len(arrs),
        out_specs=[row_spec] * 9,
        out_shape=[jax.ShapeDtypeStruct((nt * rows, LANES), jnp.float32)] * 9,
        interpret=interpret,
        name="stats_update",
    )(params, *tiles)
    unpad = lambda x: x.reshape(nt * tile)[:K]  # noqa: E731
    out = [unpad(x) for x in out]
    return (StreamMoments(*out[:6], moments.ref),
            scoring.CandidateStats(*out[6:]))



def stats_update(moments: StreamMoments, y_new, y_old, y_first, y_last,
                 length, evict, *, scale=None, tile: int | None = None,
                 backend: str | None = None, interpret: bool | None = None):
    """One collector tick: rank-1-update the moments, derive the statistics.

    Parameters
    ----------
    moments : StreamMoments
        Compensated accumulators of the window *before* this tick.
    y_new, y_old : (K,) arrays
        The appended column, and the evicted one (ignored — pass anything of
        the right shape, e.g. ``y_new`` — when ``evict`` is False).
    y_first, y_last : (K,) arrays
        First (oldest) and last column of the window *after* the tick — the
        trapezoid end corrections of the area.
    length : scalar
        Window length after the tick.
    evict : scalar bool
        Whether the window was full (slide) or still growing (append only).
    scale : (K,) float32 array, optional
        The quantized archive tier's fused dequantize-and-update path: when
        given, the four column operands are **stored int8 codes** and each
        is decoded in-register as ``code * scale`` (the exact
        ``compression.dequantize_window`` multiply) before the identical
        compensated tile math — the update consumes a quarter of the
        float32 path's column bandwidth and nothing float32-and-(K,)-shaped
        round-trips through memory.  The derived statistics then track
        ``candidate_stats`` of the *dequantized* materialized window (the
        tier's ground truth) at the same float32-ulp budget.  bf16 rings
        need no scale: their columns cast to float32 exactly, so they take
        the ``scale=None`` path as-is.

    Returns ``(new_moments, CandidateStats)`` where the statistics match
    ``scoring.candidate_stats`` of the materialized post-tick window at
    float32-ulp tolerance.  O(K) compute, no (K, T) operand anywhere.
    ``backend=None`` picks the Pallas kernel on TPU and the vectorized jnp
    pass elsewhere; ``interpret`` forces the Pallas interpreter (tests).
    Pinned to float32 like the scoring path, including under
    ``jax_enable_x64``.  Traceable under ``jit``.
    """
    tile = DEFAULT_TILE if tile is None else tile
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    moments = StreamMoments(*(f32(m) for m in moments))
    if scale is None:
        cols = (f32(y_new), f32(y_old), f32(y_first), f32(y_last))
    else:
        # Quantized path: columns stay in their storage dtype end to end;
        # the cast-and-scale happens inside the tile math.
        cols = tuple(jnp.asarray(y)  # spotlint: disable=SPL002 (storage dtype)
                     for y in (y_new, y_old, y_first, y_last))
        scale = f32(scale)
    args = (moments, *cols, f32(length), jnp.asarray(evict, bool), scale)
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "vec"
    if backend == "pallas":
        interp = (jax.default_backend() != "tpu") if interpret is None \
            else interpret
        return _stats_update_pallas(*args, tile=tile, interpret=interp)
    if backend != "vec":
        raise ValueError(f"unknown stats_update backend: {backend!r}")
    return _stats_update_vec(*args)
