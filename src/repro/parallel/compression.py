"""Quantisation machinery: archive-tier storage + gradient all-reduce.

Two consumers share the int8-with-float32-scale scheme in this module:

- **Archive tiers** (the serving stack): T3 ring buffers and staged archives
  can hold their (K, T) window as int8 codes with one float32 scale per
  candidate (or as bfloat16, scale-free), cutting resident window bytes ~4x
  (~2x for bf16) so the candidate fan-out per device can grow past 10^6.
  The per-candidate scale **is** the quantisation step: one int8 code spans
  ``scale`` units, so any stored sample differs from its float32 source by
  at most ``scale / 2`` (as long as the value stays inside the clip range
  ``[-127 * scale, 127 * scale]`` — the rolling archives count clipped
  samples instead of hiding them).  ``repro.core.quantized`` turns that
  per-sample step into the documented score-drift budget.

- **Gradient exchange** (the elastic data-parallel cluster): each worker
  quantises its local gradient to int8 with a per-tensor scale, the
  reduction runs on the quantised payload (8x wire-format saving vs f32 /
  4x vs bf16), and the quantisation residual is fed back into the next
  round (error feedback keeps the scheme unbiased over time — Seide et
  al., Karimireddy et al.).

Every function pins scales and dequantised outputs to float32 explicitly,
so results are identical under ``jax_enable_x64`` (the x64-default promotion
rules never see a weakly-typed operand).
"""
from __future__ import annotations

from typing import Any

import ml_dtypes
import numpy as np

import jax
import jax.numpy as jnp

#: Storage dtypes an archive window can be held in.  "float32" is the exact
#: baseline; "bfloat16" halves window bytes (scale-free — dequantisation is
#: a cast); "int8" quarters them with a per-candidate float32 scale.
ARCHIVE_PRECISIONS = ("float32", "bfloat16", "int8")

#: bf16 keeps 8 significand bits (1 implicit + 7 stored), so rounding to
#: nearest puts a stored sample within ``|y| * 2**-8`` of its float32
#: source.  Expressed as a per-candidate "step" (``maxabs * 2**-7``) the
#: bf16 tier shares the int8 tier's ``error <= step / 2`` contract and
#: bound derivations.
BF16_RELATIVE_STEP = 2.0 ** -7

#: Host-side chunk (rows) for staging-time passes over a (K, T) window, so
#: seeding a K=10^6 archive never materialises a second full-window copy.
STAGE_CHUNK = 65536

_DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16,
           "int8": np.int8}


def resolve_precision(precision: str) -> str:
    """Validate an ``archive_precision`` knob value."""
    if precision not in ARCHIVE_PRECISIONS:
        raise ValueError(
            f"archive precision must be one of {ARCHIVE_PRECISIONS}, "
            f"got {precision!r}")
    return precision


def storage_dtype(precision: str):
    """The numpy storage dtype of an archive tier."""
    return _DTYPES[resolve_precision(precision)]


def candidate_scales(window, precision: str, *, headroom: float = 1.0,
                     chunk: int = STAGE_CHUNK) -> np.ndarray:
    """Per-candidate quantisation step of a (K, T) seed window, float32.

    ``int8``: ``maxabs * headroom / 127`` — the width one code spans, so the
    clip range is ``[-127 * scale, 127 * scale]`` and ``headroom > 1`` buys
    slack for live columns exceeding the seed window's per-candidate range
    (at the cost of a proportionally coarser step).  ``bfloat16``: the
    effective step ``maxabs * headroom * BF16_RELATIVE_STEP`` — not used to
    dequantise (bf16 is a cast), only for byte accounting and the error
    bounds.  ``float32``: zeros (lossless tier).  Rows are processed in
    ``chunk``-sized blocks so no full-window temporary is allocated.
    """
    resolve_precision(precision)
    if headroom < 1.0:
        raise ValueError(f"headroom must be >= 1.0, got {headroom}")
    window = np.asarray(window)
    K = window.shape[0]
    if precision == "float32":
        return np.zeros(K, np.float32)
    maxabs = np.empty(K, np.float32)
    for a in range(0, K, chunk):
        b = min(a + chunk, K)
        maxabs[a:b] = np.abs(window[a:b]).max(axis=-1).astype(np.float32)
    step = BF16_RELATIVE_STEP if precision == "bfloat16" else 1.0 / 127.0
    return np.maximum(maxabs * np.float32(headroom), np.float32(1e-12)) \
        .astype(np.float32) * np.float32(step)


def quantize_window(window, scale: np.ndarray, precision: str, *,
                    chunk: int = STAGE_CHUNK) -> np.ndarray:
    """Encode a host (K, T) window at ``precision`` (chunked, no full temp).

    int8 codes are float32 ``y / scale`` rounded half to even and clipped to
    +-127, all on the host: :func:`quantize_column` encodes a tick through
    this function, so a staged window and a stream of appended columns land
    on identical codes (a device divide need not round exactly, and at an
    exact tie of ``y / scale`` one off is the other side of the tie).
    """
    resolve_precision(precision)
    window = np.asarray(window)
    if precision == "float32":
        return window.astype(np.float32)
    out = np.empty(window.shape, _DTYPES[precision])
    for a in range(0, window.shape[0], chunk):
        b = min(a + chunk, window.shape[0])
        blk = window[a:b].astype(np.float32)
        if precision == "bfloat16":
            out[a:b] = blk.astype(ml_dtypes.bfloat16)
        else:
            out[a:b] = np.clip(_int8_round(blk, scale[a:b, None]),
                               -127, 127).astype(np.int8)
    return out


def dequantize_window(q, scale, precision: str):
    """Decode stored window/ring content back to float32 (jnp or numpy in,
    jnp out).  ``int8``: ``code * scale`` per candidate row; ``bfloat16``:
    an exact cast; ``float32``: identity.  One multiply in float32, so the
    host (numpy) and device (XLA) decodes agree bit for bit.
    """
    resolve_precision(precision)
    q = jnp.asarray(q)  # spotlint: disable=SPL002 (codes keep storage dtype)
    if precision == "int8":
        return q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)[:, None]
    return q.astype(jnp.float32)


def quantize_column(col, scale, precision: str):
    """Encode one (K,) tick column on the host; returns ``(codes,
    n_clipped)``.

    The codes are :func:`quantize_window`'s of the (K, 1) window, so a tick
    appended to a ring stores what a cold re-stage of the same window
    would.  ``n_clipped`` counts samples whose code fell outside +-127
    (always 0 for bf16/f32): the error-bound contract only holds for
    unclipped samples, so the archives surface the count rather than
    silently saturating.
    """
    col = np.asarray(col, np.float32)
    scale = np.asarray(scale, np.float32)
    codes = quantize_window(col[:, None], scale, precision)[:, 0]
    if precision != "int8":
        return codes, 0
    return codes, int(np.count_nonzero(np.abs(_int8_round(col, scale)) > 127))


def _int8_round(y: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """``y / scale`` in float32, rounded half to even: int8 codes before
    the clip."""
    return np.round(y.astype(np.float32) / scale.astype(np.float32))


def quantize(g: jax.Array, error: jax.Array | None = None):
    """Returns (q int8, scale fp32, new_error fp32) — per-tensor scale."""
    g32 = g.astype(jnp.float32)
    if error is not None:
        g32 = g32 + error.astype(jnp.float32)
    scale = (jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0) \
        .astype(jnp.float32)
    q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    return q, scale, (g32 - deq).astype(jnp.float32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


class ErrorFeedback:
    """Per-worker error-feedback state over a gradient pytree."""

    def __init__(self):
        self._err: Any = None

    def compress(self, grads: Any):
        if self._err is None:
            self._err = jax.tree.map(
                lambda g: jnp.zeros(g.shape, jnp.float32), grads)
        qs, scales, errs = [], [], []
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(self._err)
        for g, e in zip(flat_g, flat_e):
            q, s, ne = quantize(g, e)
            qs.append(q)
            scales.append(s)
            errs.append(ne)
        self._err = jax.tree.unflatten(treedef, errs)
        return jax.tree.unflatten(treedef, qs), jax.tree.unflatten(treedef, scales)


def allreduce_compressed(worker_grads: list, feedbacks: list[ErrorFeedback]):
    """Mean-reduce gradients across workers on int8 payloads.

    `worker_grads`: list of per-worker gradient pytrees (same structure).
    Returns the dequantised mean pytree + wire bytes actually exchanged.
    """
    n = len(worker_grads)
    payloads = []
    wire_bytes = 0
    for grads, fb in zip(worker_grads, feedbacks):
        q, s = fb.compress(grads)
        payloads.append((q, s))
        wire_bytes += sum(x.size for x in jax.tree.leaves(q))          # int8
        wire_bytes += 4 * len(jax.tree.leaves(s))                      # scales
    deq = [jax.tree.map(dequantize, q, s) for q, s in payloads]
    mean = jax.tree.map(lambda *xs: sum(xs) / n, *deq)
    return mean, wire_bytes


def allreduce_exact(worker_grads: list):
    """Uncompressed reference reduction (fp32 wire format)."""
    n = len(worker_grads)
    mean = jax.tree.map(lambda *xs: sum(x.astype(jnp.float32) for x in xs) / n,
                        *worker_grads)
    wire = sum(4 * x.size for x in jax.tree.leaves(worker_grads[0])) * n
    return mean, wire
