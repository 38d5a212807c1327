"""Quantisation machinery (``repro.parallel.compression``): archive tiers +
the gradient-exchange round trip, under both float regimes.

The load-bearing contracts:

- round-trip error is bounded by half the per-candidate (or per-tensor)
  quantisation step — the premise every ``repro.core.quantized`` score
  bound is derived from;
- a staged window (``quantize_window``) and a stream of appended columns
  (``quantize_column``, which encodes a tick on the host through
  ``quantize_window``) land on bit-identical codes, exact ties of
  ``y / step`` included, so a rolling ring and a cold re-stage can never
  disagree about stored content;
- every scale/output dtype is pinned to float32 explicitly, so enabling
  ``jax_enable_x64`` changes nothing (satellite fix — the gradient path
  used to rely on default promotion).
"""
import ml_dtypes
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.parallel import compression as comp


@pytest.fixture
def window():
    rng = np.random.default_rng(7)
    return rng.uniform(0.0, 50.0, (129, 23))


# ---------------------------------------------------------------------------
# archive tiers
# ---------------------------------------------------------------------------

def test_resolve_precision_rejects_unknown():
    with pytest.raises(ValueError, match="precision"):
        comp.resolve_precision("float16")
    for p in comp.ARCHIVE_PRECISIONS:
        assert comp.resolve_precision(p) == p


def test_candidate_scales_headroom_and_floor(window):
    with pytest.raises(ValueError, match="headroom"):
        comp.candidate_scales(window, "int8", headroom=0.5)
    s1 = comp.candidate_scales(window, "int8")
    s2 = comp.candidate_scales(window, "int8", headroom=2.0)
    np.testing.assert_allclose(s2, 2.0 * s1, rtol=1e-6)
    maxabs = np.abs(window).max(-1).astype(np.float32)
    np.testing.assert_allclose(s1, maxabs / 127.0, rtol=1e-6)
    # all-zero rows get the epsilon floor, not a 0/0 code
    z = comp.candidate_scales(np.zeros((3, 5)), "int8")
    assert (z > 0).all()
    assert comp.candidate_scales(window, "float32").sum() == 0.0


@pytest.mark.parametrize("precision", ["int8", "bfloat16"])
def test_window_round_trip_error_bound(window, precision):
    """|dequantize(quantize(x)) - x| <= scale / 2 per sample, no clipping
    when the scale is derived from this exact window."""
    scale = comp.candidate_scales(window, precision)
    q = comp.quantize_window(window, scale, precision)
    assert q.dtype == comp.storage_dtype(precision)
    deq = np.asarray(comp.dequantize_window(q, scale, precision))
    assert deq.dtype == np.float32
    err = np.abs(deq - window.astype(np.float32))
    assert (err <= 0.5 * scale[:, None] * (1 + 1e-5)).all()


def test_float32_tier_is_lossless(window):
    scale = comp.candidate_scales(window, "float32")
    q = comp.quantize_window(window, scale, "float32")
    deq = np.asarray(comp.dequantize_window(q, scale, "float32"))
    np.testing.assert_array_equal(deq, window.astype(np.float32))


def test_chunked_staging_matches_monolithic(window):
    """Chunk size is a memory knob, never a value knob."""
    for precision in ("int8", "bfloat16"):
        s_a = comp.candidate_scales(window, precision, chunk=7)
        s_b = comp.candidate_scales(window, precision, chunk=10_000)
        np.testing.assert_array_equal(s_a, s_b)
        q_a = comp.quantize_window(window, s_a, precision, chunk=7)
        q_b = comp.quantize_window(window, s_a, precision, chunk=10_000)
        np.testing.assert_array_equal(
            np.asarray(q_a, np.float32), np.asarray(q_b, np.float32))


def test_column_codes_match_window_codes(window):
    """Streamed appends and staged windows agree bit for bit."""
    scale = comp.candidate_scales(window, "int8")
    q = comp.quantize_window(window, scale, "int8")
    for t in range(window.shape[1]):
        codes, clipped = comp.quantize_column(window[:, t], scale, "int8")
        assert codes.dtype == np.int8
        np.testing.assert_array_equal(codes, q[:, t])
        assert clipped == 0


def tie_window():
    """T3 counts (multiples of 5) whose per-candidate maximum is an even
    multiple of 5, so the step maximum / 127 puts half the maximum exactly
    on a tie of ``y / step`` (63.5 in float32), and one tick column: for
    each maximum, that tie, a count past the clip range, and a count off
    any tie."""
    maxima = np.repeat(np.arange(10.0, 51.0, 10.0), 3)
    window = np.minimum(np.arange(0.0, 51.0, 5.0)[None, :],
                        maxima[:, None])
    kind = np.arange(len(maxima)) % 3
    tick = np.choose(kind, [maxima / 2, maxima + 10, maxima / 2 + 5])
    return window, tick


def test_column_codes_at_exact_ties_and_clips():
    """A tick column full of exact ties of ``y / step`` and of samples past
    the clip range encodes as the staged window would, half to even, and
    every clipped sample is counted."""
    window, tick = tie_window()
    scale = comp.candidate_scales(window, "int8")
    q = np.asarray(tick, np.float32) / scale
    ties = q - np.floor(q) == np.float32(0.5)
    over = np.abs(np.round(q)) > 127
    assert (ties & ~over).sum() == 5
    codes, clipped = comp.quantize_column(tick, scale, "int8")
    np.testing.assert_array_equal(
        codes, comp.quantize_window(tick[:, None], scale, "int8")[:, 0])
    np.testing.assert_array_equal(codes[ties & ~over], 64)  # 63.5, to even
    assert clipped == int(over.sum()) == 5
    np.testing.assert_array_equal(codes[over], 127)


def test_column_clipping_is_counted_not_hidden():
    scale = np.full(4, 1.0, np.float32)
    col = np.asarray([10.0, -500.0, 200.0, 127.4])
    codes, clipped = comp.quantize_column(col, scale, "int8")
    assert clipped == 2
    np.testing.assert_array_equal(codes, [10, -127, 127, 127])


def test_bf16_effective_step_bounds_cast_error(window):
    """The bf16 'scale' is not used to decode, but it must still bound the
    cast error — that is what the shared error-budget derivation assumes."""
    scale = comp.candidate_scales(window, "bfloat16")
    cast = window.astype(np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)
    err = np.abs(cast - window.astype(np.float32))
    assert (err <= 0.5 * scale[:, None] * (1 + 1e-5)).all()


# ---------------------------------------------------------------------------
# gradient exchange (satellite: x64 safety + direct round-trip coverage)
# ---------------------------------------------------------------------------

def test_gradient_round_trip_error_bound():
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(0.0, 2.0, 513), jnp.float32)
    q, scale, err = comp.quantize(g)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    deq = comp.dequantize(q, scale)
    assert deq.dtype == jnp.float32
    step = float(scale)
    assert np.abs(np.asarray(deq) - np.asarray(g)).max() <= 0.5 * step * (1 + 1e-5)
    # the returned error *is* the residual the feedback loop replays
    np.testing.assert_allclose(np.asarray(err),
                               np.asarray(g) - np.asarray(deq), atol=1e-7)


def test_quantize_dequantize_pinned_under_x64():
    rng = np.random.default_rng(11)
    g64 = rng.normal(0.0, 1.0, 257)
    win = rng.uniform(0.0, 50.0, (17, 9))
    jax.config.update("jax_enable_x64", True)
    try:
        q, scale, err = comp.quantize(jnp.asarray(g64))
        assert scale.dtype == jnp.float32
        assert err.dtype == jnp.float32
        assert comp.dequantize(q, scale).dtype == jnp.float32
        # error feedback keeps float32 on the second round too
        q2, scale2, err2 = comp.quantize(jnp.asarray(g64), err)
        assert scale2.dtype == jnp.float32 and err2.dtype == jnp.float32
        s = comp.candidate_scales(win, "int8")
        assert s.dtype == np.float32
        deq = comp.dequantize_window(
            comp.quantize_window(win, s, "int8"), s, "int8")
        assert deq.dtype == jnp.float32
        codes, clipped = comp.quantize_column(win[:, 0], s, "int8")
        assert codes.dtype == np.int8 and clipped == 0
    finally:
        jax.config.update("jax_enable_x64", False)


def test_x64_codes_match_x32_codes():
    """Same inputs, same codes and scales, with x64 on or off."""
    rng = np.random.default_rng(13)
    g = rng.normal(0.0, 1.0, 129).astype(np.float32)
    q_32, s_32, _ = comp.quantize(jnp.asarray(g))
    jax.config.update("jax_enable_x64", True)
    try:
        q_64, s_64, _ = comp.quantize(jnp.asarray(g))
    finally:
        jax.config.update("jax_enable_x64", False)
    np.testing.assert_array_equal(np.asarray(q_32), np.asarray(q_64))
    assert float(s_32) == float(s_64)
