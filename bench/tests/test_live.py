"""The live deployment: the seeded collector, the reference that follows
archive versions, and faults of the ingestion path planted under whole CPU
runs, each of which the check must find."""
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import catalog, check, live, reference, runner, trace
from bench.kernels import stats_update
from conftest import LIVE_CELL as CELL, LIVE_CONFIG as CONFIG


def run(bench, fault=None, seed=2 ** 31 + 77, seconds=3.0):
    return runner.run_cell(bench, bench.cell(CELL), seed, seconds, False,
                           time.monotonic(), cache_dir=None,
                           require_tpu=False, fault=fault)


def test_ticks_are_the_walks_next_columns(small):
    """Tick ``i`` of the stand-in is column ``window + i`` of one longer
    walk, bit for bit, and the primed window is its first ``window``."""
    cfg = small.config(CONFIG)
    seed, n = 2 ** 33 + 5, 6
    dep = catalog.build(cfg, seed, ticks=n)
    walk = cfg["t3_walk"]
    key = jax.random.wrap_key_data(jnp.asarray(catalog.seed_words(seed, 0)))
    levels = np.asarray(catalog._walk(
        key, k=len(dep.cands), steps=dep.window + n, lo=walk["level"][0],
        hi=walk["level"][1], p_lo=walk["p_move"][0], p_hi=walk["p_move"][1],
        revert=walk["revert"]))
    cols = levels.astype(np.float32) * np.float32(cfg["t3_counts"][2])
    c = live.Collector(dep)
    assert c.ticks == dep.window
    assert np.array_equal(c.to_candidate_set(window=dep.window).t3,
                          cols[:, :dep.window])
    for i in range(n):
        c.collect()
        assert c.ticks == dep.window + i + 1
        assert np.array_equal(c.column(dep.window + i), cols[:, dep.window + i])
    assert np.array_equal(c.to_candidate_set(window=dep.window).t3,
                          cols[:, n:])
    with pytest.raises(RuntimeError):
        c.collect()


@pytest.mark.parametrize("bits", [8, 4])
def test_sliding_statistics_equal_each_windows_own(bits):
    """Every version's sliding float64 statistics equal those computed
    directly from that version's stored window."""
    rng = np.random.default_rng(3)
    k, t, n = 300, 40, 12
    y = (5.0 * rng.integers(0, 11, (k, t + n))).astype(np.float32)
    y[:7] = 0.0                                # candidates that never move
    y[7:20, t:] = 50.0                         # and some beyond their range
    w = reference.TierWindows(y[:, :t], y[:, t:], 1.0, bits)
    scale = reference.tier_scale(y[:, :t], 1.0, bits)
    stored = reference.tier_decode(y, scale[:, None], bits)
    assert (np.abs(stored[:, :t] - y[:, :t])
            <= scale[:, None] / 2 * (1 + 1e-6)).all()
    for v in range(n + 1):
        got = w.stats(v)
        want = reference.window_stats(stored[:, v:v + t])
        for g, e in zip(got, want):
            np.testing.assert_allclose(g, e, rtol=1e-9, atol=1e-9)


def test_versions_off_by_hand():
    began = {0: 0.0, 1: 9.9, 2: 19.9}
    published = {0: 0.0, 1: 10.0, 2: 20.0}
    # (served, stamp, snapshot began, snapshot ended)
    sound = [(0, None, 5.0, 5.1), (0, 0, 9.95, 10.05), (1, 1, 9.95, 10.05),
             (1, None, 12.0, 12.1), (2, 2, 25.0, 25.1)]
    assert check.versions_off(sound, published, began) == 0
    off = [(0, None, 12.0, 12.1),     # one tick stale: 1 published at 10
           (1, 2, 12.0, 12.1),        # stamped with another version
           (3, None, 30.0, 30.1),     # never published
           (2, None, 15.0, 15.1)]     # its poll began after the snapshot
    for row in off:
        assert check.versions_off([row], published, began) == 1, row


def skipped_ticks(system):
    """The pump skips every other tick's column: on odd ticks the previous
    column is appended again."""
    st = system.stand_in
    real = st.column

    def column(i):
        j = i - st.window
        return real(i - 1) if j >= 0 and j % 2 == 1 else real(i)
    st.column = column


def stamped_ahead(system):
    """Every drain's answers carry the version after the one that served
    them."""
    q = system.queue
    real = q.resolve_archive

    def resolve_archive():
        snap = real()
        return dataclasses.replace(snap, version=snap.version + 1)
    q.resolve_archive = resolve_archive


def stale_snapshot(system):
    """Every drain serves the snapshot of the version before the newest one
    a drain saw, consistently: stamp and statistics agree."""
    arc = system.archive
    real = arc.snapshot
    kept = {}

    def snapshot():
        snap = real()
        kept[snap.version] = snap
        for v in [v for v in kept if v < snap.version - 1]:
            del kept[v]
        return kept.get(snap.version - 1, snap)
    arc.snapshot = snapshot


def stamped_behind(system):
    """Every snapshot after the first carries the statistics of its version
    and the stamp of the one before it, as a snapshot that read the version
    before an append and the statistics after it would."""
    arc = system.archive
    real = arc.snapshot

    def snapshot():
        snap = real()
        return dataclasses.replace(snap, version=max(snap.version - 1, 0))
    arc.snapshot = snapshot


def ring_held(system):
    """Versions advance, but the ring and its statistics stay at the primed
    window."""
    arc = system.archive

    def append(column):
        arc.version += 1
        arc.appends += 1
        return arc
    arc.append = append


def test_live_run_is_correct(small):
    out = run(small)
    assert out["correct"], out["check"]
    assert out["check"]["versions_off"]["value"] == 0
    w = out["window"]
    assert w["ticks"] >= 4 and w["pump_errors"] == 0
    assert w["versions_served"][1] >= 4
    assert out["failed"] == 0 and out["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [skipped_ticks, stamped_ahead,
                                   stale_snapshot, stamped_behind, ring_held])
def test_ingest_fault_is_not_correct(loaded, fault):
    out = run(loaded, fault=fault)
    assert not out["correct"], out["check"]


def test_traced_live_run_reads_the_ingest_layer(small):
    out = runner.run_cell(small, small.cell(CELL), 2 ** 31 + 78, 3.0, True,
                          time.monotonic(), cache_dir=None,
                          require_tpu=False)
    assert out["correct"], out["check"]
    names = {m["name"] for m in small.cell(CELL).per_layer}
    assert {"tick_ms.rps", "stats_update_roofline.rps"} <= names
    # the CPU trace has no TPU planes: the device readers find nothing
    assert "tick_ms.rps" not in out["metrics"]
    assert "batch_fill.rps" in out["metrics"]


def test_stats_update_bytes_from_the_recorded_ticks():
    """The op's operand and result bytes, read from the HLO text of the
    ticks in the recorded K = 131072 int8 trace: a (1, 2) float32 parameter
    block, seven float32 moment rows, four int8 column rows and the float32
    step row in; six moment rows and three statistics rows out, float32."""
    k = 131072
    want = 2 * 4 + 7 * 4 * k + 4 * 1 * k + 4 * k + 9 * 4 * k
    tr = trace.load(str(Path(__file__).parent / "data" / "live.xplane.pb"))
    ops = [o for o in tr.ops[0] if trace.op_stem(o.name) == "stats_update"]
    assert ops
    for o in ops:
        res, opd = trace.shapes(o.name)
        assert len(res) == 9 and len(opd) == 13
        assert stats_update.cost(res, opd) == (40 * k, float(want))
