#!/usr/bin/env python3
"""Smoke run of the SpotVista recommender's main path on a TPU.

    python chip_smoke.py             # one chip: serving and live ingestion
    python chip_smoke.py --chips 4   # four chips: the K-sharded archive only

The deployment is the paper's single-region setting: K = 32768 candidates,
a T = 1008-sample window (7 days at 10-minute ticks), a seeded synthetic
catalog, and multi-node requests of 5-50 nodes on the vCPU and the memory
axis, with and without filters.  Everything runs in this one process, since
a chip belongs to one process at a time.

Every phase checks what comes out against the repo's plain references and
raises on a mismatch, so any failure exits non-zero.  A host whose JAX finds
no TPU exits non-zero before any phase runs.  The wall and compile times
printed are the times of this one run, not benchmark numbers.  Only when
every phase passed is the last line of standard output the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

K = 32768
B = 64                     # the server's largest default bucket
TICKS = 8                  # collector ticks per ring
SHARD_TICKS = 3
SEED = 0
#: Scores span 0-110.  The float32 device path may differ from the float64
#: references by rounding of the Eq. 3 statistics over T samples, amplified
#: by the MinMax range; on a well-spread catalog that stays far below this.
SCORE_ATOL = 1e-2
#: Streamed moments vs a one-shot pass over the decoded window: the budget of
#: tests/test_stats_update.py.
STATS_RTOL, STATS_ATOL = 1e-5, 1e-4
KERNELS = {"pool_scan", "score_fuse", "stats_update"}


def require(ok, message):
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(message)


class Phases:
    """Labelled wall and compile seconds of each phase."""

    def __init__(self):
        import jax
        self.rows: list[tuple[str, float, float]] = []
        self._compile = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self._compile += duration

    @contextlib.contextmanager
    def __call__(self, name):
        t0, c0 = time.perf_counter(), self._compile
        yield
        wall, comp = time.perf_counter() - t0, self._compile - c0
        self.rows.append((name, wall, comp))
        print(f"phase {name}: wall {wall:.3f} s, compile {comp:.3f} s",
              flush=True)


def window_len() -> int:
    from repro.configs.spotvista import CONFIG
    return int(CONFIG.window_days * 24 * 60 / CONFIG.collect_period_min)


def catalog(k: int, t: int, seed: int = SEED):
    """A seeded synthetic catalog: 3 regions x 3 AZs, 4 families."""
    from repro.core import CandidateSet
    rng = np.random.default_rng(seed)
    fams = rng.choice(["m5", "c5", "r5", "t3"], k)
    return CandidateSet(
        names=np.array([f"{f}.x{i}" for i, f in enumerate(fams)]),
        regions=rng.choice(["us-east-1", "eu-west-1", "ap-north-1"], k),
        azs=rng.choice(["a", "b", "c"], k),
        families=fams,
        categories=rng.choice(["general", "compute", "memory"], k),
        vcpus=rng.choice([2, 4, 8, 16, 32, 64, 96], k).astype(np.float64),
        memory_gb=rng.choice([4, 8, 16, 64, 128, 384], k).astype(np.float64),
        prices=rng.uniform(0.01, 5.0, k),
        t3=rng.uniform(0.0, 50.0, (k, t)),
    )


def requests(cands, seed: int, filtered: bool, n: int = B):
    """``n`` multi-node requests of 5-50 nodes, half on each capacity axis.
    ``filtered`` cycles through every region, family and (region, family)
    filter of the catalog; otherwise no request has a filter."""
    from repro.core import ResourceRequest
    rng = np.random.default_rng(seed)
    regions = [str(r) for r in np.unique(cands.regions)]
    families = [str(f) for f in np.unique(cands.families)]
    presets = ([{"regions": [r]} for r in regions]
               + [{"families": [f]} for f in families]
               + [{"regions": [r], "families": [f]}
                  for r in regions for f in families]) if filtered else [{}]
    out = []
    for i in range(n):
        nodes = int(rng.integers(1, 11)) * 5
        if i % 2:
            axis = {"memory_gb": nodes * float(rng.choice([16, 32, 64, 128]))}
        else:
            axis = {"cpus": nodes * float(rng.choice([4, 8, 16, 32]))}
        out.append(ResourceRequest(weight=float(rng.choice([0.3, 0.5, 0.7])),
                                   lam=0.1, **axis, **presets[i % len(presets)]))
    return out


def fused_rows(engine, cands, reqs, archive):
    """The (B, K) outputs of the fused dispatch ``recommend_batch`` makes
    for ``reqs``, and the program that dispatch compiles to."""
    import jax
    from repro.core import engine as engine_lib
    from repro.core.types import RequestBatch
    batch = RequestBatch.from_requests(cands, reqs, pad_to=B)
    ops, statics = engine._fused_operands(cands, batch, archive)
    require(statics == {"pool_impl": "tiled", "score_impl": "tiled"},
            f"the engine did not pick the tiled paths: {statics}")
    fn = engine_lib._fused_recommend_batch
    return batch, jax.device_get(fn(*ops, **statics)), fn.lower(
        *ops, **statics)


def assert_same_pools(got, want, what):
    for b, (g, w) in enumerate(zip(got, want)):
        require(list(g.names) == list(w.names), f"{what}: row {b} members")
        np.testing.assert_array_equal(g.counts, w.counts,
                                      err_msg=f"{what}: row {b} counts")


def check_served(engine, cands, reqs, recs, archive, what):
    """Pools vs host Algorithm 1 on the chip's own score rows and vs the
    per-request path; scores vs the float64 references.  Returns the
    kernels the served program runs and the largest score error."""
    from repro.core import scoring
    from repro.core.pool import greedy_pool
    from repro.kernels import compiled_kernels
    batch, rows, lowered = fused_rows(engine, cands, reqs, archive)
    comb, avail, cost = rows[:3]
    refs: dict = {}
    err = 0.0
    for b, (req, rec) in enumerate(zip(reqs, recs)):
        idx = np.flatnonzero(batch.masks[b])
        caps = np.asarray(req.capacity_of(cands), np.float64)[idx]
        pool = greedy_pool(comb[b][idx], caps, req.amount)
        require(list(rec.names) == list(cands.names[idx[pool.indices]]),
                f"{what}: row {b} members differ from greedy_pool")
        np.testing.assert_array_equal(rec.counts, pool.counts,
                                      err_msg=f"{what}: row {b} counts")
        key = (batch.masks[b].tobytes(), req.lam)
        if key not in refs:
            refs[key] = scoring.availability_scores_ref(cands.t3[idx],
                                                        req.lam)
        a_ref = refs[key]
        c_ref = scoring.cost_scores_ref(cands.prices[idx], caps, req.amount)
        s_ref = req.weight * a_ref + (1.0 - req.weight) * c_ref
        for got, want in ((avail[b][idx], a_ref), (cost[b][idx], c_ref),
                          (comb[b][idx], s_ref)):
            err = max(err, float(np.abs(got - want).max()))
    require(err <= SCORE_ATOL, f"{what}: score error {err} > {SCORE_ATOL}")
    for b in range(0, len(reqs), 8):
        one = engine.recommend(cands, reqs[b])
        assert_same_pools([recs[b]], [one], f"{what}: per-request row {b}")
    return compiled_kernels(lowered.compile().as_text()), err


def run_single_chip(phase, k: int, t: int, ticks: int = TICKS):
    """Serving at D1 and live ingestion on f32 and int8 rings.  Returns the
    Pallas kernels found in the served and ingest programs."""
    import jax
    from repro.core import CandidateSet, EngineConfig, scoring
    from repro.kernels import compiled_kernels
    from repro.serve import BatchServer, DeviceArchive
    from repro.stream import RollingDeviceArchive

    with phase("catalog"):
        cands = catalog(k, t)
        mixes = [(f"{name}{i}", requests(cands, SEED + 10 * i + j, filtered))
                 for j, (name, filtered) in enumerate(
                     (("filterless", False), ("filtered", True)))
                 for i in range(2)]
    server = BatchServer(config=EngineConfig())
    kernels: set = set()
    for name, reqs in mixes:
        with phase(f"serve_{name}"):
            recs = server.serve(cands, reqs)
        with phase(f"check_{name}"):
            archive = server.cache.get(cands)
            seen, err = check_served(server.engine, cands, reqs, recs,
                                     archive, name)
            kernels |= seen
        print(f"serve_{name}: {len(reqs)} requests, pools match greedy_pool "
              f"and the per-request path, max score error {err:.3e}",
              flush=True)

    reqs = mixes[-1][1]
    rng = np.random.default_rng(SEED + 1)
    for precision in ("float32", "int8"):
        with phase(f"stage_{precision}_ring"):
            ring = RollingDeviceArchive(cands, precision=precision)
            jax.block_until_ready(ring.score_stats())
        step, ops, statics = ring._append_dispatch(
            rng.uniform(0.0, 50.0, k))
        kernels |= compiled_kernels(
            step.lower(*ops, **statics).compile().as_text())
        worst = 0.0
        for tick in range(ticks):
            with phase(f"tick_{precision}_{tick}"):
                ring.append(rng.uniform(0.0, 50.0, k))
                snap = ring.snapshot()
                recs = server.serve(snap, reqs)
            window = ring.materialize()
            want = jax.device_get(scoring.candidate_stats(window))
            for name, g, w in zip(("area", "slope", "std"),
                                  jax.device_get(snap.stats), want):
                np.testing.assert_allclose(
                    g, w, rtol=STATS_RTOL, atol=STATS_ATOL,
                    err_msg=f"{precision} ring tick {tick}: {name}")
                worst = max(worst, float(np.abs(g - w).max()))
        with phase(f"restage_{precision}"):
            cold = DeviceArchive.stage(CandidateSet(
                **{**cands.__dict__, "t3": window}))
            assert_same_pools(server.serve(cold, reqs), recs,
                              f"{precision} ring vs cold re-stage")
        print(f"ingest_{precision}: {ticks} ticks, streamed stats match "
              f"candidate_stats (max abs diff {worst:.3e}), pools match a "
              f"cold re-stage", flush=True)
    return kernels


def run_four_chips(phase, k: int, t: int, devices, ticks: int = SHARD_TICKS):
    """The K-sharded archive and ring over four devices against the
    single-device tiled path in this process: pools and score rows must be
    bit-identical."""
    from repro.core import EngineConfig, RecommendationEngine
    from repro.core.engine import _dedup_masks
    from repro.serve import BatchServer, DeviceArchive
    from repro.shard import (ShardedArchive, ShardedRollingArchive,
                             sharded_batch_arrays)
    from repro.stream import RollingDeviceArchive

    def shard_devices(archive):
        return {d for s in archive.shards for d in s.prices.devices()}

    def compare(single, sharded, reqs, what):
        batch, want, _ = fused_rows(engine, cands, reqs, single)
        uniq, inv = _dedup_masks(batch.masks)
        got = sharded_batch_arrays(
            sharded, batch.masks, batch.use_cpus, batch.weights, batch.lams,
            batch.amounts, uniq, inv, pool_impl="tiled")
        for name, g, w in zip(("comb", "avail", "cost", "order", "counts",
                               "k_stop"), got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=f"{what}: {name}")
        assert_same_pools(server.serve(sharded, reqs),
                          server.serve(single, reqs), what)

    with phase("catalog"):
        cands = catalog(k, t)
        batches = [requests(cands, SEED, False), requests(cands, SEED, True)]
    engine = RecommendationEngine(EngineConfig())
    server = BatchServer(engine)
    with phase("stage_sharded"):
        single = DeviceArchive.stage(cands, device=devices[0])
        sharded = ShardedArchive.stage(cands, n_shards=4, devices=devices)
    require(shard_devices(sharded) == set(devices),
            f"shards not on four distinct devices: {shard_devices(sharded)}")
    for i, reqs in enumerate(batches):
        with phase(f"sharded_batch_{i}"):
            compare(single, sharded, reqs, f"sharded archive batch {i}")

    with phase("stage_sharded_ring"):
        ring1 = RollingDeviceArchive(cands, device=devices[0])
        ring4 = ShardedRollingArchive(cands, n_shards=4, devices=devices)
    require(shard_devices(ring4) == set(devices),
            f"ring shards not on four distinct devices: {shard_devices(ring4)}")
    rng = np.random.default_rng(SEED + 2)
    for tick in range(ticks):
        with phase(f"sharded_tick_{tick}"):
            col = rng.uniform(0.0, 50.0, k)
            ring1.append(col)
            ring4.append(col)
            compare(ring1.snapshot(), ring4.snapshot(), batches[tick % 2],
                    f"sharded ring tick {tick}")
    print(f"sharded: 4 shards on {len(set(devices))} distinct devices; pools "
          f"and score rows bit-identical to one device over "
          f"{len(batches)} batches and {ticks} ring ticks", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the K-sharded archive over 4 chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from repro.runtime import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind}, {len(devices)} visible; compile "
          f"cache {use_compile_cache()}", flush=True)
    phase = Phases()
    t = window_len()
    if args.chips == 4:
        run_four_chips(phase, K, t, devices[:4])
    else:
        kernels = run_single_chip(phase, K, t)
        print(f"pallas kernels in the served and ingest programs: "
              f"{sorted(kernels)}", flush=True)
        require(KERNELS <= kernels,
                f"not compiled as Pallas: {sorted(KERNELS - kernels)}")
    for d in devices[:args.chips]:
        print(f"peak bytes in use on device {d.id}: "
              f"{d.memory_stats().get('peak_bytes_in_use')}", flush=True)
    print(f"total: wall {sum(r[1] for r in phase.rows):.3f} s, compile "
          f"{sum(r[2] for r in phase.rows):.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
