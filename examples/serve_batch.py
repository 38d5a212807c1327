"""Batched recommendation serving: the SpotVista web-service path end-to-end.

Collects a (simulated) T3 archive, stages it on device, then serves a burst
of heterogeneous requests through the BatchServer — fused batched scoring +
pool formation — and compares wall-clock against the per-request loop:

    PYTHONPATH=src python examples/serve_batch.py --requests 48

(The former LLM decoding demo lives in examples/serve_model.py.)
"""
import argparse
import time

import numpy as np

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import RecommendationEngine, ResourceRequest
from repro.serve import BatchServer
from repro.runtime import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--targets", type=int, default=80)
    ap.add_argument("--cycles", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    if args.requests < 1:
        ap.error("--requests must be >= 1")

    # 1. a simulated cloud + collected T3 archive (see examples/quickstart.py)
    market = SpotMarket(Catalog(seed=args.seed, n_regions=2), seed=args.seed)
    service = SPSQueryService(market, n_accounts=2000)
    targets = [(t.name, r, az) for (t, r, az) in market.pool_keys[::7]][:args.targets]
    collector = DataCollector(service, targets, CollectorConfig(mode="usqs"))
    print(f"collecting {args.cycles} USQS cycles over {len(targets)} pools ...")
    collector.run(args.cycles)
    cands = collector.to_candidate_set()

    # 2. a burst of heterogeneous user requests (mixed targets and filters)
    rng = np.random.default_rng(args.seed)
    regions = sorted(set(cands.regions))
    reqs = []
    for i in range(args.requests):
        kw = ({"cpus": float(rng.integers(16, 640))} if i % 3 else
              {"memory_gb": float(rng.integers(64, 2048))})
        if i % 4 == 0:
            kw["regions"] = [regions[i % len(regions)]]
        reqs.append(ResourceRequest(weight=float(rng.uniform(0.2, 0.8)), **kw))

    # 3. serve them batched (archive staged on device, bucketed dispatch)
    engine = RecommendationEngine()
    server = BatchServer(engine)
    server.serve(cands, reqs)              # warm the per-bucket compile caches
    t0 = time.perf_counter()
    recs = server.serve(cands, reqs)
    t_batch = time.perf_counter() - t0

    # 4. the same work through the per-request loop
    for r in reqs:                         # warm every (filter, K_sub) shape
        engine.recommend(cands, r)
    t0 = time.perf_counter()
    for r in reqs:
        engine.recommend(cands, r)
    t_loop = time.perf_counter() - t0

    print(f"\nserved {len(recs)} requests over {len(cands)} candidates")
    print(f"  batched : {t_batch * 1e3:7.1f} ms "
          f"({len(recs) / t_batch:8.0f} req/s)")
    print(f"  loop    : {t_loop * 1e3:7.1f} ms "
          f"({len(recs) / t_loop:8.0f} req/s)")
    print(f"  speedup : {t_loop / t_batch:.1f}x   "
          f"buckets={server.stats.bucket_counts} "
          f"padded={server.stats.padded_slots}")

    rec = recs[0]
    print(f"\nfirst request -> {rec.num_types} types, "
          f"${rec.hourly_cost:.2f}/hr:")
    for n, az, cnt, s in zip(rec.names, rec.azs, rec.counts, rec.combined):
        print(f"  {n:<16} {az:<12} x{int(cnt):<3} S={s:6.2f}")


if __name__ == "__main__":
    main()
