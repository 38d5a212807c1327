"""Live-ingestion serving: the streaming collector -> recommendation loop.

Runs the Fig. 3 pipeline end to end, *live*: a simulated collector keeps
ticking, each tick flows into the serving layer as one O(K) column append
(rolling device archive + rank-1 statistics update — no re-staging, no
O(K*T) recompute), and requests arrive through the deadline-batched
admission queue, each drain pinned to one archive version:

    PYTHONPATH=src python examples/stream_serve.py --cycles 12

Compare examples/serve_batch.py, which serves one immutable snapshot.
"""
import argparse
import time

import numpy as np

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import RecommendationEngine, ResourceRequest
from repro.serve import ArchiveCache, BatchServer
from repro.stream import AdmissionQueue, LiveIngestor
from repro.runtime import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--targets", type=int, default=80)
    ap.add_argument("--window", type=int, default=24)
    ap.add_argument("--cycles", type=int, default=12)
    ap.add_argument("--requests-per-cycle", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    # 1. the live collector (host ring sized to keep column reads O(K))
    market = SpotMarket(Catalog(seed=args.seed, n_regions=2), seed=args.seed)
    service = SPSQueryService(market, n_accounts=2000)
    targets = [(t.name, r, az)
               for (t, r, az) in market.pool_keys[::7]][:args.targets]
    collector = DataCollector(
        service, targets,
        CollectorConfig(mode="usqs", ring_capacity=4 * args.window))
    print(f"priming: {args.window} USQS cycles over {len(targets)} pools ...")
    collector.run(args.window)

    # 2. collector -> rolling device archive -> versioned cache
    cache = ArchiveCache(capacity=4)
    ingestor = LiveIngestor(collector, window=args.window, cache=cache,
                            name="live")
    archive = ingestor.prime()
    print(f"staged {archive.key}: K={len(archive)}, T={archive.window_len}")

    # 3. deadline-batched admission in front of the batch server
    server = BatchServer(RecommendationEngine(), bucket_sizes=(1, 8, 64))
    queue = AdmissionQueue(server, lambda: ingestor.archive,
                           max_wait_s=0.02).start()

    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    try:
        for cycle in range(args.cycles):
            collector.run(1)                 # one live tick ...
            ingestor.poll()                  # ... absorbed in O(K)
            tickets = [
                queue.submit(ResourceRequest(
                    cpus=float(rng.integers(32, 1024)),
                    weight=float(np.round(rng.random(), 2))))
                for _ in range(args.requests_per_cycle)]
            recs = [t.result(timeout=30.0) for t in tickets]
            best = recs[0]
            print(f"tick {cycle + 1:>3}: {archive.key:>10}  "
                  f"lag={ingestor.lag}  "
                  f"first pool: {best.num_types} types, "
                  f"${best.hourly_cost:.2f}/hr "
                  f"(v{best.diagnostics['archive_version']})")
    finally:
        queue.stop()

    dt = time.perf_counter() - t0
    st = queue.stats
    print(f"\n{st.served} requests over {st.drains} drains "
          f"({st.coalesced} coalesced) across "
          f"{len(st.versions)} archive versions in {dt:.2f}s")
    print(f"server: {server.stats.batches} batches, "
          f"{server.stats.padded_slots} padded slots; "
          f"cache: {len(cache)} entries, {cache.nbytes / 2**20:.2f} MiB")


if __name__ == "__main__":
    main()
