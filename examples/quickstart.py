"""Quickstart: collect a multi-node availability dataset and get a
recommendation — the full SpotVista pipeline in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py --cpus 160
"""
import argparse

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import RecommendationEngine, ResourceRequest
from repro.runtime import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpus", type=float, default=160.0)
    ap.add_argument("--weight", type=float, default=0.5, help="W: avail vs cost")
    ap.add_argument("--cycles", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    # 1. a (simulated) cloud + the rate-limited SPS query service
    market = SpotMarket(Catalog(seed=args.seed, n_regions=2), seed=args.seed)
    service = SPSQueryService(market, n_accounts=2000)

    # 2. the Fig-3 data collector: USQS over all (type, region, az) targets
    targets = [(t.name, r, az) for (t, r, az) in market.pool_keys[::7]][:80]
    collector = DataCollector(service, targets,
                              CollectorConfig(period_min=10, mode="usqs"))
    print(f"collecting {args.cycles} USQS cycles over {len(targets)} pools ...")
    collector.run(args.cycles)
    print(f"  total SPS queries: {service.total_queries} "
          f"(full-scan equivalent: {len(targets) * args.cycles * 50})")

    # 3. score + recommend a heterogeneous pool (Algorithm 1)
    engine = RecommendationEngine()
    rec = engine.recommend(collector.to_candidate_set(),
                           ResourceRequest(cpus=args.cpus, weight=args.weight))
    print(f"\nrecommended pool for {args.cpus:.0f} vCPUs (W={args.weight}):")
    print(f"{'instance':<16} {'az':<16} {'nodes':>5} {'S_i':>7} "
          f"{'AS_i':>7} {'CS_i':>7}")
    for i in range(rec.num_types):
        print(f"{rec.names[i]:<16} {rec.azs[i]:<16} {rec.counts[i]:>5} "
              f"{rec.combined[i]:>7.1f} {rec.availability[i]:>7.1f} "
              f"{rec.cost[i]:>7.1f}")
    print(f"\nestimated hourly cost: ${rec.hourly_cost:.3f}  "
          f"(candidates considered: {rec.diagnostics['candidates_considered']}, "
          f"solve: {rec.diagnostics['solve_time_s'] * 1e3:.2f} ms)")

    # 4. verify the pick with real spot requests (Wu et al. probing)
    from repro.cloudsim import probe_real_availability
    pools = [(rec.names[i], rec.regions[i], rec.azs[i])
             for i in range(rec.num_types)]
    probes = probe_real_availability(market, pools, n_nodes=int(rec.counts.max()),
                                     period_min=30, duration_min=360)
    for p in probes:
        print(f"probe {p.target[0]:<16} success "
              f"{p.successes}/{p.attempts} -> real availability "
              f"{p.real_availability:.0f}%")


if __name__ == "__main__":
    main()
