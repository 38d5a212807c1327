"""Closed-loop operation: recommend, launch, reconcile, survive a burst.

Runs the operator end to end on the simulated market: pools are
recommended and launched, the reconcile loop keeps ingesting collector
ticks and re-reading node liveness from the market, and halfway through
the run a targeted interruption burst reclaims tracked nodes — the
operator must observe the deaths, re-recommend the wounded pools, and
refill them through phased, quorum-floored migrations:

    PYTHONPATH=src python examples/operator_loop.py --cycles 16

Compare benchmarks/operator_replay.py, which runs the same loop under a
full fault schedule (collector outages, delayed ticks, failing drains)
and gates the delivered-vs-recommended availability gap.
"""
import argparse

import numpy as np

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.core import EngineConfig, ResourceRequest
from repro.operator import Operator, OperatorConfig
from repro.stream import LiveIngestor
from repro.runtime import use_compile_cache


def delivered(op: Operator, market: SpotMarket) -> float:
    """Mean delivered capacity fraction over tracked pools (market truth)."""
    pools = op.cmdb.active_pools
    if not pools:
        return 1.0
    return float(np.mean([
        min(1.0, sum(m.capacity for m in p.members.values()
                     if market.node(m.node_id).alive) / p.amount)
        for p in pools]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--targets", type=int, default=48)
    ap.add_argument("--window", type=int, default=12)
    ap.add_argument("--cycles", type=int, default=16)
    ap.add_argument("--burst", type=int, default=6,
                    help="nodes reclaimed at the midpoint cycle")
    ap.add_argument("--period-min", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    # 1. the simulated market + collector, warmed to a full window
    market = SpotMarket(Catalog(seed=args.seed, n_regions=2), seed=args.seed)
    service = SPSQueryService(market, n_accounts=3000)
    targets = [(t.name, r, az)
               for (t, r, az) in market.pool_keys[::7]][:args.targets]
    collector = DataCollector(
        service, targets,
        CollectorConfig(period_min=args.period_min,
                        ring_capacity=max(args.window * 2, 16)))
    for _ in range(args.window):
        collector.collect_once()
        market.advance(market.now + args.period_min)

    # 2. serving stack + live ingestor, then the operator on top
    server = EngineConfig().build_server(bucket_sizes=(1, 2, 4, 8))
    ingestor = LiveIngestor(collector, window=args.window, cache=server.cache)
    ingestor.prime()
    op = Operator(server, ingestor, market,
                  config=OperatorConfig(cooldown_cycles=0, seed=args.seed))

    # 3. recommend + launch: the operator adopts every issued pool
    for req in (ResourceRequest(cpus=48.0, weight=0.5),
                ResourceRequest(cpus=24.0, weight=0.8),
                ResourceRequest(memory_gb=96.0, weight=0.3)):
        op.launch(req)
    print(f"launched {len(op.cmdb.active_pools)} pools, "
          f"{sum(len(p.alive_members) for p in op.cmdb.active_pools)} nodes")

    # 4. reconcile; a targeted burst lands halfway through
    for cycle in range(args.cycles):
        market.advance(market.now + args.period_min)
        if cycle == args.cycles // 2:
            # reclaim nodes until the biggest pool is genuinely short of
            # capacity (bounded by --burst) — a dent the operator must fix
            victim = max(op.cmdb.active_pools,
                         key=lambda p: len(p.alive_members))
            hit = 0
            while hit < args.burst:
                alive = [m for m in victim.members.values()
                         if market.node(m.node_id).alive]
                if sum(m.capacity for m in alive) < victim.amount:
                    break
                target = max(alive, key=lambda m: m.capacity)
                events = market.reclaim(*target.key, 1)
                if not events:
                    break
                hit += len(events)
            print(f"-- cycle {cycle}: injected burst, reclaimed {hit} nodes "
                  f"from pool {victim.pool_id}")
        op.reconcile_once()
        s = op.stats
        print(f"cycle {cycle:2d}  delivered={delivered(op, market):.3f}  "
              f"interruptions={s.interruptions_observed}  "
              f"rerecs={s.rerecommendations}  plans={s.migrations_planned}  "
              f"launches={s.launches}  retired={s.retirements}  "
              f"stale={s.stale_cycles}")

    # 5. the closed-loop contract: no wounded pool left unhandled
    unhandled = [p.pool_id for p in op.cmdb.active_pools
                 if p.interrupted_total > 0 and p.rerecommendations == 0
                 and p.plan is None and p.delivered_fraction() < 1.0]
    print(f"final delivered={delivered(op, market):.3f}  "
          f"risk triggers={dict(op.stats.risk_triggers)}  "
          f"unhandled pools={unhandled or 'none'}")


if __name__ == "__main__":
    main()
