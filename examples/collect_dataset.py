"""Dataset-collection example: USQS vs TSTP vs full scan under query limits.

Shows the §3 trade-off live: per-cycle query budgets, T3 accuracy against
the simulator ground truth, and what the 50-scenario/24h account limit means
for each strategy.

    PYTHONPATH=src python examples/collect_dataset.py --cycles 20
"""
import argparse

import numpy as np

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.runtime import use_compile_cache


def collect(mode: str, seed: int, cycles: int, n_targets: int, accounts: int):
    market = SpotMarket(Catalog(seed=seed, n_regions=1), seed=seed)
    service = SPSQueryService(market, n_accounts=accounts)
    targets = [(t.name, r, az) for (t, r, az) in market.pool_keys[::11]][:n_targets]
    col = DataCollector(service, targets, CollectorConfig(mode=mode))
    col.run(cycles)
    errs = []
    for tgt in targets:
        truth = market.t3_true(*tgt, t=col.times[-1])
        errs.append(abs(col.t3_archive[tgt][-1] - truth))
    return service.total_queries, float(np.mean(errs)), float(np.median(errs))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=20)
    ap.add_argument("--targets", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    print(f"{'mode':<10} {'queries':>8} {'accounts needed':>16} "
          f"{'mean|err|':>10} {'median':>7}")
    for mode, accounts in (("usqs", 50), ("tstp", 400), ("full", 2000)):
        q, mean_e, med_e = collect(mode, args.seed, args.cycles,
                                   args.targets, accounts)
        # each account: 50 distinct scenarios / 24h
        need = int(np.ceil(q / args.cycles / 50 * (1440 / 10 / args.cycles + 1)))
        print(f"{mode:<10} {q:>8} {need:>16} {mean_e:>10.2f} {med_e:>7.1f}")
    print("\nUSQS: 1 query/target/cycle; TSTP: ~7-12; full scan: 50 "
          "(the paper's 165k-queries-for-50-counts problem).")


if __name__ == "__main__":
    main()
