"""End-to-end driver: train an LM on a SpotVista-provisioned spot cluster.

The full loop the paper's infrastructure enables: provision via the
recommendation engine → data-parallel training with int8-compressed gradient
exchange → interruptions handled by checkpoint-restore + engine-driven
re-provision → straggler ejection.

    PYTHONPATH=src python examples/train_elastic.py --steps 300 --preset small

`--preset full100m` trains a ~100M-parameter qwen2-family model (slow on this
CPU container; the default preset is a reduced config of the same family).
"""
import argparse
import pathlib
import tempfile

import numpy as np

from repro.cloudsim import (Catalog, CollectorConfig, DataCollector,
                            SpotMarket, SPSQueryService)
from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.data import make_pipeline
from repro.elastic import ElasticConfig, SpotElasticTrainer
from repro.models import get_model
from repro.runtime import use_compile_cache

PRESETS = {
    # reduced same-family config: fast on CPU
    "small": dict(num_layers=4, d_model=128, num_heads=4, num_kv_heads=2,
                  head_dim=32, d_ff=512, vocab_size=2048, seq=128, batch=8),
    # ~100M-parameter config (takes hours of CPU for hundreds of steps)
    "full100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                     head_dim=64, d_ff=3072, vocab_size=32768, seq=512, batch=8),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--preset", choices=list(PRESETS), default="small")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--minutes-per-step", type=float, default=10.0,
                    help="simulated market minutes per training step")
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    use_compile_cache()

    p = PRESETS[args.preset]
    cfg = get_config("qwen2-0.5b").reduced(
        num_layers=p["num_layers"], d_model=p["d_model"],
        num_heads=p["num_heads"], num_kv_heads=p["num_kv_heads"],
        head_dim=p["head_dim"], d_ff=p["d_ff"], vocab_size=p["vocab_size"])
    model = get_model(cfg)
    print(f"model: qwen2-family reduced, {model.num_params() / 1e6:.1f}M params")

    market = SpotMarket(Catalog(seed=args.seed, n_regions=2), seed=args.seed)
    service = SPSQueryService(market, n_accounts=2000)
    targets = [(t.name, r, az) for (t, r, az) in market.pool_keys[::9]][:60]
    collector = DataCollector(service, targets, CollectorConfig())
    collector.run(25)

    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=20,
                       total_steps=args.steps)
    pipeline = make_pipeline(cfg, seq_len=p["seq"], global_batch=p["batch"],
                             seed=args.seed)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="spotvista_ckpt_")
    trainer = SpotElasticTrainer(
        model, tcfg, market, collector.to_candidate_set(),
        ElasticConfig(nodes_wanted=args.nodes, checkpoint_every=25,
                      compress_grads=not args.no_compress),
        pipeline, ckpt_dir, seed=args.seed)

    print(f"training {args.steps} steps on {len(trainer.nodes)} spot nodes "
          f"(pools: {sorted({n.pool[0] for n in trainer.nodes})})")
    out = trainer.train(args.steps, minutes_per_step=args.minutes_per_step)

    losses = out["losses"]
    k = max(len(losses) // 10, 1)
    print(f"\nloss: first10 {np.mean(losses[:k]):.3f} -> last10 "
          f"{np.mean(losses[-k:]):.3f}")
    print(f"gradient wire bytes: {out['wire_bytes'] / 1e6:.1f} MB "
          f"({'int8+EF' if not args.no_compress else 'fp32'})")
    print(f"final pool size: {out['final_nodes']}")
    if out["events"]:
        print("events:")
        for e in out["events"][-12:]:
            print(f"  step {e.step:>4} {e.kind:<12} {e.detail}")
    print(f"checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
