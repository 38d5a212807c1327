"""Batched serving example: prefill a prompt batch, decode N tokens.

Runs a reduced config of any assigned architecture on CPU:

    PYTHONPATH=src python examples/serve_model.py --arch rwkv6-7b --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCH_IDS, get_config
from repro.models import get_model
from repro.runtime import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"{args.arch} (reduced): {model.num_params() / 1e6:.1f}M params")

    B, P = args.batch, args.prompt_len
    key = jax.random.key(1)
    prompt = jax.random.randint(key, (B, P), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": prompt}
    if cfg.encdec:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.frontend_len, cfg.d_model)).astype(jnp.bfloat16)
    if cfg.frontend == "vision":
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.frontend_len, cfg.d_model)).astype(jnp.bfloat16)

    max_len = P + args.tokens + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    cache = model.init_cache(B, max_len)

    t0 = time.perf_counter()
    prefill = jax.jit(model.prefill)
    logits, cache = prefill(params, batch, cache)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    generated = [tok]
    start = P + (cfg.frontend_len if cfg.frontend == "vision" else 0)
    t0 = time.perf_counter()
    for i in range(args.tokens - 1):
        key, sub = jax.random.split(key)
        logits, cache = decode(params, tok, cache, jnp.int32(start + i))
        tok = jax.random.categorical(
            sub, logits[:, -1].astype(jnp.float32) / args.temperature
        )[:, None].astype(jnp.int32)
        generated.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    out = jnp.concatenate(generated, axis=1)
    print(f"prefill: {t_prefill * 1e3:.1f} ms for {B}x{P} tokens")
    print(f"decode : {t_decode / max(args.tokens - 1, 1) * 1e3:.2f} ms/token "
          f"(batch {B})")
    for b in range(min(B, 2)):
        print(f"seq{b}: {[int(x) for x in out[b][:12]]}...")


if __name__ == "__main__":
    main()
