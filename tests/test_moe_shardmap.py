"""shard_map EP MoE vs the single-device scatter oracle (8 host devices).

Runs in a subprocess because the device count must be fixed before JAX
initialises (the main test process runs single-device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import MoEConfig, ModelConfig
    from repro.models import moe as moe_lib
    from repro.models.param import init_params

    from repro.launch.mesh import auto_mesh
    mesh = auto_mesh((2, 4), ("data", "model"))
    base = ModelConfig(
        arch_id="t", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, head_dim=8, d_ff=64, vocab_size=128,
        moe=MoEConfig(num_experts=8, num_shared_experts=1, top_k=2, d_ff=48,
                      capacity_factor=8.0))   # ample capacity: no drops
    cfg_local = dataclasses.replace(base, moe_impl="scatter")
    cfg_sm = dataclasses.replace(base, mesh=mesh, moe_impl="shardmap")

    params = init_params(moe_lib.moe_specs(base), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (4, 16, 32), jnp.float32).astype(jnp.bfloat16)

    y0, aux0 = jax.jit(lambda p, x: moe_lib.apply_moe(cfg_local, p, x))(params, x)
    y1, aux1 = jax.jit(lambda p, x: moe_lib.apply_moe(cfg_sm, p, x))(params, x)
    err = float(jnp.abs(y0.astype(jnp.float32) - y1.astype(jnp.float32)).max())
    aux_err = abs(float(aux0) - float(aux1))
    print(f"ERR={err:.6f} AUXERR={aux_err:.6f}")
    assert err < 3e-2, err
    assert aux_err < 1e-3, (float(aux0), float(aux1))

    # gradients agree too
    def loss(c):
        def f(p, x):
            y, aux = moe_lib.apply_moe(c, p, x)
            return (y.astype(jnp.float32) ** 2).mean() + aux
        return f
    g0 = jax.jit(jax.grad(loss(cfg_local)))(params, x)
    g1 = jax.jit(jax.grad(loss(cfg_sm)))(params, x)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        gerr = float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        scale = float(jnp.abs(a.astype(jnp.float32)).max()) + 1e-6
        assert gerr / scale < 5e-2, (a.shape, gerr, scale)
    print("GRADS_OK")
""")


def test_shardmap_matches_scatter_oracle():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "GRADS_OK" in res.stdout, res.stdout
