"""Mixture-of-Experts: top-k router + capacity-based scatter dispatch.

TPU-native adaptation: instead of GPU-style ragged grouped GEMMs, tokens are
scattered into a dense (experts, capacity, d_model) buffer and expert MLPs
run as one batched matmul on the MXU (the kernels/moe_gmm.py Pallas kernel
implements exactly this (E, C, D) x (E, D, F) contraction with VMEM tiling).
With experts sharded over the "model" axis the scatter/gather lowers to the
EP all-to-all pair.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import constrain
from .param import ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    m = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.d_ff
    s = {
        "router": ParamSpec((D, E), ("embed", "experts"), dtype=jnp.float32),
        "w1": ParamSpec((E, D, F), ("experts", "embed", "ffn")),
        "w3": ParamSpec((E, D, F), ("experts", "embed", "ffn")),
        "w2": ParamSpec((E, F, D), ("experts", "ffn", "embed")),
    }
    if m.num_shared_experts:
        Fs = m.d_ff * m.num_shared_experts
        s["shared_w1"] = ParamSpec((D, Fs), ("embed", "ffn"))
        s["shared_w3"] = ParamSpec((D, Fs), ("embed", "ffn"))
        s["shared_w2"] = ParamSpec((Fs, D), ("ffn", "embed"))
    return s


def capacity_of(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(m.top_k * n_tokens / m.num_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)  # multiple of 8 for TPU lane alignment


def apply_moe(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: (B, S, D) → (y, aux_loss).  Dispatches to the shard_map EP path on
    a real mesh (see _apply_moe_shardmap); the single-device scatter path
    below doubles as its correctness oracle."""
    mesh = cfg.mesh
    if (cfg.moe_impl in ("auto", "shardmap")
            and mesh is not None and "model" in getattr(mesh, "axis_names", ())
            and mesh.shape["model"] > 1
            and cfg.moe.num_experts % mesh.shape["model"] == 0):
        return _apply_moe_shardmap(cfg, p, x)
    return _apply_moe_local(cfg, p, x)


def _apply_moe_local(cfg: ModelConfig, p: dict, x: jax.Array):
    m = cfg.moe
    B, S, D = x.shape
    N = B * S
    E, K = m.num_experts, m.top_k
    C = capacity_of(cfg, N)
    xt = x.reshape(N, D)

    logits = (xt.astype(jnp.float32) @ p["router"])            # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # (N, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # Position of each (token, slot) within its expert's capacity buffer.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)    # (N, K, E)
    flat_oh = onehot.reshape(N * K, E)
    pos_in_expert = (jnp.cumsum(flat_oh, axis=0) - flat_oh)    # (N*K, E)
    pos = (pos_in_expert * flat_oh).sum(-1).reshape(N, K)      # (N, K)
    keep = pos < C
    gate_vals = gate_vals * keep

    # Scatter tokens into the (E, C, D) dispatch buffer.
    e_flat = expert_idx.reshape(-1)
    pos_flat = jnp.where(keep, pos, C).reshape(-1)             # overflow -> C (dropped)
    buf = jnp.zeros((E, C + 1, D), x.dtype)
    src = jnp.repeat(xt, K, axis=0) if K > 1 else xt
    buf = buf.at[e_flat, pos_flat].set(src)
    buf = buf[:, :C]                                           # (E, C, D)
    buf = constrain(buf, cfg, ("model", None, None))           # EP all-to-all

    # Batched expert MLP — the MXU-friendly (E, C, D) x (E, D, F) contraction.
    if cfg.use_pallas:
        from ..kernels import ops as kops
        hid = kops.moe_gmm(buf, p["w1"], p["w3"])
        out_buf = kops.moe_gmm_down(hid, p["w2"])
    else:
        hid = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) \
            * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
        out_buf = jnp.einsum("ecf,efd->ecd", hid, p["w2"])     # (E, C, D)

    # Gather back, weighted by gates.
    gathered = out_buf[e_flat, jnp.minimum(pos_flat, C - 1)]   # (N*K, D)
    y = (gathered.reshape(N, K, D) *
         gate_vals[..., None].astype(x.dtype)).sum(1)

    if m.num_shared_experts:
        h = jax.nn.silu(xt @ p["shared_w1"]) * (xt @ p["shared_w3"])
        y = y + h @ p["shared_w2"]

    # Switch-style load-balancing auxiliary loss.
    me = probs.mean(0)                                         # (E,)
    ce = (onehot.sum(1) > 0).astype(jnp.float32).mean(0)       # fraction routed
    aux = (me * ce).sum() * E * m.aux_loss_coef
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# shard_map EP path (production mesh)
# ---------------------------------------------------------------------------
#
# On the (data, model) mesh, boundary activations are replicated over the
# "model" axis while experts are sharded over it.  Each device therefore
# already holds every token it could need: it routes locally, runs *its*
# E/tp experts on the tokens assigned to them, and one psum over "model"
# sums the partial expert outputs (the same collective pattern as TP-FFN).
# No dispatch all-to-all, no partitioner-inferred gathers — the naive
# scatter path costs ~100 GB/layer/device of involuntary all-gathers at
# deepseek scale (measured in the §Perf log); this path costs one
# (B_loc, S, D) all-reduce.

def _apply_moe_shardmap(cfg: ModelConfig, p: dict, x: jax.Array):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh = cfg.mesh
    tp = mesh.shape["model"]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    E_loc = E // tp

    x_spec = P(dp, None, None) if (dp and B % _dp_size(mesh) == 0) \
        else P(None, None, None)
    p_specs = {
        "router": P(),
        "w1": P("model", None, None),
        "w3": P("model", None, None),
        "w2": P("model", None, None),
    }
    if m.num_shared_experts:
        p_specs["shared_w1"] = P(None, "model")
        p_specs["shared_w3"] = P(None, "model")
        p_specs["shared_w2"] = P("model", None)

    def local_moe(p_loc, x_loc):
        Bl, Sl, _ = x_loc.shape
        N = Bl * Sl
        C = capacity_of(cfg, N)
        xt = x_loc.reshape(N, D)
        logits = xt.astype(jnp.float32) @ p_loc["router"]       # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)
        flat_oh = onehot.reshape(N * K, E)
        pos = ((jnp.cumsum(flat_oh, axis=0) - flat_oh) * flat_oh).sum(-1)
        pos = pos.reshape(N, K)
        keep = pos < C
        gate_vals = gate_vals * keep

        # keep only the experts this model-rank owns
        j = jax.lax.axis_index("model")
        e_lo = j * E_loc
        mine = (expert_idx >= e_lo) & (expert_idx < e_lo + E_loc)
        e_local = jnp.clip(expert_idx - e_lo, 0, E_loc - 1)
        slot = jnp.where(mine & keep, pos, C)                   # C = drop slot

        buf = jnp.zeros((E_loc, C + 1, D), x_loc.dtype)
        src = jnp.repeat(xt, K, axis=0) if K > 1 else xt
        buf = buf.at[e_local.reshape(-1), slot.reshape(-1)].set(src)
        buf = buf[:, :C]

        if cfg.use_pallas:
            from ..kernels import ops as kops
            hid = kops.moe_gmm(buf, p_loc["w1"], p_loc["w3"])
            out_buf = kops.moe_gmm_down(hid, p_loc["w2"])
        else:
            hid = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p_loc["w1"])) \
                * jnp.einsum("ecd,edf->ecf", buf, p_loc["w3"])
            out_buf = jnp.einsum("ecf,efd->ecd", hid, p_loc["w2"])

        gathered = out_buf[e_local.reshape(-1),
                           jnp.minimum(slot.reshape(-1), C - 1)]
        w = (gate_vals * mine)[..., None].astype(x_loc.dtype)
        y = (gathered.reshape(N, K, D) * w).sum(1)

        if m.num_shared_experts:                # TP-sharded shared experts
            h = jax.nn.silu(xt @ p_loc["shared_w1"]) * (xt @ p_loc["shared_w3"])
            y = y + h @ p_loc["shared_w2"]
        y = jax.lax.psum(y, "model")            # sum partial expert outputs

        me = probs.mean(0)
        ce = (onehot.sum(1) > 0).astype(jnp.float32).mean(0)
        aux = (me * ce).sum() * E * m.aux_loss_coef
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(Bl, Sl, D), aux

    fn = jax.shard_map(local_moe, mesh=mesh,
                       in_specs=(p_specs, x_spec),
                       out_specs=(x_spec, P()))
    return fn(p, x)


def _dp_size(mesh) -> int:
    import numpy as _np
    return int(_np.prod([mesh.shape[a] for a in ("pod", "data")
                         if a in mesh.axis_names]))
