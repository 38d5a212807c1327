"""Shared neural building blocks (pure-functional, bf16-first)."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .param import ParamSpec


def constrain(x: jax.Array, cfg, template: tuple) -> jax.Array:
    """Activation sharding constraint from a template of {"dp","model","sp",None}.

    "dp" shards over the data-parallel axes, "model" over the tensor-parallel
    axis, "sp" over "model" only when cfg.sp (sequence parallelism knob).
    Dims that don't divide evenly fall back to replicated.  No-op off-mesh.
    """
    mesh = cfg.mesh
    if mesh is None or mesh.size == 1:
        return x
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if getattr(cfg, "dp_only", False) and "model" in mesh.axis_names:
        dp = dp + ("model",)           # pure-DP scheme: model axis joins DP
    dp_sz = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    used_model = False
    parts = []
    for dim, t in zip(x.shape, template):
        if t == "dp" and dp and dim % dp_sz == 0:
            parts.append(dp)
        elif t in ("model", "sp") and not used_model \
                and (t == "model" or cfg.sp) \
                and not getattr(cfg, "dp_only", False) \
                and "model" in mesh.axis_names and dim % mesh.shape["model"] == 0:
            parts.append("model")
            used_model = True
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def rmsnorm_spec(dim: int) -> ParamSpec:
    return ParamSpec((dim,), ("embed",), dtype=jnp.float32, init="ones")


def tp_project_rs(h: jax.Array, w: jax.Array, cfg, *, contract_model_dims: int):
    """TP output projection with an explicit reduce-scatter (Megatron g-op).

    ``h``: activations whose model-sharded dims are contracted by ``w``
    (e.g. heads×head_dim, or the ffn hidden).  The plain einsum leaves a
    partial sum that GSPMD lowers to a full all-reduce (wire 2(n-1)/n·bytes);
    here shard_map computes the local partial and ``psum_scatter``s over the
    sequence dim (wire (n-1)/n·bytes — half), leaving the output in the
    sequence-parallel layout the next block consumes anyway.

    Falls back to the plain einsum when the mesh/shape doesn't allow it
    (decode Sq=1, replicated attention heads, no mesh).
    """
    mesh = cfg.mesh
    if contract_model_dims == 2:
        ein = "bshk,hkd->bsd"
        h_spec_dims = ("model", None)         # h: (B, S, H, Dh), H sharded
        w_spec = P("model", None, None)
    else:
        ein = "bsf,fd->bsd"
        h_spec_dims = ("model",)              # h: (B, S, F), F sharded
        w_spec = P("model", None)

    def plain_path():
        y = jnp.einsum(ein, h, w)
        return constrain(y, cfg, ("dp", "sp", None))

    if mesh is None or "model" not in getattr(mesh, "axis_names", ()) \
            or mesh.shape["model"] == 1 or not cfg.sp \
            or getattr(cfg, "tp_impl", "gspmd") != "shardmap":
        return plain_path()
    tp = mesh.shape["model"]
    S = h.shape[1]
    shard_dim_size = h.shape[2]
    if S % tp != 0 or shard_dim_size % tp != 0:
        return plain_path()
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    B = h.shape[0]
    dp_sz = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bdim = dp if (dp and B % dp_sz == 0) else None

    h_spec = P(bdim, None, *h_spec_dims)
    out_spec = P(bdim, "model", None)

    def local(hl, wl):
        y = jnp.einsum(ein, hl, wl)           # local partial sum
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1, tiled=True)

    return jax.shard_map(local, mesh=mesh, in_specs=(h_spec, w_spec),
                         out_specs=out_spec)(h, w)


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for `positions` (any leading shape), half-dim layout."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, Dh); cos/sin: (..., S, half) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    """Gated-SiLU MLP: (silu(x@w1) * (x@w3)) @ w2."""
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def geglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    h = jax.nn.gelu(x @ w1) * (x @ w3)
    return h @ w2


def mlp_specs(d_model: int, d_ff: int, prefix_axes=()) -> dict:
    """Gated MLP parameter structure (w1/w3 sharded on ffn, w2 on ffn-in)."""
    return {
        "w1": ParamSpec((d_model, d_ff), ("embed", "ffn")),
        "w3": ParamSpec((d_model, d_ff), ("embed", "ffn")),
        "w2": ParamSpec((d_ff, d_model), ("ffn", "embed")),
    }
