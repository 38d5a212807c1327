"""Seeded deployments: the candidate catalog and its T3 series.

The structure of a catalog (which (type, zone) pairs exist, their vCPUs and
memory) follows from the configuration alone; the seed draws the spot prices
and every candidate's T3 series.  T3 values lie on the paper's count grid
(multiples of 5 nodes, 0..50) and come from a clipped walk with a
per-candidate level and move probability, made on the device in one jitted
call.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CandidateSet


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two 32-bit words from any integer seed, for a threefry key."""
    return np.random.default_rng([seed, stream]).integers(
        0, 2 ** 32, 2, dtype=np.uint64).astype(np.uint32)


@dataclass
class Layout:
    names: np.ndarray
    regions: np.ndarray
    azs: np.ndarray
    families: np.ndarray
    categories: np.ndarray
    vcpus: np.ndarray
    memory_gb: np.ndarray
    od_price: np.ndarray       # on-demand $/h
    variant: np.ndarray        # widening index of the type table
    price_key: np.ndarray      # (vendor, type, region) id: one spot price each


def layout(cfg: dict) -> Layout:
    """Every (type, zone) candidate of the configuration, zone-contiguous:
    each zone offers the first ``candidates / zones`` types of its vendor's
    table, widened by variants (``m5``, ``m5-v1``, ...) of every family and
    size."""
    zones = [(v, r, f"{r}{chr(ord('a') + i)}") for v in cfg["vendors"]
             for r, n in v["regions"].items() for i in range(n)]
    k = int(cfg["candidates"])
    per, extra = divmod(k, len(zones))
    cols = {c: [] for c in ("names", "regions", "azs", "families",
                            "categories", "vcpus", "memory_gb", "od",
                            "variant", "pkey")}
    keys: dict = {}
    for z, (v, region, az) in enumerate(zones):
        base = [(fam, cat, spec, size, n) for cat, spec in v["categories"].items()
                for fam in spec["families"] for size, n in cfg["sizes"].items()]
        for i in range(per + (z < extra)):
            j, b = divmod(i, len(base))
            fam, cat, spec, size, n = base[b]
            name = f"{fam}{'' if j == 0 else f'-v{j}'}.{size}"
            cols["names"].append(name)
            cols["regions"].append(region)
            cols["azs"].append(az)
            cols["families"].append(fam)
            cols["categories"].append(cat)
            cols["vcpus"].append(n)
            cols["memory_gb"].append(n * spec["gb_per_vcpu"])
            cols["od"].append(n * spec["od_per_vcpu"])
            cols["variant"].append(j)
            cols["pkey"].append(keys.setdefault((v["name"], name, region),
                                                len(keys)))
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    return Layout(np.array(cols["names"]), np.array(cols["regions"]),
                  np.array(cols["azs"]), np.array(cols["families"]),
                  np.array(cols["categories"]), f64(cols["vcpus"]),
                  f64(cols["memory_gb"]), f64(cols["od"]),
                  np.asarray(cols["variant"], np.int64),
                  np.asarray(cols["pkey"], np.int64))


@functools.partial(jax.jit, static_argnames=("k", "steps"))
def _walk(key, *, k: int, steps: int, lo, hi, p_lo, p_hi, revert):
    """(k, steps) int8 T3 levels: a clipped walk per candidate."""
    k_level, k_p, k_steps = jax.random.split(key, 3)
    level = jax.random.randint(k_level, (k,), lo, hi + 1, jnp.int32)
    p = jax.random.uniform(k_p, (k,), jnp.float32, p_lo, p_hi)

    def step(y, key):
        k_move, k_dir = jax.random.split(key)
        move = jax.random.uniform(k_move, (k,), jnp.float32) < p
        back = jax.random.uniform(k_dir, (k,), jnp.float32) < revert
        toward = jnp.sign(level - y)
        coin = jnp.where(back, -1, 1)
        d = jnp.where(toward == 0, coin, jnp.where(back, toward, -toward))
        y = jnp.clip(y + jnp.where(move, d, 0), 0, 10)
        return y, y.astype(jnp.int8)

    _, ys = jax.lax.scan(step, level, jax.random.split(k_steps, steps))
    return ys.T


@dataclass
class Deployment:
    cands: CandidateSet        # the catalog and its (K, window) float32 T3
    window: int
    ticks: np.ndarray | None = None   # (K, n) float32: the columns after it


def build(cfg: dict, seed: int, ticks: int = 0) -> Deployment:
    """The configuration's catalog and T3 series for ``seed``.

    ``ticks`` more columns of the same walk follow the window: the columns
    a live collector appends, one per tick (``Deployment.ticks``).
    """
    lay = layout(cfg)
    k, t = len(lay.names), int(cfg["window"])
    walk = cfg["t3_walk"]
    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed, 0)))
    levels = np.asarray(jax.device_get(_walk(
        key, k=k, steps=t + ticks, lo=walk["level"][0], hi=walk["level"][1],
        p_lo=walk["p_move"][0], p_hi=walk["p_move"][1],
        revert=walk["revert"])))
    step = float(cfg["t3_counts"][2])
    series = levels[:, :t].astype(np.float32) * np.float32(step)
    pr = cfg["prices"]
    rng = np.random.default_rng([seed, 1])
    n_keys = int(lay.price_key.max()) + 1
    discount = rng.uniform(pr["discount"][0], pr["discount"][1], n_keys)
    regions, region_id = np.unique(lay.regions, return_inverse=True)
    markup = 1.0 + pr["region_markup"] * rng.random(len(regions))
    prices = (lay.od_price * (1.0 - discount[lay.price_key])
              * markup[region_id]
              * (1.0 + pr["variant_step"] * lay.variant))
    cands = CandidateSet(
        names=lay.names, regions=lay.regions, azs=lay.azs,
        families=lay.families, categories=lay.categories, vcpus=lay.vcpus,
        memory_gb=lay.memory_gb, prices=prices, t3=series)
    extra = (levels[:, t:].astype(np.float32) * np.float32(step)
             if ticks else None)
    return Deployment(cands, t, extra)
