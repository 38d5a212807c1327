"""Ahead-of-time compiles of the served and ingest kernels for a TPU v5e.

Interpret mode runs the kernel bodies in Python and accepts block shapes and
vector ops that Mosaic, the TPU kernel compiler, refuses.  These tests
compile the three Pallas kernels of the main path with ``interpret=False``
for a *described* v5e chip (no chip attached) at the archive sizes the
engine serves, and check that each one lands in the compiled program as a
Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and test collection
happens in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compiled_kernels
from repro.kernels import pool_scan as ps
from repro.kernels import score_fuse as sf
from repro.kernels import stats_update as su

B = 64                                   # the server's largest default bucket
PALLAS = {"backend": "pallas", "interpret": False}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but never read back
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _pool(s, c, r):
    return ps.pool_scan(s, c, r, **PALLAS)


def _score(area, slope, std, prices, vcpus, mem, mask, use_cpus, req, lam,
           wt, lo, hi):
    return sf.score_fuse(area, slope, std, prices, vcpus, mem, mask,
                         use_cpus, req, lam, wt, extrema=(lo, hi), **PALLAS)


def _stats(m, y_new, y_old, y_first, y_last, scale=None):
    return su.stats_update(m, y_new, y_old, y_first, y_last,
                           jnp.float32(1008), jnp.asarray(True),
                           scale=scale, **PALLAS)


def _operands(case, k, sharding):
    f32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=sharding)
    flag = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bool_, sharding=sharding)
    moments = su.StreamMoments(*(f32(k),) * 7)
    if case == "pool_scan":
        return _pool, (f32(k), f32(k), f32())
    if case == "pool_scan_vmap":
        return jax.vmap(_pool), (f32(B, k), f32(B, k), f32(B))
    if case == "score_fuse":
        return _score, ((f32(k),) * 6 + (flag(k), flag())
                        + (f32(),) * 3 + (f32(3), f32(3)))
    if case == "score_fuse_vmap":
        return (jax.vmap(_score, in_axes=(None,) * 6 + (0,) * 7),
                (f32(k),) * 6 + (flag(B, k), flag(B))
                + (f32(B),) * 3 + (f32(B, 3), f32(B, 3)))
    if case == "stats_update":
        return _stats, (moments,) + (f32(k),) * 4
    if case == "stats_update_int8":
        code = jax.ShapeDtypeStruct((k,), jnp.int8, sharding=sharding)
        return _stats, (moments,) + (code,) * 4 + (f32(k),)
    raise AssertionError(case)


@pytest.mark.parametrize("k", [2 ** 15, 2 ** 20])
@pytest.mark.parametrize("case", ["pool_scan", "pool_scan_vmap", "score_fuse",
                                  "score_fuse_vmap", "stats_update",
                                  "stats_update_int8"])
def test_kernel_compiles_for_v5e(one_chip, case, k):
    fn, args = _operands(case, k, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert case.removesuffix("_vmap").removesuffix("_int8") \
        in compiled_kernels(text)


def test_compiled_kernels_reads_custom_call_names():
    text = ('  %pool_scan.1 = (s32[8,128]) custom-call(%a), '
            'custom_call_target="tpu_custom_call", api_version=X\n'
            '  %fusion.3 = f32[8] fusion(%b), kind=kLoop\n'
            '  %vmap_score_fuse_ = (f32[8,128]) custom-call(%c), '
            'custom_call_target="tpu_custom_call"\n')
    assert compiled_kernels(text) == {"pool_scan", "score_fuse"}
    assert compiled_kernels("%x = f32[] add(%a, %b)") == set()

