"""Pallas TPU kernels (tested under interpret=True on CPU).

- flash_attention : causal GQA flash attention (online softmax, VMEM stats)
- rwkv6_scan      : chunked WKV6 linear-attention scan (state in VMEM)
- rglru_scan      : chunked RG-LRU diagonal recurrence (log-depth in-chunk)
- moe_gmm         : grouped expert matmul on (E, C, D) capacity buffers
- pool_scan       : tiled Algorithm 1 all-prefix termination scan (O(K)
                    memory vs the dense K x K matrix; SMEM scratch carry)
                    with a ``lax`` CPU/GPU fallback — the production
                    large-K path behind ``core.pool``'s ``pool_impl``
- score_fuse      : streaming masked Eq. 2-4 scoring (per-request masked
                    MinMax / C_min scalars in SMEM carry, tiled row
                    emission) over archive-cached per-candidate statistics
                    — the large-K scoring stage behind the engine's
                    ``score_impl``, with a ``lax.scan`` CPU/GPU fallback
- stats_update    : O(K) rank-1 update of the Eq. 3 candidate statistics
                    when the live collector appends/evicts one T3 column
                    (compensated float32 moment pairs, elementwise tiles)
                    — the per-tick path behind ``repro.stream``'s rolling
                    archives, with a vectorized CPU/GPU fallback

Each has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py
(pool_scan's oracle is the dense scan + greedy_pool loop in core/pool.py,
and its dispatch lives in pool_scan.pool_scan).

On a TPU the three recommender kernels compile through Mosaic; their blocks
are (tile // 128, 128) row views of the candidate axis.  Each passes its
name to ``pallas_call``, which names its custom call in the compiled
program, so :func:`compiled_kernels` can tell which of them a program runs.
"""
import re

_CUSTOM_CALL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')


def compiled_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels a compiled TPU program runs natively
    (Mosaic custom calls in ``Compiled.as_text()``).  A batched kernel is
    named ``vmap(<name>)``, which HLO spells ``vmap_<name>_``."""
    return {re.sub(r"^(vmap_)+", "", name).rstrip("_")
            for name in _CUSTOM_CALL.findall(hlo_text)}
