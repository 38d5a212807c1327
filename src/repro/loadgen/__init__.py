"""Latency-SLO load harness: traffic generation over the serving stack.

The serving layers (``repro.serve`` batching + ``repro.stream`` admission)
had throughput numbers but no *latency-under-load* story — no p50/p99/p99.9,
no backpressure behavior, no answer for "what happens at 2x capacity".
This package closes that:

- :mod:`arrivals` — Poisson / diurnal (thinned inhomogeneous Poisson) /
  bursty (2-state MMPP) arrival processes, deterministic per seed;
- :mod:`workload` — request mixes over discrete signature populations:
  the filterless mask-dedup fast path, the distinct-mask worst case, and
  blends;
- :mod:`harness` — a closed-loop virtual-time driver: arrivals replay on a
  virtual clock, each drain's service time is the measured wall time of the
  real batched dispatch, and every ticket's end-to-end latency streams into
  the lock-guarded histograms on ``AdmissionStats``.

``benchmarks/latency_slo.py`` runs the {steady, diurnal, bursty} x
{filterless, distinct-mask} matrix plus a 2x-overload shedding scenario and
commits the tail-latency artifact CI gates against.
"""
from .arrivals import MMPP2, Arrivals, Diurnal, Steady  # noqa: F401
from .harness import (LoadHarness, LoadReport, VirtualClock)  # noqa: F401
from .workload import (DEFAULT_AMOUNTS, DEFAULT_WEIGHTS, RequestMix,  # noqa: F401
                       distinct_mask_mix, filterless_mix, mixed_mix)
