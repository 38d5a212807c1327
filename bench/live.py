"""A seeded collector for a live deployment: what the program's
``repro.stream.LiveIngestor`` reads, made from the benchmark's own walk.

``LiveIngestor`` reads a collector through three members: ``ticks`` (the
columns completed so far), ``column(i)`` (the (K,) T3 column of tick
``i``, counted from the collector's first) and ``to_candidate_set(window=)``
(the catalog with its newest ``window`` columns).  ``Collector`` answers
them from one walk of ``window + ticks`` columns (``catalog.build``): the
first ``window`` columns are the archive the ingestor primes, column
``window + i`` is tick ``i``, and a tick becomes visible only when
``collect()`` is called, as a real collector's next column appears only
when its collection cycle completes.
"""
from __future__ import annotations

import math
from dataclasses import replace

import numpy as np

SLACK_TICKS = 4        # ticks held beyond the window's own, for late closes


def ticks_for(cfg: dict, seconds: float) -> int:
    """Columns a run needs after the primed window: one absorbed in
    warm-up, one per ``ingest.period_s`` of the window (the pump's first
    tick lands as it opens), and a few to spare."""
    period = float(cfg["ingest"]["period_s"])
    return 2 + math.ceil(seconds / period) + SLACK_TICKS


class Collector:
    """The stand-in collector over one ``catalog.Deployment`` with ticks."""

    def __init__(self, dep):
        if dep.ticks is None:
            raise ValueError("the deployment was built without tick columns")
        self.cands = dep.cands
        self.window = dep.window
        self.extra = dep.ticks
        self.visible = 0               # ticks made visible by collect()

    @property
    def ticks(self) -> int:
        return self.window + self.visible

    def collect(self) -> None:
        """Complete one collection cycle: the next column becomes visible."""
        if self.visible >= self.extra.shape[1]:
            raise RuntimeError(f"all {self.extra.shape[1]} tick columns of "
                               "the walk are used")
        self.visible += 1

    def column(self, i: int) -> np.ndarray:
        if not 0 <= i < self.ticks:
            raise IndexError(f"column {i} of {self.ticks} completed")
        if i < self.window:
            return self.cands.t3[:, i]
        return self.extra[:, i - self.window]

    def to_candidate_set(self, window: int | None = None):
        window = self.ticks if window is None else int(window)
        cols = [self.column(i) for i in range(self.ticks - window,
                                              self.ticks)]
        return replace(self.cands, t3=np.stack(cols, axis=1))
