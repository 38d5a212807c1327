"""Ingest: mean device time of one collector tick in the traced stretch:
the read of the evicted column (``jit__read_col``) plus the donated slot
write with its fused ``stats_update`` call (``jit__append_step_q`` on a
quantized ring, ``jit__append_step`` on a float32 one)."""
import numpy as np

PROGRAMS = ("jit__read_col", "jit__append_step")


def read(run):
    if run.trace is None:
        return None
    means = []
    for prefix in PROGRAMS:
        runs = run.trace.module_runs(prefix)
        if not runs:
            return None
        means.append(np.mean([m.dur for m in runs]))
    return float(sum(means)) * 1e-6
