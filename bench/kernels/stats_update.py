"""Cost of one ``stats_update`` call: a collector tick's rank-1 update of
the streaming moments and the Eq. 3 statistics, for K candidates.

One Pallas call over (K / 128, 128) row blocks: a (1, 2) parameter block,
the seven float32 moment rows (three Neumaier pairs and the centering
point), the four column rows (new, evicted, first, last: int8 codes on the
int8 tier, float32 otherwise) and, on the int8 tier, the float32 step row
in; the six moment rows and the three statistics rows out.  Each array
counts once.  Bound by memory; ``FLOPS_PER_LANE`` is a floor on the eight
compensated adds and the derivation per candidate.
"""
from bench import trace

PATTERN = r"stats_update"
FLOPS_PER_LANE = 40


def cost(results, operands) -> tuple[float, float]:
    lanes = trace.elements(results[0])
    return (FLOPS_PER_LANE * lanes,
            float(trace.nbytes(results) + trace.nbytes(operands)))

