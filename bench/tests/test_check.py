"""The output check fails what it must: the control (the reference in the
next precision below the configuration's, in the program's place) and each
fault the cells can have, planted in the timed path underneath a whole run.
The harness's look for a chip is skipped; everything else runs."""
import time

import pytest

from bench import runner
from conftest import LIVE_CELL

CELLS = ["aws-32k-f32-adaptive.overload-distinct", LIVE_CELL]
# the reference in the precision below the configuration's archive
CONTROL = {"aws-32k-f32-adaptive.overload-distinct": "bfloat16",
           LIVE_CELL: "int4"}


def run(bench, cell, fault=None, control=None, seed=2 ** 31 + 99):
    return runner.run_cell(bench, bench.cell(cell), seed, 3.0, False,
                           time.monotonic(), cache_dir=None,
                           require_tpu=False, fault=fault, control=control)


def over(numbers: dict) -> list:
    return [k for k, v in numbers.items() if v["value"] > v["limit"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small, cell):
    out = run(small, cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(small, cell):
    out = run(small, cell, control=CONTROL[cell])
    limits = {k: v["limit"] for k, v in out["check"].items()}
    failed = [k for k, v in out["control"].items() if v > limits[k]]
    assert failed, (out["control"], limits)


def half_batch(system):
    """Each dispatch computes only the first half of its requests and hands
    the rest copies of those answers."""
    eng = system.server.engine
    real = eng.recommend_batch

    def recommend_batch(cands, requests, **kw):
        requests = list(requests)
        h = max(1, len(requests) // 2)
        recs = real(cands, requests[:h], **kw)
        return recs + [recs[i % h] for i in range(len(requests) - h)]
    eng.recommend_batch = recommend_batch


def altered_answer(system):
    """Every answer leaves the engine with one node more per member."""
    eng = system.server.engine
    real = eng._build_recommendations

    def build(*args, **kw):
        recs = real(*args, **kw)
        for r in recs:
            r.counts = r.counts + 1
        return recs
    eng._build_recommendations = build


def one_row(system):
    """One answer of each dispatch, its last row, leaves the engine with one
    node more for its first member; every other answer is sound."""
    eng = system.server.engine
    real = eng._build_recommendations

    def build(*args, **kw):
        recs = real(*args, **kw)
        r = recs[-1]
        r.counts = r.counts.copy()
        r.counts[0] += 1
        return recs
    eng._build_recommendations = build


@pytest.mark.parametrize("fault", [half_batch, altered_answer, one_row])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(loaded, cell, fault):
    out = run(loaded, cell, fault=fault)
    assert not out["correct"], out["check"]
    assert over(out["check"])
