"""Tests of the benchmark itself, run on the CPU:

    python -m pytest bench/tests

They shrink the cells to a size a test run holds (fewer candidates, a
shorter window, a lower rate) and keep everything else, the limits of the
output check among it.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

# A live configuration and cell that only the tests run: the first
# configuration's catalog on an int8 rolling archive fed a column every
# 0.5 s, under the first cell's traffic.
LIVE_CONFIG = "live-int8"
LIVE_CELL = "live-int8.overload-distinct"
INGEST = [{"name": name, "unit": unit, "better": better,
           "source": "device_trace", "layer": "ingest", "moves": "rps",
           "workloads": [LIVE_CELL]}
          for name, unit, better in (("tick_ms.rps", "ms", "lower"),
                                     ("stats_update_roofline.rps", "%",
                                      "higher"))]


def add_live(doc: dict, tmp: Path) -> None:
    """Add ``LIVE_CONFIG`` and ``LIVE_CELL`` to ``doc`` (a copy of
    ``BENCHMARK.json`` whose files lie under ``tmp``), with the ingest
    layer's metrics."""
    base = doc["configs"][0]
    cfg = json.loads((tmp / base["file"]).read_text())
    cfg["archive"] = {"kind": "rolling", "precision": "int8",
                      "headroom": 1.0}
    cfg["ingest"] = {"period_s": 0.5}
    cfg["limits"]["versions_off"] = 0
    path = f"bench/configs/{LIVE_CONFIG}.json"
    (tmp / path).write_text(json.dumps(cfg))
    doc["configs"].append({**base, "name": LIVE_CONFIG, "file": path})
    cell = next(w for w in doc["workloads"] if w["config"] == base["name"])
    doc["workloads"].append({**cell, "name": LIVE_CELL,
                             "config": LIVE_CONFIG})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if cell["name"] in m.get("workloads", ()):
            m["workloads"].append(LIVE_CELL)
    doc["per_layer"] += INGEST


def shrink(tmp: Path, candidates: int = 2048, window: int = 48,
           rate_scale: float = 0.1, sample: int | None = 16) -> Path:
    """A copy of the benchmark under ``tmp`` with every cell cut down and
    the live cell added; ``sample=None`` keeps each cell's own sample
    size."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        cfg["candidates"], cfg["window"] = candidates, window
        path.write_text(json.dumps(cfg))
    add_live(doc, tmp)
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    for path in (tmp / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        for k in ("rate", "rate_low", "rate_high"):
            if k in t["arrivals"]:
                t["arrivals"][k] *= rate_scale
        if sample is not None:
            t["check"]["sample"] = sample
        path.write_text(json.dumps(t))
    return tmp


@pytest.fixture
def small(tmp_path):
    from bench import spec
    root = shrink(tmp_path)
    return spec.Bench(root, root / "bench")


@pytest.fixture
def loaded(tmp_path):
    """Cut down as ``small``, at half the cells' rates, so that drains
    carry several requests, and with each cell's own sample size."""
    from bench import spec
    root = shrink(tmp_path, rate_scale=0.5, sample=None)
    return spec.Bench(root, root / "bench")
