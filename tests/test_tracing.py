"""Program spans of the served path and of ingestion (``repro.tracing``)
and the read-back counter of ``ServeStats``.

Off, a span is a shared no-op and no ``TraceAnnotation`` is made; on, under
a profiler session, one drain writes ``repro.admission.drain`` around
``repro.serve`` around the engine's stages, in order, with their args, and
one poll writes ``repro.ingest.poll`` around a ``repro.ingest.append`` per
tick; the byte counter equals the result bytes the shapes give.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core import EngineConfig, RecommendationEngine, ResourceRequest
from repro.serve import BatchServer, DeviceArchive
from repro.stream import AdmissionQueue, LiveIngestor
from test_quantized_archive import WalkCollector
from test_serve_batch import synth_candidates

K, T = 64, 24
ENGINE_STAGES = ("repro.engine.masks", "repro.engine.dedup",
                 "repro.engine.dispatch", "repro.engine.readback",
                 "repro.engine.build")


@pytest.fixture(scope="module")
def cands():
    return synth_candidates(seed=5, K=K, T=T)


@pytest.fixture
def traced():
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()


def requests(n):
    """``n`` requests, every third filtered to one region (two distinct
    masks once n >= 3)."""
    return [ResourceRequest(cpus=float(8 * (1 + i % 4)),
                            regions=["us-east-1"] if i % 3 == 2 else None)
            for i in range(n)]


def tiled_server():
    return BatchServer(config=EngineConfig(score_impl="tiled"),
                       bucket_sizes=(1, 4, 16))


def test_off_by_default_and_switchable():
    assert not tracing.enabled()
    with tracing.span("repro.x", a=1) as s:
        s.set_metadata(b=2)
    assert tracing.span("repro.x") is tracing.span("repro.y", n=3)
    tracing.enable()
    try:
        assert tracing.enabled()
        assert isinstance(tracing.span("repro.x"),
                          jax.profiler.TraceAnnotation)
    finally:
        tracing.disable()
    assert not tracing.enabled()


def test_off_serve_enters_no_trace_annotation(cands, monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **args):
            made.append(name)
            super().__init__(name, **args)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    server = tiled_server()
    archive = DeviceArchive.stage(cands)
    server.serve(archive, requests(3))
    assert made == []
    tracing.enable()
    try:
        server.serve(archive, requests(3))
    finally:
        tracing.disable()
    assert made == ["repro.serve", *ENGINE_STAGES]


def _program_spans(logdir):
    """``(name, start, end, line, args)`` of every ``repro.*`` host event."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns, i,
                    dict(e.stats))
                   for i, line in enumerate(host.lines) for e in line.events
                   if e.name.startswith("repro.")), key=lambda s: s[1])


def test_drain_writes_nested_spans_with_args(cands, traced, tmp_path):
    server = tiled_server()
    queue = AdmissionQueue(server, DeviceArchive.stage(cands))
    n = 3
    for r in requests(n):
        queue.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert queue.drain(force=True) == n
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    names = [s[0] for s in spans]
    assert names == ["repro.admission.drain", "repro.serve", *ENGINE_STAGES]
    assert len({s[3] for s in spans}) == 1          # one thread
    drain, serve, *stages = spans
    assert drain[1] <= serve[1] and serve[2] <= drain[2]
    for s in stages:
        assert serve[1] <= s[1] and s[2] <= serve[2]
    for a, b in zip(stages, stages[1:]):
        assert a[2] <= b[1]                          # in order, disjoint
    args = {s[0]: s[4] for s in spans}
    assert args["repro.admission.drain"] == {"drain": 1, "taken": n,
                                             "pending": 0}
    assert args["repro.serve"] == {"rows": n, "chunks": 1}
    assert args["repro.engine.masks"] == {"rows": n, "bucket": 4}
    assert args["repro.engine.dedup"] == {"unique": 2, "padded": 2}
    assert args["repro.engine.dispatch"] == {}
    assert args["repro.engine.readback"] == {"d2h_bytes":
                                             server.stats.d2h_bytes}
    assert args["repro.engine.build"] == {"rows": n}


def test_pools_identical_with_tracing_on_and_off(cands):
    server = tiled_server()
    archive = DeviceArchive.stage(cands)
    reqs = requests(20)
    off = server.serve(archive, reqs)
    tracing.enable()
    try:
        on = server.serve(archive, reqs)
    finally:
        tracing.disable()
    for a, b in zip(off, on):
        assert list(a.names) == list(b.names)
        np.testing.assert_array_equal(a.counts, b.counts)
        assert a.hourly_cost == b.hourly_cost
        np.testing.assert_array_equal(a.combined, b.combined)
        assert a.diagnostics == b.diagnostics


def _ingestor(precision="int8", ticks=2):
    """A primed ``LiveIngestor`` with ``ticks`` collector ticks pending (and
    one more in its walk): each repeats a primed column, except that the
    second tick's first two samples lie past the int8 clip range."""
    rng = np.random.default_rng(3)
    primed = rng.uniform(0.0, 50.0, (K, T))
    walk = np.concatenate([primed, primed[:, :ticks + 1]], axis=1)
    walk[:2, T + 1] = 1e4
    col = WalkCollector(synth_candidates(seed=5, K=K, T=T), walk, T)
    ing = LiveIngestor(col, window=T, precision=precision)
    ing.prime()
    col.run(ticks)
    return ing


def test_off_ingest_enters_no_trace_annotation(monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **args):
            made.append(name)
            super().__init__(name, **args)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    ing = _ingestor(ticks=3)
    assert ing.poll() == 3 and made == []
    ing.collector.run(1)
    tracing.enable()
    try:
        ing.poll()
    finally:
        tracing.disable()
    assert made == ["repro.ingest.poll", "repro.ingest.append"]


@pytest.mark.parametrize("precision,h2d", [("int8", K), ("float32", 4 * K)])
def test_ingest_poll_spans_its_appends_with_args(traced, tmp_path,
                                                 precision, h2d):
    ing = _ingestor(precision)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert ing.poll() == 2
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    assert [s[0] for s in spans] == ["repro.ingest.poll",
                                     "repro.ingest.append",
                                     "repro.ingest.append"]
    poll, *appends = spans
    assert poll[4] == {"ticks": 2}
    for s in appends:
        assert poll[1] <= s[1] and s[2] <= poll[2]
    clipped = 2 if precision == "int8" else 0
    assert [s[4] for s in appends] == [
        {"clipped": 0, "h2d_bytes": h2d},
        {"clipped": clipped, "h2d_bytes": h2d}]
    assert ing.archive.clipped_samples == clipped


def _d2h(b):
    """Results read back: combined, availability, cost, order and counts
    (B, K) 4-byte; k_stop (B,) int32; any_term (B,) bool."""
    return 5 * 4 * b * K + 4 * b + b


@pytest.mark.parametrize("score_impl", ["tiled", "dense"])
def test_transfer_bytes_match_the_shapes(cands, score_impl):
    server = BatchServer(config=EngineConfig(score_impl=score_impl),
                         bucket_sizes=(1, 4, 16))
    server.serve(DeviceArchive.stage(cands), requests(20))   # 16 + 4
    assert server.stats.batches == 2
    assert server.stats.d2h_bytes == _d2h(16) + _d2h(4)

    moved = []
    RecommendationEngine(EngineConfig(score_impl=score_impl)).recommend_batch(
        cands, requests(3), pad_to=4, on_transfer=moved.append)
    assert moved == [_d2h(4)]
