"""The program's spans reduced against the device trace (``bench.spans``).

``serve.xplane.pb``: 2 s of a traced run of
``aws-32k-f32-adaptive.overload-distinct`` on a TPU v5e with the program's
tracer on and the profiler running 1 s past both edges of the stretch, cut
down to what the reductions read (chip 0's ``XLA Ops`` and ``XLA Modules``,
the host's ``bench.*`` and ``repro.*`` events)."""
from pathlib import Path

import pytest

from bench import spans, trace

DATA = Path(__file__).parent / "data"
MS = 1e6                                   # ns
STAGES = ("repro.engine.masks", "repro.engine.dedup", "repro.engine.dispatch",
          "repro.engine.readback", "repro.engine.build")


def program(name):
    from jax.profiler import ProfileData
    return spans.program(ProfileData.from_file(str(DATA / name)))


@pytest.fixture(scope="module")
def served():
    path = str(DATA / "serve.xplane.pb")
    return trace.load(path), program("serve.xplane.pb")


def E(start, dur, name="op"):
    return trace.Event(name, float(start), float(dur))


def S(name, start, dur, line=0, **args):
    return spans.Span("repro." + name, float(start), float(dur), line, args)


def test_idle_by_span_takes_the_deepest_span_on_the_dispatch_thread():
    """Idle 10-60 and 70-100 of a 0-100 window; the dispatching thread
    (line 0) runs drain 5-95 > serve 10-90 > masks, dispatch, readback,
    build; line 1 covers everything but dispatches nothing."""
    t = trace.Trace(0.0, 100.0, 1, ops=[[E(0, 10), E(60, 10)]], modules=[[]])
    got = spans.idle_by_span(t, [
        S("admission.drain", 5, 90), S("serve", 10, 80),
        S("engine.masks", 12, 8), S("engine.dispatch", 20, 5),
        S("engine.readback", 25, 45), S("engine.build", 70, 10),
        S("other", 0, 100, line=1)])
    want = {"repro.engine.readback": 35, "repro.serve": 12,
            "repro.engine.build": 10, "repro.engine.masks": 8,
            "repro.engine.dispatch": 5, "repro.admission.drain": 5,
            "none": 5}
    assert dict(got) == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert [v for _, v in got] == sorted((v for _, v in got), reverse=True)


def test_idle_by_span_without_program_spans_is_all_none():
    t = trace.Trace(0.0, 100.0, 1, ops=[[E(0, 10)]], modules=[[]])
    assert spans.idle_by_span(t, []) == [["none", pytest.approx(90e-9)]]


def test_readback_counts_only_what_follows_the_execution():
    """Two executions end 10 ms before their read-back spans do; a third
    read-back overlaps only another program and counts whole."""
    t = trace.Trace(0.0, 300 * MS, 1, ops=[[]], modules=[[
        E(0, 50 * MS, spans.PROGRAM + ".1"),
        E(100 * MS, 50 * MS, spans.PROGRAM + ".1"),
        E(170 * MS, 10 * MS, "jit_other")]])
    got = spans.readback_ms(t, [
        S("engine.readback", 20 * MS, 40 * MS),
        S("engine.readback", 90 * MS, 70 * MS),
        S("engine.readback", 175 * MS, 4 * MS),
        S("engine.readback", 290 * MS, 20 * MS)])     # cut by the window
    assert got == pytest.approx((10 + 10 + 4) / 3)


def test_drain_self_subtracts_its_own_serve():
    t = trace.Trace(0.0, 300 * MS, 1, ops=[[]], modules=[[]])
    got = spans.drain_self_ms(t, [
        S("admission.drain", 0, 100 * MS), S("serve", 10 * MS, 80 * MS),
        S("serve", 0, 100 * MS, line=1),
        S("admission.drain", 150 * MS, 50 * MS),
        S("serve", 160 * MS, 30 * MS)])
    assert got == pytest.approx((20 + 20) / 2)


def test_mean_over_whole_spans():
    t = trace.Trace(10 * MS, 100 * MS, 1, ops=[[]], modules=[[]])
    got = [S("engine.masks", 5 * MS, 10 * MS),       # cut by the window
           S("engine.masks", 20 * MS, 2 * MS),
           S("engine.masks", 40 * MS, 4 * MS),
           S("engine.build", 50 * MS, 40 * MS)]
    assert spans.mean_ms(t, got, "repro.engine.masks") == pytest.approx(3.0)
    assert spans.mean_ms(t, got, "repro.engine.dedup") is None


def test_recorded_spans_carry_their_args(served):
    _, got = served
    assert program("live.xplane.pb") == []
    assert {s.name for s in got} == {spans.DRAIN, spans.SERVE, *STAGES}
    assert len({s.line for s in got}) == 1
    drains = [s for s in got if s.name == spans.DRAIN]
    assert [d.args["drain"] for d in drains] == list(
        range(drains[0].args["drain"], drains[0].args["drain"] + len(drains)))
    for s in got:
        want = {spans.DRAIN: {"drain", "taken", "pending"},
                spans.SERVE: {"rows", "chunks"},
                "repro.engine.masks": {"rows", "bucket"},
                "repro.engine.dedup": {"unique", "padded"},
                spans.DISPATCH: set(),
                spans.READBACK: {"d2h_bytes"},
                "repro.engine.build": {"rows"}}[s.name]
        assert set(s.args) == want
    # five (B, 32768) 4-byte results and two (B,) ones, for a bucket B
    for s in got:
        if s.name == spans.READBACK:
            b, rest = divmod(s.args["d2h_bytes"], 32768 * 20 + 4 + 1)
            assert rest == 0 and b in (1, 8, 64, 256)


def test_spans_and_device_ops_share_one_clock(served):
    """Every whole execution of the served program lies between the start
    of the dispatch span that launched it and the end of the read-back span
    that followed that dispatch."""
    tr, got = served
    disp = [s for s in got if s.name == spans.DISPATCH]
    pairs = [(max((d for d in disp if d.end <= r.start),
                  key=lambda d: d.start), r)
             for r in got if r.name == spans.READBACK]
    runs = tr.module_runs(spans.PROGRAM)
    assert runs
    for m in runs:
        assert len([1 for d, r in pairs
                    if d.start <= m.start and m.end <= r.end]) == 1


def test_idle_is_put_down_to_named_spans(served):
    tr, got = served
    by = dict(spans.idle_by_span(tr, got))
    idle = sum(b - a for a, b in trace.gaps(tr)) * 1e-9
    assert sum(by.values()) == pytest.approx(idle)
    assert by.get("none", 0.0) < 0.1 * idle
    assert set(by) <= {"none", spans.DRAIN, spans.SERVE, *STAGES}


def test_reductions_read_the_recorded_trace(served):
    tr, got = served
    for value in (spans.mean_ms(tr, got, "repro.engine.masks"),
                  spans.mean_ms(tr, got, "repro.engine.dedup"),
                  spans.mean_ms(tr, got, "repro.engine.build"),
                  spans.readback_ms(tr, got), spans.drain_self_ms(tr, got)):
        assert value is not None and 0.0 < value < 1000.0
