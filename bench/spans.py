"""The program's own spans in a profiler trace, reduced against the device.

``repro.tracing`` marks each stage of the served path with a host span
named ``repro.<layer>.<stage>`` whose args (drain id, rows, bytes) are the
event's stats.  ``trace.load`` keeps only the benchmark's ``bench.*`` host
spans; ``program`` here reads the program's from the same parsed
``ProfileData``, with the thread line each ran on, so one pass over the
file serves both, and the functions below put them beside the
``trace.Trace`` of that file (same clock, same window).

A run records the spans only with the program's tracer on
(``repro.tracing.enable()``), and a span only if the profiler was on both
when it opened and when it closed: to see the spans open at the edges of
the stretch, the profiler has to run past both edges by the longest span
(a drain lasts ~0.65 s in the overload cell).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from bench import trace

DRAIN = "repro.admission.drain"
SERVE = "repro.serve"
DISPATCH = "repro.engine.dispatch"
READBACK = "repro.engine.readback"
PROGRAM = "jit__fused_recommend_batch"


@dataclass
class Span(trace.Event):
    """One ``repro.*`` host event."""

    line: int = 0        # the thread's line, by position on ``/host:CPU``
    args: dict = field(default_factory=dict)


def program(data) -> list:
    """The ``repro.*`` spans of a parsed trace (``jax.profiler.ProfileData``),
    by start."""
    host = data.find_plane_with_name("/host:CPU")
    if host is None:
        return []
    return sorted((Span(e.name, e.start_ns, e.duration_ns, i, dict(e.stats))
                   for i, line in enumerate(host.lines) for e in line.events
                   if e.name.startswith("repro.")), key=lambda s: s.start)


def whole(tr: trace.Trace, spans, name: str) -> list:
    """The spans named ``name`` that lie wholly inside the window."""
    return [s for s in spans
            if s.name == name and s.start >= tr.t0 and s.end <= tr.t1]


def mean_ms(tr: trace.Trace, spans, name: str) -> float | None:
    """Mean duration of the whole spans named ``name``, in ms."""
    runs = whole(tr, spans, name)
    return sum(s.dur for s in runs) / len(runs) * 1e-6 if runs else None


def readback_ms(tr: trace.Trace, spans) -> float | None:
    """Mean time a whole read-back span runs on after the end of the served
    program's execution it overlaps: the transfer to the host and the
    conversion, not the wait for the device."""
    runs = whole(tr, spans, READBACK)
    if not runs:
        return None
    mods = [m for m in tr.modules[0] if m.name.startswith(PROGRAM)]
    after = []
    for s in runs:
        ends = [m.end for m in mods if m.start < s.end and m.end > s.start]
        after.append(s.end - max([s.start] + ends))
    return sum(after) / len(after) * 1e-6


def drain_self_ms(tr: trace.Trace, spans) -> float | None:
    """Mean self time of a whole drain span: its duration less the serve
    span inside it on its thread (the earliest-deadline-first selection,
    the archive snapshot, stamping answers, resolving tickets)."""
    drains = whole(tr, spans, DRAIN)
    if not drains:
        return None
    serves = [s for s in spans if s.name == SERVE]
    own = [d.dur - trace.union_length(trace.clip(
        [s for s in serves if s.line == d.line], d.start, d.end))
        for d in drains]
    return sum(own) / len(own) * 1e-6


def idle_by_span(tr: trace.Trace, spans, chip: int = 0) -> list:
    """A chip's idle time in the window put down to the deepest span open
    at each idle instant on the thread that dispatches the served program
    (the line holding most dispatch spans): ``[[span, seconds]]``, largest
    first; ``none`` is idle time no span covers.  Spans of one thread nest,
    so the deepest cover is the one that opened last."""
    lines: dict = {}
    for s in spans:
        if s.name == DISPATCH:
            lines[s.line] = lines.get(s.line, 0) + 1
    line = max(lines, key=lines.get) if lines else None
    mine = [s for s in spans if s.line == line]
    out: dict = {}
    for a, b in trace.gaps(tr, chip):
        near = [s for s in mine if s.start < b and s.end > a]
        cuts = sorted({a, b} | {t for s in near for t in (s.start, s.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [s for s in near if s.start <= x and s.end >= y]
            name = (max(cover, key=lambda s: (s.start, -s.dur)).name
                    if cover else "none")
            out[name] = out.get(name, 0.0) + (y - x)
    return [[k, v * 1e-9]
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])]
