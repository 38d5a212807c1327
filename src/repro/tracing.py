"""Host spans of the served path, on the profiler's clock.

``span(name, **args)`` marks one stage of the served path.  Off (the
default) it costs one read of a module flag and hands back a shared no-op
context: no object, no clock, no profiler call.  On, it is a
``jax.profiler.TraceAnnotation``, so under a profiler session it lands on
the host thread's line of the same trace as the device's ops, and ``args``
(small ints: drain id, rows, bytes) become the event's stats.  Spans nest
as the code does: a span's parent is the span that encloses it on the same
thread.  The profiler keeps the events and writes them out when its trace
stops; nothing is stored here.

Span names are ``repro.<layer>.<stage>``; the benchmark's reduction of
them against the device trace is ``bench/spans.py``.
"""
from __future__ import annotations

import jax

_on = False


class _Off:
    """The shared context every span is while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        """Args known only once the span is open (``TraceAnnotation`` has the
        same method)."""


_OFF = _Off()


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def span(name: str, **args):
    """A context manager spanning one stage; see the module docstring."""
    if not _on:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)
