"""Spans of the served paths, written into the profiler's own trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation`` named
``jalad.<name>``. While a profiler trace runs (``jax.profiler.trace(dir)``
or a client of ``jax.profiler.start_server(port)``) each span lands on
its host thread, on the clock of the device timeline; spans nest by
containment, and ``stats`` (small ints: ``uid``, ``slots``, ``rows``,
``requests``; or a short name: ``side``) are the event's stats. With no
trace running a span costs one to two microseconds of host time. The
profiler is the only switch. ``mark`` records a span of no length, for
counts the host learns after the work they count (the counts of the
routes a fetch brings back).

A span named ``sync`` marks the host waiting for the device: every
blocking device-to-host fetch on the served paths sits in one, and no
other span has that name.
"""
from __future__ import annotations

import jax

PREFIX = "jalad."


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """The context manager of span ``jalad.<name>`` with ``stats``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)


def mark(name: str, **stats) -> None:
    """A span ``jalad.<name>`` of no length, with ``stats``."""
    with span(name, **stats):
        pass
