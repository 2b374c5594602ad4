"""The program's own spans (``repro.runtime.trace``) in a run's window.

The program records its spans while a profiler trace is being captured,
which a ``--trace 1`` run does over exactly its window; the readers of
the metrics that rest on them take them from here after the run. Where
the program has no such module (a checkout older than it), every reader
finds nothing and its metric is left out of the line.
"""
from __future__ import annotations


def in_window(run) -> list:
    """The program's closed spans that lie inside the run's window."""
    try:
        from repro.runtime import trace
    except ImportError:
        return []
    if run.window is None or run.window[1] is None:
        return []
    w0, w1 = run.window
    return [r for r in trace.records() if w0 <= r.t0 and r.t1 <= w1]


def named(recs: list, name: str) -> list:
    return [r for r in recs if r.name == name]


def waits(recs: list) -> list:
    """The device→host syncs: ``<name>.wait`` spans."""
    return [r for r in recs if r.name.endswith(".wait")]
