"""Program spans and device→host sync counts, on the profiler's clock.

The serving scheduler and engine and the pruning executor mark their
layer boundaries with ``span``. A span is recorded in memory as a
``Record`` (``time.perf_counter`` start and end, the enclosing span,
attributes) and, while it is open, is a ``jax.profiler.TraceAnnotation``
named ``repro:<name>``: in a profiler trace it sits on the host plane on
the device ops' clock, so a gap in the device's work carries the name of
the program phase the host was in. Spans of one request carry ``rid=``.

Every device→host sync in the instrumented code goes through ``wait``,
which makes it a ``<name>.wait`` span: each sync is both timed and
counted.

Recording is on while ``enable()`` is in force or a JAX profiler trace
is being captured (``jax.profiler.start_trace``). Off, ``span`` returns
one shared no-op context and ``wait`` is exactly the call it wraps.
Records stay in memory, in a bounded buffer, until ``clear()``. Spans
nest per process: instrument one thread.
"""
from __future__ import annotations

import collections
import time
from typing import NamedTuple

import jax

MAX_RECORDS = 1 << 16

_profiling = jax.profiler.TraceAnnotation.is_enabled


class Record(NamedTuple):
    id: int
    name: str
    t0: float                 # time.perf_counter() seconds
    t1: float
    parent: int | None        # id of the enclosing span
    attrs: dict


class _State:
    on = False
    next_id = 0
    stack: list = []
    records: collections.deque = collections.deque(maxlen=MAX_RECORDS)


def enable() -> None:
    _State.on = True


def disable() -> None:
    _State.on = False


def enabled() -> bool:
    """Whether spans are recorded now."""
    return _State.on or _profiling()


def records() -> list[Record]:
    """Closed spans, oldest first (children close before their parent)."""
    return list(_State.records)


def clear() -> None:
    _State.records.clear()


class Span:
    """An open span. ``set`` adds attributes; once closed, ``seconds`` is
    its duration. Only a recording span builds an annotation."""

    __slots__ = ("name", "attrs", "t0", "t1", "_record", "_id", "_parent",
                 "_ann")

    def __init__(self, name: str, attrs: dict, record: bool):
        self.name, self.attrs, self._record = name, attrs, record
        self.t0 = self.t1 = 0.0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        if self._record:
            st = _State
            self._id, st.next_id = st.next_id, st.next_id + 1
            self._parent = st.stack[-1] if st.stack else None
            st.stack.append(self._id)
            self._ann = jax.profiler.TraceAnnotation("repro:" + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        if self._record:
            self._ann.__exit__(*exc)
            _State.stack.pop()
            _State.records.append(Record(self._id, self.name, self.t0,
                                         self.t1, self._parent, self.attrs))


class _Off:
    """The span handed out while recording is off."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def span(name: str, /, **attrs):
    """A span named ``name`` (the no-op context while recording is off)."""
    if not (_State.on or _profiling()):
        return _OFF
    return Span(name, attrs, True)


def timed(name: str, /, **attrs) -> Span:
    """A span whose ``seconds`` the caller reads whether or not recording
    is on; off, it is two clock reads."""
    return Span(name, attrs, _State.on or _profiling())


def wait(x, name: str, get=jax.block_until_ready):
    """``get(x)``, the device→host sync, inside a ``<name>.wait`` span:
    ``jax.block_until_ready`` by default, ``np.asarray`` or
    ``jax.device_get`` to fetch values."""
    if not (_State.on or _profiling()):
        return get(x)
    with Span(name + ".wait", {}, True):
        return get(x)
