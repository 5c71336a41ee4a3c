"""Spans and counters of the fabric's own work, on the profiler's clock.

``span(name, **stats)`` marks one stretch of host work — planning a
stream, marshalling operands, dispatching the engine, splitting a batch
— as a ``jax.profiler.TraceAnnotation`` named ``fabric:<name>``.  Under a
profile (``jax.profiler.trace``) the span lands on the host plane, on
the clock the device planes use, so it sits over the device operations
it launched.  With no profiler session an annotation costs one check.

The stats are the counters: keyword integers recorded on the span where
the work happens (``events`` planned, ``bytes`` handed to the device,
``compiled`` when the call grew a jit cache, ``instances`` carried), so
any ratio is taken at its boundary.  Stats known only at the end of the
work are added with ``handle.stat(...)`` before the span closes.

While :func:`enable` is in force each span is also kept in memory as a
:class:`Span`: its name, start and end (``time.time_ns()``, the clock
the profiler stamps its events with), the id of its parent span, the id
of the call it belongs to (a span opened with no span around it starts a
new call; nested spans share their root's) and its stats.
:func:`drain` returns the kept spans and clears them::

    from repro.core import tracing
    tracing.enable()
    res = fabric.run(spec)
    for s in tracing.drain():
        print(s.name, (s.end_ns - s.start_ns) / 1e3, "us", s.stats)
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: prefix of every span's annotation name
PREFIX = "fabric:"


class Span(NamedTuple):
    """One kept span."""
    name: str            # without ``PREFIX``
    start_ns: int        # time.time_ns() at entry
    end_ns: int          # time.time_ns() at exit
    id: int              # unique among the spans of this process
    parent: int | None   # id of the enclosing span, None for a root
    call: int            # id of the root span's call
    stats: dict


_on = False
_kept: list[Span] = []
_local = threading.local()
_ids = itertools.count(1)
_calls = itertools.count(1)


def enable(on: bool = True) -> None:
    """Keep spans in memory from now on (``enable(False)`` stops)."""
    global _on
    _on = bool(on)


def drain() -> list[Span]:
    """The spans kept since the last drain, by start time; clears them."""
    global _kept
    out, _kept = _kept, []
    return sorted(out, key=lambda s: (s.start_ns, s.id))


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """Context manager of one span; ``with span(...) as sp`` gives the
    handle whose :meth:`stat` adds counters before the span closes."""

    __slots__ = ("name", "stats", "_ann", "_t0", "_id", "_parent",
                 "_call")

    def __init__(self, name: str, **stats):
        self.name, self.stats = name, stats

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(PREFIX + self.name, **self.stats)
        self._ann.__enter__()
        self._id = None
        if _on:
            st = _stack()
            up = st[-1] if st else None
            self._parent = up._id if up is not None else None
            self._call = up._call if up is not None else next(_calls)
            self._id = next(_ids)
            st.append(self)
            self._t0 = time.time_ns()
        return self

    def stat(self, **stats) -> None:
        """Add counters to the open span (annotation and kept record)."""
        self.stats.update(stats)
        self._ann.set_metadata(**stats)

    def __exit__(self, *exc):
        if self._id is not None:
            t1 = time.time_ns()
            st = _stack()
            if st and st[-1] is self:
                st.pop()
            _kept.append(Span(self.name, self._t0, t1, self._id,
                              self._parent, self._call, dict(self.stats)))
        self._ann.__exit__(*exc)
        return False
