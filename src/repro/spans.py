"""Program spans: a process-wide flight recorder of the serving path.

``span(name, **attrs)`` times one piece of host work.  It opens a
``jax.profiler.TraceAnnotation`` of the same name (so the span lands in a
profiler trace when one is running, and costs next to nothing otherwise),
stamps ``time.perf_counter_ns()`` at entry and exit, and on exit appends a
:class:`Span` record to a bounded in-memory ring.  A span's ``parent`` is
the index of the span open around it on the same thread (-1 at the root).

``pull(x, site)`` is the one way the serving path brings a device value to
the host: ``np.asarray(x)`` inside a ``host_pull`` span that names its
``site``.  The number of host pulls is the number of those spans.

Names are ``layer/what`` (``sched/step``, ``engine/advance``,
``tier/flush``, ...) plus ``host_pull``.  The recorder is always on; read
it with :func:`spans` and :func:`self_time`.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

import jax
import numpy as np

# Spans the ring holds: about 25 a scheduler step at ~21 steps/s is ~27k
# for a 51 s window.
CAPACITY = 65_536
PULL = "host_pull"


class Span(NamedTuple):
    index: int          # this span's number, in order of entry
    name: str
    start: int          # perf_counter_ns at entry
    end: int            # perf_counter_ns at exit
    parent: int         # index of the enclosing span; -1 at the root
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


class _Local(threading.local):
    def __init__(self):
        self.stack: list[int] = []     # indices of the open spans


class _Ring:
    """The last ``CAPACITY`` closed spans, one preallocated list per field.
    The attribute slots start as empty dicts, so each recorded span frees
    the dict it replaces: recording adds nothing for the garbage collector
    to count or walk."""

    def __init__(self):
        self.index = [None] * CAPACITY
        self.name = [None] * CAPACITY
        self.start = [0] * CAPACITY
        self.end = [0] * CAPACITY
        self.parent = [0] * CAPACITY
        self.attrs = [{} for _ in range(CAPACITY)]
        self.written = itertools.count()

    def put(self, sp: "span") -> None:
        i = next(self.written) % CAPACITY
        self.index[i], self.name[i] = sp.index, sp.name
        self.start[i], self.end[i] = sp.start, sp.end
        self.parent[i], self.attrs[i] = sp.parent, sp.attrs

    def records(self):
        return (Span(*r) for r in zip(self.index, self.name, self.start,
                                      self.end, self.parent, self.attrs)
                if r[0] is not None)


_ring = _Ring()
_index = itertools.count()
_local = _Local()


class span:
    """Context manager recording one span (see module docstring).
    ``elapsed`` is its duration in seconds: so far while it is open, the
    whole once it has closed."""

    __slots__ = ("name", "attrs", "index", "parent", "start", "end", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.end = None

    def __enter__(self) -> "span":
        stack = _local.stack
        self.parent = stack[-1] if stack else -1
        self.index = next(_index)
        stack.append(self.index)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _ring.put(self)

    @property
    def elapsed(self) -> float:
        end = time.perf_counter_ns() if self.end is None else self.end
        return (end - self.start) / 1e9


def pull(x, site: str) -> np.ndarray:
    """``np.asarray(x)`` recorded as a ``host_pull`` span at ``site``."""
    with span(PULL, site=site):
        return np.asarray(x)


def spans(t0: float, t1: float) -> list[Span]:
    """The recorded spans that start in ``[t0, t1)`` (perf_counter
    seconds), in order of start."""
    lo, hi = t0 * 1e9, t1 * 1e9
    return sorted((s for s in _ring.records() if lo <= s.start < hi),
                  key=lambda s: s.start)


def self_time(sp: Span, among: list[Span]) -> float:
    """``sp``'s duration in seconds less what its children in ``among``
    cover (children run one after another, so their durations add)."""
    return sp.seconds - sum(c.seconds for c in among if c.parent == sp.index)
