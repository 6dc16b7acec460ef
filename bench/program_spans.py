"""The program's own spans (``repro.spans``), as the metric readers see
them: the window's spans, per-step sums, and device idle time put down to
the innermost program span open at the time.

The program stamps its spans with ``time.perf_counter_ns()``, the host
clock ``ctx.t0`` and ``ctx.t1`` are read on; the trace extract is on the
profiler's clock.  The window anchors one to the other: the harness's
window span ``(lo, hi)`` opens and closes within microseconds of ``t0``
and ``t1``, so a program time ``t`` lies at ``lo + (t - t0)`` on the
trace's clock.  Where the two windows' lengths differ by ``ANCHOR_TOL_S``
or more, the mapping is not trusted and the reader reports nothing.

A program without ``repro.spans`` records no spans: every function here
then gives None, and the readers stay silent.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import trace

ANCHOR_TOL_S = 1e-3
STEP = "sched/step"
PULL = "host_pull"


def window_spans(ctx) -> list | None:
    """The program spans that start in the window ``[ctx.t0, ctx.t1)``,
    in order of start; None where the program records none."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.spans(ctx.t0, ctx.t1) or None


def _children(sp: list) -> dict:
    kids: dict[int, list] = {}
    for s in sp:
        kids.setdefault(s.parent, []).append(s)
    return kids


def _descendants(root, kids: dict) -> list:
    out, todo = [], list(kids.get(root.index, ()))
    while todo:
        s = todo.pop()
        out.append(s)
        todo.extend(kids.get(s.index, ()))
    return out


def _union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, -np.inf
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def per_step_ms(sp: list, prefixes: tuple[str, ...],
                self_time: bool) -> list[float]:
    """For each ``sched/step`` span: the time its descendants named with
    one of ``prefixes`` cover (their union), or with ``self_time`` the
    step's duration less that, in ms."""
    kids = _children(sp)
    out = []
    for step in (s for s in sp if s.name == STEP):
        covered = _union_ns((d.start, d.end)
                            for d in _descendants(step, kids)
                            if d.name.startswith(prefixes))
        ns = (step.end - step.start) - covered if self_time else covered
        out.append(ns / 1e6)
    return out


def anchor(tr: dict, t0: float, t1: float):
    """The map from program time (perf_counter ns) to the trace's clock,
    or None (with the reason on stderr) where the window's length on the
    two clocks differs by ``ANCHOR_TOL_S`` or more."""
    lo, hi = trace.window(tr)
    skew = abs((hi - lo) / 1e9 - (t1 - t0))
    if skew >= ANCHOR_TOL_S:
        print(f"program spans: window is {(hi - lo) / 1e9} s on the trace "
              f"and {t1 - t0} s on the host clock ({skew} s apart, limit "
              f"{ANCHOR_TOL_S}); not put on the trace's clock",
              file=sys.stderr)
        return None
    base = t0 * 1e9
    return lambda t_ns: lo + (t_ns - base)


def innermost(sp: list, to_trace, lo: float, hi: float) -> list:
    """Segments ``(a, b, span, parent)`` on the trace's clock that cover
    ``[lo, hi]`` and beyond: in each, ``span`` is the innermost program
    span open (None where none is) and ``parent`` the span around it.
    Spans of one thread nest, so a stack of the open ones suffices."""
    out, stack, cur = [], [], lo

    def top(k):
        return stack[-k][0] if len(stack) >= k else None

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][1] <= t:
            s, b = stack.pop()
            if b > cur:
                out.append((cur, b, s, top(1)))
                cur = b
        if t > cur:
            out.append((cur, t, top(1), top(2)))
            cur = t

    for s in sorted(sp, key=lambda s: (s.start, -s.end, s.index)):
        close_until(to_trace(s.start))
        stack.append((s, to_trace(s.end)))
    close_until(hi)
    return out


def _label(s, parent) -> str:
    if s is None:
        return "none"
    if s.name == PULL:
        return (f"{PULL}:{s.attrs.get('site')} "
                f"({parent.name if parent is not None else 'none'})")
    return s.name


def _in_tiers(s, parent) -> bool:
    if s is None:
        return False
    if s.name == PULL:
        return parent is not None and parent.name.startswith("tier/")
    return s.name.startswith("tier/")


def idle_by_span(tr: dict, sp: list, t0: float, t1: float):
    """``(table, tier_s)``: device idle seconds in the traced window by the
    innermost open program span (``host_pull`` by site, with the span it
    ran under) and ``none``, largest first; and the idle seconds in which
    that span is a ``tier/*`` span or a ``host_pull`` under one.  None
    where the anchor fails."""
    to_trace = anchor(tr, t0, t1)
    if to_trace is None:
        return None
    lo, hi = trace.window(tr)
    segs = innermost(sp, to_trace, lo, hi)
    gaps = trace.idle_gaps(tr)
    table: dict[str, float] = {}
    tier_ns, i, j = 0.0, 0, 0
    while i < len(segs) and j < len(gaps):
        a0, b0, s, parent = segs[i]
        a, b = max(a0, gaps[j][0]), min(b0, gaps[j][1])
        if b > a:
            name = _label(s, parent)
            table[name] = table.get(name, 0.0) + (b - a) / 1e9
            if _in_tiers(s, parent):
                tier_ns += b - a
        if b0 < gaps[j][1]:
            i += 1
        else:
            j += 1
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in rows], tier_ns / 1e9
