"""Profiler trace capture and its reduction to device numbers.

The reduction works on a plain extract of the profiler's XSpace: device
events (op name, program name, start, duration in ns) and the harness's
own host spans, all on the profiler's one clock.  Tests feed it a small
recorded extract (``tests/bench/data``).
"""
from __future__ import annotations

import glob
import os

import numpy as np

# Host spans the harness records (bench/serve.py); the window span bounds
# the traced window.
WINDOW_SPAN = "bench.window"
SPANS = ("sched.step", "advance_lanes", "prefill_lane", "daemon.tick")


def short(name: str) -> str:
    """An XLA op event's name without its HLO text: ``%fusion.12 = ...``
    becomes ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(logdir: str) -> dict:
    """Device ops (short names) and programs of TPU 0, and the harness's
    spans, from the one trace under ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[short(ev.name), ev.start_ns, ev.duration_ns]
                            for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [[ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[ev.name, ev.start_ns, ev.duration_ns]
                          for ev in line.events
                          if ev.name in SPANS or ev.name == WINDOW_SPAN]
    return {"ops": ops, "modules": modules, "spans": spans}


def window(tr: dict) -> tuple[float, float]:
    """(start, end) ns of the traced window: the harness's window span."""
    (w,) = [s for s in tr["spans"] if s[0] == WINDOW_SPAN]
    return float(w[1]), float(w[1] + w[2])


def _arrays(events, lo: float, hi: float):
    """(names, starts, ends) of ``events`` clipped to [lo, hi], as arrays."""
    if not events:
        return np.array([], object), np.zeros(0), np.zeros(0)
    names = np.array([e[0] for e in events], object)
    t = np.array([[e[1], e[1] + e[2]] for e in events], np.float64)
    a, b = np.maximum(t[:, 0], lo), np.minimum(t[:, 1], hi)
    keep = b > a
    return names[keep], a[keep], b[keep]


def busy_intervals(tr: dict) -> np.ndarray:
    """Union of device-op intervals inside the window: (n, 2) sorted."""
    lo, hi = window(tr)
    _, a, b = _arrays(tr["ops"], lo, hi)
    if not a.size:
        return np.zeros((0, 2))
    order = np.argsort(a, kind="stable")
    a, b = a[order], np.maximum.accumulate(b[order])
    # a new interval starts where an op begins after all before it ended
    new = np.ones(a.size, bool)
    new[1:] = a[1:] > b[:-1]
    starts = a[new]
    ends = np.append(b[np.flatnonzero(new)[1:] - 1], b[-1])
    return np.stack([starts, ends], axis=1)


def busy_s(tr: dict) -> float:
    iv = busy_intervals(tr)
    return float(np.sum(iv[:, 1] - iv[:, 0])) / 1e9


def window_s(tr: dict) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def op_seconds(tr: dict, match=None, source: str = "ops") -> float:
    """Device seconds of the ops (or programs, ``source="modules"``) whose
    name satisfies ``match`` (all when None), inside the window."""
    lo, hi = window(tr)
    names, a, b = _arrays(tr[source], lo, hi)
    if match is not None:
        keep = np.array([bool(match(n)) for n in names], bool)
        a, b = a[keep], b[keep]
    return float(np.sum(b - a)) / 1e9


def idle_gaps(tr: dict) -> np.ndarray:
    """Device idle intervals inside the window: (n, 2)."""
    lo, hi = window(tr)
    iv = busy_intervals(tr)
    edges = np.concatenate([[lo], iv.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _open_spans(tr: dict, t: np.ndarray) -> np.ndarray:
    """The innermost harness span open at each time ``t`` ("none" where
    none is).  Spans of one name never overlap each other."""
    out = np.full(t.shape, "none", object)
    best = np.full(t.shape, np.inf)
    for name in SPANS:
        sp = np.array(sorted((s[1], s[1] + s[2]) for s in tr["spans"]
                             if s[0] == name), np.float64).reshape(-1, 2)
        if not sp.size:
            continue
        i = np.searchsorted(sp[:, 0], t, side="right") - 1
        ok = i >= 0
        j = np.maximum(i, 0)
        inside = ok & (t < sp[j, 1])
        dur = sp[j, 1] - sp[j, 0]
        win = inside & (dur < best)
        out[win], best[win] = name, dur[win]
    return out


def leaf_ops(tr: dict) -> list:
    """The op events that hold no other op: the trace nests the ops of a
    loop body inside the loop's own event."""
    if not tr["ops"]:
        return []
    names = np.array([e[0] for e in tr["ops"]], object)
    t = np.array([[e[1], e[2]] for e in tr["ops"]], np.float64)
    order = np.lexsort((-t[:, 1], t[:, 0]))      # by start, longer first
    names, start, dur = names[order], t[order, 0], t[order, 1]
    end = start + dur
    holds = np.zeros(start.size, bool)
    holds[:-1] = (start[1:] < end[:-1]) & (end[1:] <= end[:-1])
    keep = ~holds
    return [[n, a, d] for n, a, d in zip(names[keep], start[keep], dur[keep])]


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device ops that took most time (leaf ops, by name, summed), and
    the device's idle time by the harness span open at the time (summed
    over gaps), each as at most ``top`` [name, seconds] pairs."""
    lo, hi = window(tr)
    per_op: dict[str, float] = {}
    names, a, b = _arrays(leaf_ops(tr), lo, hi)
    for name, d in zip(names, b - a):
        per_op[name] = per_op.get(name, 0.0) + float(d) / 1e9
    gaps = idle_gaps(tr)
    per_span: dict[str, float] = {}
    for name, d in zip(_open_spans(tr, gaps.mean(axis=1)),
                       gaps[:, 1] - gaps[:, 0]):
        per_span[name] = per_span.get(name, 0.0) + float(d) / 1e9

    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": head(per_op), "idle_gaps": head(per_span)}
