"""The reduction from a profiler trace to device numbers, on a small
recorded trace (12 ms of a qwen1.5-4b.chat window on a TPU v5e across a
step boundary, checked in as the harness's extract) against counts made
here by brute force on a 1 ns grid."""
import json
from pathlib import Path

import numpy as np
import pytest

import bench_cells  # noqa: F401
from bench import trace

DATA = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def tr():
    return json.loads(DATA.read_text())


def _grid(tr):
    lo, hi = trace.window(tr)
    lo, hi = int(lo), int(hi)
    busy = np.zeros(hi - lo, bool)
    for _, start, dur in tr["ops"]:
        a, b = max(int(start), lo), min(int(start + dur), hi)
        if b > a:
            busy[a - lo:b - lo] = True
    return lo, hi, busy


def test_busy_is_the_union_of_op_intervals(tr):
    lo, hi, busy = _grid(tr)
    assert trace.window_s(tr) == pytest.approx((hi - lo) / 1e9)
    assert trace.busy_s(tr) == pytest.approx(busy.sum() / 1e9, abs=2e-9)
    idle = sum(b - a for a, b in trace.idle_gaps(tr)) / 1e9
    assert idle == pytest.approx((~busy).sum() / 1e9, abs=2e-9)
    # overlapping ops are counted once: the plain sum is larger
    total = trace.op_seconds(tr)
    assert total >= trace.busy_s(tr)


def test_kernel_time_sums_its_ops(tr):
    lo, hi = trace.window(tr)
    want = sum(min(s + d, hi) - max(s, lo) for n, s, d in tr["ops"]
               if "paged_attn" in n and s + d > lo and s < hi) / 1e9
    assert want > 0
    assert trace.op_seconds(tr, lambda n: "paged_attn" in n) == \
        pytest.approx(want)


def test_breakdown_names_ops_and_idle_time(tr):
    bd = trace.breakdown(tr)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # every idle second is named by the span open at the time, or "none"
    idle = sum(b - a for a, b in trace.idle_gaps(tr)) / 1e9
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(idle)
    names = {n for n, _ in bd["idle_gaps"]}
    assert names <= set(trace.SPANS) | {"none"}


def test_an_op_outside_the_window_does_not_count(tr):
    lo, hi = trace.window(tr)
    far = dict(tr, ops=tr["ops"] + [["paged_attn_far", hi + 10, 1000]])
    assert trace.busy_s(far) == trace.busy_s(tr)
    assert trace.op_seconds(far, lambda n: n == "paged_attn_far") == 0


def test_hand_counted_trace():
    """A loop op holding two ops, one op after it, and spans: by hand,
    busy is 0-100 and 120-125 of a 200 ns window; the loop is a container,
    so the breakdown names its ops; idle 100-120 falls in sched.step (open
    0-150), idle 125-200 in none."""
    tr = {"ops": [["loop", 0, 100], ["a", 10, 20], ["b", 40, 10],
                  ["c", 120, 5]],
          "modules": [["jit_step(1)", 0, 100]],
          "spans": [[trace.WINDOW_SPAN, 0, 200], ["sched.step", 0, 150]]}
    assert trace.busy_s(tr) == pytest.approx(105e-9)
    assert trace.window_s(tr) == pytest.approx(200e-9)
    assert trace.idle_gaps(tr).tolist() == [[100, 120], [125, 200]]
    bd = trace.breakdown(tr)
    assert bd["device_ops"] == [["a", 20e-9], ["b", 10e-9], ["c", 5e-9]]
    assert bd["idle_gaps"] == [["none", pytest.approx(75e-9)],
                               ["sched.step", pytest.approx(20e-9)]]
    assert trace.op_seconds(tr, source="modules") == pytest.approx(100e-9)
