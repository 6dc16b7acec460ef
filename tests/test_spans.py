"""The program span recorder (repro.spans): nesting, self time, the bounded
ring, host pulls, the timers that read span stamps, and the span's place on
the profiler trace's clock."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.tiering as tm
from repro import spans
from repro.configs.registry import get_smoke_config
from repro.models import transformer as tr
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sched import SchedConfig, Scheduler, Tenant
from repro.tiering.memory import DaemonParams, TieredMemory
from repro.tiering.stats import TierStats


def _recorded(fn):
    """Run ``fn`` and return what it returned and the spans it recorded."""
    t0 = time.perf_counter()
    out = fn()
    return out, spans.spans(t0, time.perf_counter())


def test_nesting_gives_each_span_its_parent():
    def work():
        with spans.span("t/top") as root:
            with spans.span("t/a") as a:
                with spans.span("t/a1"):
                    pass
            with spans.span("t/b", rid=3):
                pass
        return root, a
    (root, a), rec = _recorded(work)
    by = {s.name: s for s in rec}
    assert [s.name for s in rec] == ["t/top", "t/a", "t/a1", "t/b"]
    assert by["t/top"].parent == -1
    assert by["t/a"].parent == by["t/b"].parent == root.index
    assert by["t/a1"].parent == a.index
    assert by["t/b"].attrs == {"rid": 3}
    assert all(s.start <= s.end for s in rec)
    assert by["t/top"].start <= by["t/a"].start and \
        by["t/b"].end <= by["t/top"].end
    # a span that closed reports its whole duration
    assert root.elapsed == pytest.approx(by["t/top"].seconds)


def test_self_time_subtracts_only_the_children():
    def work():
        with spans.span("t/top"):
            time.sleep(0.002)
            with spans.span("t/child"):
                time.sleep(0.002)
                with spans.span("t/grandchild"):
                    time.sleep(0.002)
            with spans.span("t/child"):
                time.sleep(0.002)
    _, rec = _recorded(work)
    root = next(s for s in rec if s.name == "t/top")
    kids = [s for s in rec if s.name == "t/child"]
    grand = next(s for s in rec if s.name == "t/grandchild")
    want = root.seconds - sum(k.seconds for k in kids)
    assert spans.self_time(root, rec) == pytest.approx(want, abs=1e-12)
    assert spans.self_time(kids[0], rec) == pytest.approx(
        kids[0].seconds - grand.seconds, abs=1e-12)
    assert spans.self_time(grand, rec) == pytest.approx(grand.seconds)


def test_ring_keeps_the_newest_spans_up_to_its_capacity():
    t0 = time.perf_counter()
    n = spans.CAPACITY + 10
    for i in range(n):
        with spans.span("t/ring", i=i):
            pass
    kept = [s for s in spans.spans(t0, time.perf_counter())
            if s.name == "t/ring"]
    assert len(kept) == spans.CAPACITY
    assert [s.attrs["i"] for s in kept] == list(range(10, n))


def test_pull_records_one_host_pull_per_call():
    x = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    (a, b), rec = _recorded(lambda: (spans.pull(x, "alpha"),
                                     spans.pull(x[0], "beta")))
    np.testing.assert_array_equal(a, np.arange(6).reshape(2, 3))
    assert isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
    pulls = [s for s in rec if s.name == spans.PULL]
    assert [s.attrs["site"] for s in pulls] == ["alpha", "beta"]


# -- the timers that take their time from span stamps -------------------------

def _lane_sched():
    cfg = get_smoke_config("llama3.2-3b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_seq=48, paged=True, page_t=4, hot_slots=5, migration_interval=4,
        resources=("embeddings",), embed_hot_slots=4, embed_rows_per_page=8,
        lanes=2, kv_segments=2))
    sched = Scheduler(eng, [Tenant("a")], SchedConfig())
    rng = np.random.default_rng(0)
    for _ in range(2):
        sched.submit("a", rng.integers(0, cfg.vocab, 6).astype(np.int32), 5)
    return sched


def _decode_s():
    sched = _lane_sched()
    _, rec = _recorded(sched.run)
    return sched.eng._decode_s, [s for s in rec if s.name == "engine/advance"]


def _stall_s():
    spec = tm.ResourceSpec(name="t", n_pages=32, hot_slots=8, quota_pages=4,
                           row_shape=(4,), row_dtype="float32")
    mem = TieredMemory.from_spec(spec, daemon_params=DaemonParams(
        migration_interval=1, async_plane=False))
    mem.bind_data(np.arange(128, dtype=np.float32).reshape(32, 4))
    st, stats = mem.init(), TierStats("t")

    def work():
        nonlocal st
        for i in range(6):
            mem.enqueue([i, (i * 5) % 32])
            st, _ = mem.tick(st, stats)
    _, rec = _recorded(work)
    return stats.stall_s, [s for s in rec if s.name == "tier/stall"]


def _sched_clock():
    sched = _lane_sched()
    _, rec = _recorded(sched.run)
    return sched.clock["decode"], [s for s in rec if s.name == "sched/turn"]


@pytest.mark.parametrize("timer", [_decode_s, _stall_s, _sched_clock],
                         ids=["decode_s", "stall_s", "sched_clock"])
def test_timer_is_the_sum_of_its_spans(timer):
    total, sp = timer()
    assert sp and total > 0
    assert total == pytest.approx(sum(s.seconds for s in sp), rel=1e-9)


# -- the span on the profiler trace's clock -----------------------------------

def test_span_and_its_annotation_agree_on_the_trace_clock(tmp_path):
    """A recorder span and the annotation it opens, read back from the
    trace, agree within 1 ms once program time is put on the trace's clock
    through a window anchor: t0 and t1 read just inside the window's own
    annotation, as the benchmark harness reads them."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.window"):
            t0 = time.perf_counter()
            time.sleep(0.01)
            with spans.span("t/clock") as sp:
                time.sleep(0.02)
            time.sleep(0.01)
            t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("test.window", "t/clock"):
                    events[ev.name] = (ev.start_ns, ev.duration_ns)
    lo, dur = events["test.window"]
    assert abs(dur / 1e9 - (t1 - t0)) < 1e-3
    start, length = events["t/clock"]
    mapped = lo + (sp.start - t0 * 1e9)
    assert abs(mapped - start) < 1e6
    assert abs(sp.elapsed * 1e9 - length) < 1e6
