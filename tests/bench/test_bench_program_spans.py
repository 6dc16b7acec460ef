"""The per-layer metrics that read the program's own spans (repro.spans):
a traced tiny drive on the CPU, the pull count of a tick-free window
against a hand count of the pull sites, and the device idle table on a
hand-built extract and hand-built spans."""
import collections
import time
from types import SimpleNamespace

import numpy as np
import pytest

import bench_cells
from bench import model, program_spans, serve, trace
from bench.spec import arch_module, reader
from repro import spans
from repro.configs.base import ArchConfig
from repro.serve.engine import ServeEngine
from repro.serve.sched import SchedConfig, Scheduler, Tenant
from repro.spans import Span

HOST_METRICS = ("sched_self_ms", "host_pulls_per_step", "tier_host_ms")


def _read(metric, ctx):
    return reader(SimpleNamespace(root=bench_cells.REPO), metric).read(ctx)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = bench_cells.tiny_root(tmp_path_factory.mktemp("spans"))
    return bench_cells.drive(root, seed=2**35 + 11, seconds=1.0, trace=True)


def test_traced_tiny_drive_reports_the_host_span_metrics(traced):
    assert traced["correct"] is True
    for name in HOST_METRICS:
        assert traced["metrics"][name]["value"] > 0, name
    # the CPU trace holds no device op: the idle table has nothing to split
    assert "idle_in_tiers_share" not in traced["metrics"]


def test_program_annotations_leave_the_breakdown_names(traced):
    names = {n for n, _ in traced["breakdown"]["idle_gaps"]}
    assert names and names <= set(trace.SPANS) | {"none"}
    assert not any("/" in n or n == spans.PULL for n in names)


# -- the pull count, by hand ------------------------------------------------

# Pulls of one scheduler step with no daemon tick, every lane decoding or
# streaming its prompt: advance_lanes reads the logits (the KV ring's
# geometry is known on the host and the KV mass stays on the device); the
# scheduler's tenant meter reads the lookup's hit mask.
PULLS_PER_STEP = {"logits": 1, "meter_hit": 1}


def test_pulls_per_step_in_a_tick_free_window_match_the_hand_count():
    conf = dict(bench_cells.TINY_CONFIG, name="tiny")
    geo = dict(conf["serve"], migration_interval=10**6)
    arch = arch_module(SimpleNamespace(root=bench_cells.REPO, conf=conf))
    f = arch.fields(conf)
    params = arch.make_weights(f, model.weight_key(0))
    eng = ServeEngine(ArchConfig(**f), params, serve.serve_config(geo))
    sched = Scheduler(eng, [Tenant("t")],
                      SchedConfig(prefill_chunk=geo["prefill_chunk"]))
    rng = np.random.default_rng(0)
    for _ in range(geo["lanes"]):
        sched.submit("t", rng.integers(0, f["vocab"], 6).astype(np.int32), 40)
    for _ in range(3):                   # admission, compiles
        sched.step()
    steps = 5
    t0 = time.perf_counter()
    for _ in range(steps):
        sched.step()
    ctx = SimpleNamespace(t0=t0, t1=time.perf_counter())
    rec = spans.spans(ctx.t0, ctx.t1)
    assert not any(s.name == "tier/tick" for s in rec)
    sites = collections.Counter(s.attrs["site"] for s in rec
                                if s.name == spans.PULL)
    assert dict(sites) == {k: v * steps for k, v in PULLS_PER_STEP.items()}
    assert _read("host_pulls_per_step", ctx) == sum(PULLS_PER_STEP.values())


# -- the idle table, by hand ------------------------------------------------

T0 = 5.0                      # window start, host clock (s)
BASE = int(T0 * 1e9)          # ... in perf_counter ns
# device ops (trace ns): busy 0-100, 300-400, 700-800 of a 1000 ns window
EXTRACT = {"ops": [["a", 0, 100], ["b", 300, 100], ["c", 700, 100]],
           "modules": [],
           "spans": [[trace.WINDOW_SPAN, 0, 1000]]}


def _span(i, name, a, b, parent, **attrs):
    return Span(i, name, BASE + a, BASE + b, parent, attrs)


# program spans on the host clock, the window anchored at T0
SPANS = [_span(0, "sched/step", 50, 950, -1),
         _span(1, "engine/advance", 60, 500, 0),
         _span(2, "tier/observe", 120, 250, 1, resource="kv"),
         _span(3, spans.PULL, 150, 200, 2, site="ring_view"),
         _span(4, spans.PULL, 420, 480, 1, site="logits"),
         _span(5, "tier/tick", 600, 900, 0),
         _span(6, spans.PULL, 650, 680, 5, site="hot_pages")]
# idle 100-300: advance 100-120, observe 120-150, pull 150-200, observe
# 200-250, advance 250-300; idle 400-700: advance 400-420, pull 420-480,
# advance 480-500, step 500-600, tick 600-650, pull 650-680, tick 680-700;
# idle 800-1000: tick 800-900, step 900-950, none 950-1000
TABLE = {"engine/advance": 110, "tier/observe": 80,
         "host_pull:ring_view (tier/observe)": 50,
         "host_pull:logits (engine/advance)": 60, "sched/step": 150,
         "tier/tick": 170, "host_pull:hot_pages (tier/tick)": 30, "none": 50}
TIER_NS = 80 + 50 + 170 + 30


def _ctx(t1):
    return SimpleNamespace(t0=T0, t1=t1, trace=EXTRACT)


def test_idle_table_and_share_on_hand_built_spans(monkeypatch, capsys):
    monkeypatch.setattr(program_spans, "window_spans", lambda ctx: SPANS)
    table, tier_s = program_spans.idle_by_span(EXTRACT, SPANS, T0,
                                               T0 + 1000e-9)
    assert {k: round(v * 1e9, 6) for k, v in table} == TABLE
    assert sum(v for _, v in table) == pytest.approx(700e-9)
    assert tier_s == pytest.approx(TIER_NS * 1e-9)
    share = _read("idle_in_tiers_share", _ctx(T0 + 1000e-9))
    assert share == pytest.approx(100.0 * TIER_NS / 1000)
    assert "idle by program span" in capsys.readouterr().err


def test_a_skewed_anchor_silences_the_idle_reader(monkeypatch, capsys):
    monkeypatch.setattr(program_spans, "window_spans", lambda ctx: SPANS)
    assert _read("idle_in_tiers_share", _ctx(T0 + 1000e-9 + 2e-3)) is None
    assert "not put on the trace's clock" in capsys.readouterr().err
