"""The KV ring's geometry is known on the host: ``ServeEngine._ring_view``
derives (page_len, cur_slot, pos) from host-held positions and must equal
what the device holds after every call that moves a position; the KV
observation it feeds is the one the device read-back built, on a tier
state that stays committed to the device; and a tick-free scheduler step
reads the device twice (logits, meter hit mask)."""
import collections
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.base import ArchConfig
from repro.configs.registry import get_smoke_config
from repro.models import transformer as tr
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.sched import SchedConfig, Scheduler, Tenant

sys.path.insert(0, str(Path(__file__).resolve().parent / "bench"))
import bench_cells  # noqa: E402
from bench import model, serve  # noqa: E402
from bench.spec import reader  # noqa: E402

PAGE_T, SLOTS = 4, 3                       # a 12-token ring
KW = dict(max_seq=48, paged=True, page_t=PAGE_T, hot_slots=SLOTS,
          migration_interval=4, resources=("embeddings",),
          embed_hot_slots=4, embed_rows_per_page=8)


def _params(arch):
    cfg = get_smoke_config(arch)
    return cfg, tr.init_params(cfg, jax.random.PRNGKey(0))


def _engine(arch="llama3.2-3b", **kw):
    cfg, params = _params(arch)
    return ServeEngine(cfg, params, ServeConfig(**{**KW, **kw}))


def _tokens(eng, n, seed):
    return (np.random.default_rng(seed).integers(0, eng.cfg.vocab, n)
            .astype(np.int32))


def _assert_view_is_device(eng):
    """Every paged entry, every layer group and the dense prologue hold
    the host view's geometry (group 0 of the first entry is the
    representative the KV tier carries)."""
    plen, cur, pos = eng._ring_view()
    np.testing.assert_array_equal(
        pos, np.broadcast_to(np.asarray(eng.cache["pos"]), cur.shape))
    entries = [c for c in eng.cache["blocks"]
               if isinstance(c, dict) and "page_len" in c]
    assert entries
    for c in entries:
        for g in range(c["page_len"].shape[0]):
            np.testing.assert_array_equal(plen, np.asarray(c["page_len"][g]))
            np.testing.assert_array_equal(cur, np.asarray(c["cur_slot"][g]))
    for c in eng.cache.get("prologue", []):
        np.testing.assert_array_equal(plen, np.asarray(c["page_len"]))
        np.testing.assert_array_equal(cur, np.asarray(c["cur_slot"]))
    assert plen.dtype == cur.dtype == pos.dtype == np.int32
    assert plen.shape == (cur.shape[0], eng.scfg.hot_slots)


def _stream(eng, steps, seed, always=(0,)):
    """``steps`` advance_lanes calls under random active masks (the lanes
    in ``always`` stay active), the view checked after each."""
    rng = np.random.default_rng(seed)
    lanes = eng.scfg.lanes
    segments = np.arange(lanes, dtype=np.int32)
    for _ in range(steps):
        active = rng.random(lanes) < 0.6
        active[list(always)] = True
        eng.advance_lanes(rng.integers(0, eng.cfg.vocab, lanes), active,
                          segments)
        _assert_view_is_device(eng)


def _case_stream_wraps():
    eng = _engine(lanes=3, kv_segments=3)
    eng.start_lanes()
    _assert_view_is_device(eng)
    _stream(eng, 30, seed=1)                 # lane 0: 30 tokens, 2.5 rings
    assert eng._pos[0] == 30 and eng._pos.min() < 30


def _case_reset_mid_stream():
    eng = _engine(lanes=3, kv_segments=3)
    _stream(eng, 14, seed=2, always=(0, 1))
    eng.reset_lane(1)
    _assert_view_is_device(eng)
    _stream(eng, 9, seed=3, always=(1,))


def _case_preempt_resume():
    eng = _engine(lanes=2, kv_segments=3)
    _stream(eng, 15, seed=4, always=(0, 1))
    residual = eng.preempt_lane(0)
    assert residual["pos"] == 15
    eng.reset_lane(0)                        # the lane serves someone else
    _stream(eng, 6, seed=5, always=(0,))
    eng.preempt_lane(0)
    eng.resume_lane(0, residual)
    _assert_view_is_device(eng)
    _stream(eng, 5, seed=6, always=(0,))


def _case_prefill_ragged_chunk():
    eng = _engine(lanes=2, kv_segments=2)
    _stream(eng, 3, seed=7, always=(1,))
    eng.reset_lane(0)
    eng.prefill_lane(0, _tokens(eng, 11, 8), segment=0, chunk=4)  # 4+4+3
    assert eng._pos[0] == 11
    _assert_view_is_device(eng)
    _stream(eng, 6, seed=9, always=(0, 1))


def _case_install_reuse_pages():
    eng = _engine(lanes=1, kv_segments=2, reuse_pages=16)
    sched = Scheduler(eng, [Tenant("t")],
                      SchedConfig(reuse_match="substring"))
    shared = _tokens(eng, 16, 10)
    sched.submit("t", shared, max_new=4)     # publishes the shared pages
    sched.run(max_steps=200)
    installs = []
    install = eng.install_lane_pages

    def spy(lane, run):
        installs.append(dict(run))
        out = install(lane, run)
        _assert_view_is_device(eng)
        return out
    eng.install_lane_pages = spy
    sched.submit("t", np.concatenate([shared, _tokens(eng, 5, 11)]),
                 max_new=6)
    while sched.active:
        sched.step()
        _assert_view_is_device(eng)
    assert installs and max(map(len, installs)) >= 3


def _case_install_handoff():
    cfg, params = _params("llama3.2-3b")
    eng = ServeEngine(cfg, params, ServeConfig(**KW, lanes=2, kv_segments=5))
    sched = Scheduler(eng, [Tenant("a")], SchedConfig(
        prefill_chunk=4, prefill_lanes=1, seed=7))
    handoffs = []
    install = eng.install_handoff

    def spy(lane, residual):
        handoffs.append(residual["pos"])
        out = install(lane, residual)
        _assert_view_is_device(eng)
        return out
    eng.install_handoff = spy
    for seed, n in ((12, 18), (13, 9), (14, 6)):
        sched.submit("a", _tokens(eng, n, seed), max_new=5)
    while sched.active:
        sched.step()
        for e in (eng, sched.peng):
            _assert_view_is_device(e)
    assert sorted(handoffs) == [6, 9, 18]


def _case_generate_lockstep():
    eng = _engine()                           # single-request mode
    prompt = np.stack([_tokens(eng, 19, 15), _tokens(eng, 19, 16)])
    nxt = eng.prefill(prompt)                 # chunks of 8: 8+8+3
    _assert_view_is_device(eng)
    for _ in range(14):
        nxt = eng.step(nxt)
        _assert_view_is_device(eng)
    assert int(eng._pos[0]) == 33


def _case_mla_prologue():
    # MLA latent KV under a dense-prologue ring and MoE blocks
    eng = _engine("deepseek-v3-671b", lanes=2, kv_segments=2)
    _stream(eng, 16, seed=17)
    eng.reset_lane(1)
    _stream(eng, 4, seed=18, always=(1,))


CASES = {
    "stream_wraps": _case_stream_wraps,
    "reset_mid_stream": _case_reset_mid_stream,
    "preempt_resume": _case_preempt_resume,
    "prefill_ragged_chunk": _case_prefill_ragged_chunk,
    "install_reuse_pages": _case_install_reuse_pages,
    "install_handoff": _case_install_handoff,
    "generate_lockstep": _case_generate_lockstep,
    "mla_prologue": _case_mla_prologue,
}


@pytest.mark.parametrize("case", list(CASES))
def test_host_ring_view_equals_the_device(case):
    CASES[case]()


# -- the KV observation, against the read-back build -------------------------

def _read_back_kv(eng):
    """The reference KV observation, built by reading the ring back:
    page_len, cur_slot and pos pulled after the step, the kernel mass
    pulled and masked on the host."""
    entry = eng._paged_entry()
    plen = np.asarray(entry["page_len"])[0]
    cur = np.asarray(entry["cur_slot"])[0]
    pos = np.asarray(eng.cache["pos"])
    local = eng._ring_page_ids(plen, cur, pos, eng.scfg.page_t)
    gids = eng._map_gids(local, eng._lane_active)
    km = np.asarray(eng._last_kv_mass, np.float32)
    return np.where(gids >= 0, km, 0.0).reshape(-1), gids.reshape(-1)


def _kv_spy(eng, replace: bool):
    """Record each ("kv", mass, gids) the engine feeds the daemon with the
    read-back build beside it; ``replace`` feeds the read-back build."""
    fed = []
    observe = eng.daemon.observe

    def spy(name, *obs, **kw):
        if name == "kv":
            old = _read_back_kv(eng)
            fed.append(((np.asarray(obs[0]), np.asarray(obs[1])), old))
            if replace:
                obs = (jnp.asarray(old[0]), jnp.asarray(old[1], jnp.int32))
        return observe(name, *obs, **kw)
    eng.daemon.observe = spy
    return fed


def test_kv_observation_equals_the_read_back_build():
    cfg, params = _params("llama3.2-3b")
    engines = [ServeEngine(cfg, params, ServeConfig(
        **KW, lanes=3, kv_segments=3)) for _ in range(2)]
    fed = [_kv_spy(e, replace=(i == 1)) for i, e in enumerate(engines)]
    rng = np.random.default_rng(19)
    segments = np.arange(3, dtype=np.int32)
    for _ in range(22):                       # ticks every 4 steps
        active = rng.random(3) < 0.7
        active[0] = True
        toks = rng.integers(0, cfg.vocab, 3)
        outs = [e.advance_lanes(toks, active, segments) for e in engines]
        np.testing.assert_array_equal(outs[0], outs[1])
    assert len(fed[0]) == len(fed[1]) == 22
    for (mass, gids), (old_mass, old_gids) in fed[0]:
        assert mass.dtype == old_mass.dtype == np.float32
        np.testing.assert_array_equal(mass, old_mass)
        np.testing.assert_array_equal(gids, old_gids)
        assert (gids >= 0).any()
    for e in engines:
        e.daemon.tick()
    states = [jax.tree.leaves(e.daemon.state_dict()) for e in engines]
    assert len(states[0]) == len(states[1])
    for a, b in zip(*states):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tier_state_stays_committed_across_the_cadences():
    """The KV observation comes from the step's device outputs, so the
    tier state is committed there from registration on, and every cadence
    (migration, threshold update, sketch clear) leaves it so: the tier
    programs see one placement and compile once."""
    eng = _engine(lanes=2, kv_segments=2, async_migration=True)

    def committed():
        return all(x.committed for h in eng.daemon.resources.values()
                   for x in jax.tree.leaves(h.state))
    assert committed()
    _stream(eng, 9, seed=20)                  # ticks at steps 4 and 8
    assert committed()
    for _ in range(eng.daemon.dp.clear_interval):
        eng.daemon.tick()
        assert committed()


# -- the pull count of a tick-free step, by hand ------------------------------

# A scheduler step with no daemon tick, every lane decoding or streaming its
# prompt: advance_lanes reads the logits; the tenant meter reads its
# lookup's hit mask.  The ring view and the KV mass never leave the device.
PULLS_PER_STEP = {"logits": 1, "meter_hit": 1}


def test_tick_free_step_pulls_only_the_logits_and_the_meter_hits():
    conf = dict(bench_cells.TINY_CONFIG, name="tiny")
    geo = dict(conf["serve"], migration_interval=10**6)
    f = model.arch_fields(conf)
    params = model.make_weights(f, model.weight_key(0))
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
    assert sum(s.name == "tier/observe" for s in rec) == 2 * steps
    sites = collections.Counter(s.attrs["site"] for s in rec
                                if s.name == spans.PULL)
    assert dict(sites) == {k: v * steps for k, v in PULLS_PER_STEP.items()}
    metric = reader(SimpleNamespace(root=bench_cells.REPO),
                    "host_pulls_per_step")
    assert metric.read(ctx) == sum(PULLS_PER_STEP.values())
