"""Migration data plane (DESIGN.md §8): promotions move real bytes.

Covers the ISSUE-3 acceptance surface: bit-exact fast-tier serving after
promotion, demotion write-back round-trips, byte metering that respects the
per-epoch quota, the pinned-host slow store, the legacy shim
forwarding + deprecation warnings, and the BENCH_serve.json schema checker.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

import repro.tiering as tm
from repro.dist import host_offload as ho
from repro.tiering import migrate as migrate_lib


def _spec(**kw):
    base = dict(name="embeddings", n_pages=64, hot_slots=8, quota_pages=4,
                sketch_width=1 << 8, row_shape=(3,), row_dtype="float32")
    base.update(kw)
    return tm.ResourceSpec(**base)


def _rows(n_pages, row_shape=(3,)):
    n = int(np.prod((n_pages,) + row_shape))
    return jnp.arange(n, dtype=jnp.float32).reshape((n_pages,) + row_shape)


# ---------------------------------------------------------------------------
# TieredMemory verbs
# ---------------------------------------------------------------------------

def test_promoted_rows_served_bit_exact_from_fast_tier():
    """After a promotion epoch, read_rows returns the fast-tier copy and it
    equals the slow-tier source bit-for-bit; unpromoted pages fall back."""
    spec = _spec()
    mem = tm.TieredMemory.from_spec(spec)
    data = _rows(spec.n_pages)
    mem.bind_data(data)
    state, stats = mem.init(), tm.TierStats(name="embeddings")
    mem.enqueue([5, 17, 40])
    state, event = mem.migrate(state, stats)
    assert mem.apply_migration(event, stats) > 0
    ids = np.array([5, 17, 40, 2])
    slots, hit = tm.lookup(state, jnp.asarray(ids))
    assert list(np.asarray(hit)) == [True, True, True, False]
    got = np.asarray(mem.read_rows(state, ids))
    np.testing.assert_array_equal(got, np.asarray(data[ids]))
    # the hit rows really came from the fast buffer, not the slow store
    fast = np.asarray(mem.buffers.fast)
    np.testing.assert_array_equal(fast[np.asarray(slots[:3])],
                                  np.asarray(data[ids[:3]]))


def test_demotion_round_trip_writes_back_dirty_rows():
    """A fast-tier row mutated in place survives eviction: the write-back
    lands in the slow store and is served from there afterwards."""
    spec = _spec(n_pages=16, hot_slots=2, quota_pages=2)
    mem = tm.TieredMemory.from_spec(spec)
    mem.bind_data(_rows(16))
    state, stats = mem.init(), tm.TierStats()
    mem.enqueue([3, 7])
    state, event = mem.migrate(state, stats)
    mem.apply_migration(event, stats)
    # dirty page 3's fast copy (the owner mutating its payload)
    slot3 = int(np.asarray(state.tier.page_slot)[3])
    dirty = jnp.full(spec.row_shape, -99.0, jnp.float32)
    mem.buffers = mem.buffers._replace(
        fast=mem.buffers.fast.at[slot3].set(dirty))
    # promote two new pages -> both slots evicted, page 3 written back
    mem.enqueue([9, 12])
    state, event = mem.migrate(state, stats)
    mem.apply_migration(event, stats)
    assert int(np.asarray(state.tier.page_slot)[3]) == -1   # demoted
    got = np.asarray(mem.read_rows(state, np.array([3])))[0]
    np.testing.assert_array_equal(got, np.asarray(dirty))


def test_epoch_bytes_never_exceed_quota_under_pressure():
    """Heavy sustained demand: every epoch's moved bytes stay within the
    2 * quota_pages * row_bytes budget, and lifetime totals accumulate."""
    spec = _spec(n_pages=256, hot_slots=16, quota_pages=4)
    mem = tm.TieredMemory.from_spec(spec)
    mem.bind_data(_rows(256))
    state, stats = mem.init(), tm.TierStats()
    rng = np.random.default_rng(0)
    total = 0
    for _ in range(20):
        mem.enqueue(rng.integers(0, 256, size=64))
        state, event = mem.migrate(state, stats)
        moved = mem.apply_migration(event, stats)
        assert moved <= spec.quota_bytes
        assert stats.last_epoch_bytes == moved
        total += moved
    assert stats.migration_bytes == total > 0
    assert stats.quota_bytes == spec.quota_bytes
    assert stats.migration_epochs > 0
    # an epoch with nothing to move reports 0, not the previous epoch's bytes
    mem._pending = mem._pending[:0]      # drain the queue -> empty epoch
    state, event = mem.migrate(state, stats)
    assert event is None and stats.last_epoch_bytes == 0


def test_slow_store_lives_in_pinned_host():
    """The slow store (and the int8 codec's scales) is a real pinned-host
    array, and stays there through an epoch copy with a demotion
    write-back and through a write verb — the verbs gather and scatter in
    host memory instead of pulling the store onto the device."""
    assert ho.supports_memory_kinds()
    buffers = migrate_lib.init_buffers(_rows(8, (2,)), num_slots=2,
                                       codec="int8")
    assert buffers.fast.shape == (2, 2) and buffers.slow.shape == (8, 2)
    for store in (buffers.slow, buffers.scale):
        assert store.sharding.memory_kind == ho.SLOW_KIND
    out, n_up, n_down = migrate_lib.migrate(
        buffers, jnp.array([4, -1]), jnp.array([0, -1]), jnp.array([-1, -1]),
        codec="int8")
    assert (n_up, n_down) == (1, 0)
    np.testing.assert_array_equal(
        np.asarray(out.fast[0]),
        np.asarray(migrate_lib.gather_rows(buffers, np.array([4])))[0])
    out, n_up, n_down = migrate_lib.migrate(
        out, jnp.array([5, -1]), jnp.array([0, -1]), jnp.array([4, -1]),
        codec="int8")
    assert (n_up, n_down) == (1, 1)
    out = migrate_lib.write_rows(out, jnp.array([1, -1]), jnp.array([-1, -1]),
                                 jnp.full((2, 2), 7.0), codec="int8")
    for store in (out.slow, out.scale):
        assert store.sharding.memory_kind == ho.SLOW_KIND
    np.testing.assert_allclose(
        np.asarray(migrate_lib.gather_rows(out, np.array([1])))[0],
        np.full(2, 7.0), rtol=1e-2)


@pytest.mark.parametrize("row_shape", [(), (3,), (2, 3)],
                         ids=["scale", "flat", "tile"])
def test_host_verbs_match_device_indexing(row_shape):
    """host_take / host_put over a pinned-host store equal plain indexing
    of a device copy, dropped lanes (-1, out of range) and repeated ids
    included.  Rows of two or more dims take the per-row DMA form the
    chip runs, narrower ones the host scatter; a batch with no lane in
    range leaves the store as it was."""
    import jax
    data = _rows(8, row_shape)
    store = migrate_lib.place_slow(data)
    idx = jnp.array([5, -1, 2, 8, 5, 0, -1], jnp.int32)
    rows = -1.0 - _rows(7, row_shape)
    got = jax.jit(ho.host_take)(store, jnp.array([[7, 0], [3, 3]], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(data)[[[7, 0], [3, 3]]])
    out = ho.rehost(jax.jit(ho.host_put)(store, idx, rows), store.sharding)
    assert out.sharding.memory_kind == ho.SLOW_KIND
    want = jnp.asarray(data).at[jnp.where(idx < 0, 8, idx)].set(
        rows, mode="drop")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    untouched = jax.jit(ho.host_put)(store, jnp.array([-1, 8], jnp.int32),
                                     rows[:2])
    np.testing.assert_array_equal(np.asarray(untouched), np.asarray(data))


def test_bind_data_validates_geometry_against_spec():
    mem = tm.TieredMemory.from_spec(_spec(n_pages=64, row_shape=(3,)))
    with pytest.raises(ValueError):        # wrong page count
        mem.bind_data(jnp.zeros((32, 3), jnp.float32))
    with pytest.raises(ValueError):        # wrong row shape
        mem.bind_data(jnp.zeros((64, 5), jnp.float32))
    with pytest.raises(ValueError):        # wrong dtype
        mem.bind_data(jnp.zeros((64, 3), jnp.bfloat16))
    with pytest.raises(ValueError):        # no payload bound
        mem.read_rows(mem.init(), np.array([0]))


def test_spec_byte_accounting():
    spec = _spec(quota_pages=8, row_shape=(4, 2), row_dtype="bfloat16")
    assert spec.row_bytes == 4 * 2 * 2
    assert spec.quota_bytes == 2 * 8 * spec.row_bytes
    assert tm.ResourceSpec("x", n_pages=4, hot_slots=2).row_bytes == 0


# ---------------------------------------------------------------------------
# multiplexed daemon + write_slow
# ---------------------------------------------------------------------------

def test_daemon_meters_bytes_per_resource():
    daemon = tm.NeoMemDaemon(tm.DaemonParams(
        migration_interval=1, threshold_update_period=64, clear_interval=64))
    a = daemon.register(tm.make_resource("embeddings", _spec()))
    b = daemon.register(tm.make_resource("embeddings", _spec(
        name="b", row_shape=(7,))))
    a.bind_data(_rows(64, (3,)))
    b.bind_data(_rows(64, (7,)))
    a.mem.enqueue([1, 2, 3])
    b.mem.enqueue([4, 5])
    daemon.tick()
    assert a.stats.migration_bytes == 3 * 3 * 4      # 3 rows of (3,) f32 up
    assert b.stats.migration_bytes == 2 * 7 * 4
    np.testing.assert_array_equal(np.asarray(b.read_rows(np.array([4]))[0]),
                                  np.asarray(_rows(64, (7,))[4]))


def test_write_rows_refreshes_both_tiers_and_meters():
    h = tm.NeoMemDaemon().register(tm.make_resource("embeddings", _spec()))
    h.bind_data(jnp.zeros((64, 3), jnp.float32))
    rows = jnp.stack([jnp.full((3,), 1.5), jnp.full((3,), 2.5)])
    h.write_rows(np.array([10, -1]), rows)           # -1 lane dropped
    got = np.asarray(h.read_rows(np.array([10, 11])))
    np.testing.assert_array_equal(got[0], np.full(3, 1.5))
    np.testing.assert_array_equal(got[1], np.zeros(3))
    assert h.stats.flush_bytes == 1 * 3 * 4          # one (3,) f32 row
    # promoted pages stay coherent: a write after promotion refreshes the
    # fast copy too, so the served (fast-tier) row is never stale
    h.mem.enqueue([10])
    h.state, event = h.mem.migrate(h.state, h.stats)
    h.mem.apply_migration(event, h.stats)
    h.write_rows(np.array([10]), jnp.full((1, 3), 9.0))
    slots, hit = h.lookup(jnp.asarray([10]))
    assert bool(np.asarray(hit)[0])                  # served from fast tier
    np.testing.assert_array_equal(
        np.asarray(h.read_rows(np.array([10])))[0], np.full(3, 9.0))
    np.testing.assert_array_equal(
        np.asarray(h.mem.buffers.fast[int(np.asarray(slots)[0])]),
        np.full(3, 9.0))


def test_write_pages_matches_write_rows():
    """The fused bulk page-write verb (the chunked-prefill flush,
    DESIGN.md §11) lands byte-identical rows to the per-page write_rows
    path it batches: same [K|V] concat, -1 ids dropped, same metering."""
    G, L, S, T, H, D = 2, 2, 3, 4, 1, 3
    kw = dict(name="kv-pages", n_pages=16, row_shape=(G, T, H, 2 * D))
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(G, L, S, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(G, L, S, T, H, D)), jnp.float32)
    ids = np.array([3, -1, 7, 0, 12, -1], np.int32)      # (L*S,) slot map

    a = tm.NeoMemDaemon().register(tm.make_resource("embeddings", _spec(**kw)))
    a.bind_data(jnp.zeros((16, G, T, H, 2 * D), jnp.float32))
    a.write_pages(ids, k, v)

    b = tm.NeoMemDaemon().register(tm.make_resource("embeddings", _spec(**kw)))
    b.bind_data(jnp.zeros((16, G, T, H, 2 * D), jnp.float32))
    rows = np.moveaxis(np.asarray(jnp.concatenate([k, v], axis=-1)), 0, 2)
    b.write_rows(ids, jnp.asarray(rows.reshape((L * S,) + rows.shape[2:])))

    np.testing.assert_array_equal(np.asarray(a.mem.buffers.slow),
                                  np.asarray(b.mem.buffers.slow))
    assert a.stats.flush_bytes == b.stats.flush_bytes > 0
    # page 7 sits at (lane 0, slot 2): it round-trips bit-exactly
    got = np.asarray(a.read_rows(np.array([7])))[0]
    np.testing.assert_array_equal(
        got, np.asarray(jnp.concatenate([k, v], axis=-1))[:, 0, 2])


# ---------------------------------------------------------------------------
# legacy shims: forwarding + deprecation
# ---------------------------------------------------------------------------

def test_legacy_adapters_warn_and_forward_data_plane():
    from repro.core.adapters.embed_cache import EmbedCache, EmbedTierConfig
    with pytest.warns(DeprecationWarning, match="repro.tiering.NeoMemDaemon"):
        cache = EmbedCache(EmbedTierConfig(vocab=256, hot_slots=4,
                                           rows_per_page=64, quota_pages=4))
    data = _rows(4, (64, 8))
    cache.bind_data(data)
    cache.handle.mem.enqueue([2])
    cache.tick()
    assert cache.migration_bytes > 0
    np.testing.assert_array_equal(np.asarray(cache.read_rows(np.array([2]))),
                                  np.asarray(data[2:3]))


def test_legacy_daemon_warns():
    from repro.core.daemon import DaemonParams, NeoMemDaemon
    from repro.core.neoprof import NeoProfParams
    from repro.core.sketch import SketchParams
    from repro.core.tiering import TierParams
    with pytest.warns(DeprecationWarning, match="deprecation shim"):
        NeoMemDaemon(NeoProfParams(sketch=SketchParams(width=1 << 8)),
                     TierParams(num_pages=16, num_slots=4, quota_pages=4),
                     DaemonParams(quota_pages=4))


def test_other_legacy_adapters_warn():
    from repro.core.adapters.expert_cache import (ExpertCache,
                                                  ExpertTierConfig)
    from repro.core.adapters.kv_tier import KVTier, KVTierConfig
    with pytest.warns(DeprecationWarning):
        ExpertCache(ExpertTierConfig(n_groups=2, n_experts=4, hot_slots=2))
    with pytest.warns(DeprecationWarning):
        KVTier(KVTierConfig(n_pages_total=16, hot_slots=4))


# ---------------------------------------------------------------------------
# BENCH_serve.json schema checker
# ---------------------------------------------------------------------------

def _bench_doc(tmp_path, mutate=None):
    import json
    row = {"name": "embeddings", "fast_reads": 10, "slow_reads": 2,
           "hit_rate": 10 / 12, "promoted": 4, "demoted": 1, "ping_pong": 0,
           "migration_bytes": 1024, "last_epoch_bytes": 256,
           "max_epoch_bytes": 256, "quota_bytes": 512,
           "migration_epochs": 4, "flush_bytes": 0, "inflight_bytes": 0,
           "stall_s": 0.2, "overlap_bytes_per_decode_s": 340.0}
    case = {"arch": "a", "batch": 2, "prompt_len": 8, "n_tokens": 4,
            "compile_s": 0.5, "tokens_per_s": 1.0, "wall_s": 8.0,
            "migration_bytes": 1024, "migration_bytes_per_s": 128.0,
            "resources": {"embeddings": row}}

    def ab_arm(source, steady):
        return {"kv_mass_source": source, "steps": 100, "tokens": 50,
                "wall_s": 4.0, "kv_hit": steady, "kv_hit_steady": steady,
                "kv_promoted": 8, "migration_bytes": 2048}
    mass_ab = {"arch": "a", "trace": "zipf-hot", "arrival": "mmpp",
               "lanes": 4, "seed": 0, "trace_steps": 100,
               "fill": ab_arm("fill", 0.4), "kernel": ab_arm("kernel", 0.45)}

    def pf_arm(chunk, ttft):
        return {"chunk": chunk, "compile_s": 2.0, "steps": 600,
                "ttft_ms": ttft,
                "tpot_ms": {"p50": 5.0, "p99": 6.0, "mean": 5.2, "n": 3},
                "tokens": [1, 2, 3, 4]}
    prefill = {"arch": "a", "prompt_len": 512, "max_new": 4, "page_t": 16,
               "chunk": 64, "lanes": 2, "seed": 0, "tokens_match": True,
               "ttft_ratio": 0.05, "token": pf_arm(0, 4000.0),
               "chunked": pf_arm(64, 200.0)}
    def reuse_arm(mode, pool, hit, steady):
        stats = None
        if mode != "off":
            stats = {"pool_pages": pool, "indexed": 30, "free": 2,
                     "shared_refs": 5, "lookups": 40, "matchable": 200,
                     "page_hits": int(200 * hit), "hit_rate": hit,
                     "tokens_saved": int(200 * hit) * 4, "published": 60,
                     "evicted": 20, "rejected": 1,
                     "shared_mass_share": 0.3}
        return {"mode": mode, "reuse_pages": pool, "steps": 240,
                "completed": 24, "tokens": 96, "compile_s": 3.0,
                "wall_s": 9.0, "kv_hit_steady": steady,
                "ttft_ms": {"p50": 30.0, "p99": 60.0, "mean": 35.0, "n": 24},
                "reuse": stats}
    kv_reuse = {"arch": "a", "trace": "agentic", "seed": 0,
                "trace_steps": 224, "turns": 24, "lanes": 4, "page_t": 4,
                "reuse_pages": 32, "prefill_chunk": 8,
                "tenants": {"agent-a": 1.0, "agent-b": 1.0},
                "tokens_match": True, "prefill_tokens_saved": 776,
                "hit_rate_gap": 0.04,
                "off": reuse_arm("off", 0, 0.0, 0.13),
                "prefix": reuse_arm("prefix", 32, 0.63, 0.13),
                "substring": reuse_arm("substring", 32, 0.67, 0.136)}
    def comp_arm(codec, wire, hit):
        return {"codec": codec, "steps": 240, "tokens": 96, "wall_s": 9.0,
                "hit_steady": {"embeddings": hit, "kv": 0.4},
                "wire_row_bytes": {"embeddings": wire, "kv": wire * 2},
                "migration_bytes": wire * 100, "max_epoch_bytes": wire * 8,
                "quota_bytes": wire * 16,
                "resources": {"embeddings": dict(row)}}
    compress = {"arch": "a", "trace": "zipf-hot", "arrival": "mmpp",
                "lanes": 4, "seed": 0, "trace_steps": 160, "quick": True,
                "arms": {"none": comp_arm("none", 1024, 0.72),
                         "fp32": comp_arm("fp32", 2048, 0.72),
                         "int8": comp_arm("int8", 516, 0.73)},
                "bytes_ratio_int8_fp32": 516 / 2048,
                "bytes_ratio_bound": 0.35, "hit_eps": 0.02,
                "tokens_match_none_fp32": True,
                "probe": {"prompt_len": 12, "n_steps": 8,
                          "tokens_match_none_fp32": True,
                          "drift_fp32": 0.0, "drift_int8": 0.19,
                          "drift_bound": 0.25},
                "zero1": {"steps": 6, "padded": 1632, "bytes_fp32": 39168,
                          "bytes_int8": 9840, "byte_ratio": 9840 / 39168,
                          "byte_ratio_bound": 0.30, "update_drift": 4e-5,
                          "drift_tolerance": 1e-3}}
    def ov_arm(mode, stall):
        return {"mode": mode, "steps": 16, "compile_s": 2.0, "wall_s": 4.0,
                "tokens_per_s": 8.0, "stall_s": stall,
                "migration_bytes": 1024,
                "resources": {"embeddings": dict(row)}}
    overlap = {"arch": "a", "batch": 2, "prompt_len": 12, "n_tokens": 16,
               "tokens_match": True, "stall_ratio_bound": 0.25,
               "sync": ov_arm("sync", 0.4), "async": ov_arm("async", 0.0)}
    doc = {"quick": True, "cases": [case], "mass_ab": mass_ab,
           "prefill": prefill, "kv_reuse": kv_reuse, "compress": compress,
           "overlap": overlap}
    if mutate:
        mutate(doc)
    p = tmp_path / "BENCH_serve.json"
    p.write_text(json.dumps(doc))
    return str(p)


def test_validate_bench_accepts_documented_schema(tmp_path):
    from benchmarks.validate_bench import validate
    assert validate(_bench_doc(tmp_path)) == []


def test_validate_bench_rejects_violations(tmp_path):
    from benchmarks.validate_bench import validate

    def no_bytes(doc):
        doc["cases"][0]["migration_bytes"] = 0
    assert any("nonzero" in e for e in validate(_bench_doc(tmp_path, no_bytes)))

    def over_quota(doc):
        doc["cases"][0]["resources"]["embeddings"]["max_epoch_bytes"] = 9999
    assert any("exceeds quota" in e
               for e in validate(_bench_doc(tmp_path, over_quota)))

    def max_epoch_lost(doc):
        doc["cases"][0]["resources"]["embeddings"]["last_epoch_bytes"] = 300
    assert any("epoch maximum" in e
               for e in validate(_bench_doc(tmp_path, max_epoch_lost)))

    def reads_lost(doc):
        doc["cases"][0]["resources"]["embeddings"]["hit_rate"] = 0.8
    assert any("read conservation" in e
               for e in validate(_bench_doc(tmp_path, reads_lost)))

    def missing_key(doc):
        del doc["cases"][0]["resources"]["embeddings"]["quota_bytes"]
    assert any("missing keys" in e
               for e in validate(_bench_doc(tmp_path, missing_key)))

    def no_mass_ab(doc):
        del doc["mass_ab"]
    assert any("mass_ab" in e for e in validate(_bench_doc(tmp_path,
                                                           no_mass_ab)))

    def fidelity_lost(doc):
        doc["mass_ab"]["kernel"]["kv_hit_steady"] = 0.30
    assert any("fidelity gate" in e
               for e in validate(_bench_doc(tmp_path, fidelity_lost)))

    def uneven_load(doc):
        doc["mass_ab"]["kernel"]["tokens"] = 49
    assert any("identical trace" in e
               for e in validate(_bench_doc(tmp_path, uneven_load)))

    def slow_chunked(doc):
        doc["prefill"]["chunked"]["ttft_ms"] = 3000.0
    assert any("1/4" in e for e in validate(_bench_doc(tmp_path,
                                                       slow_chunked)))

    def tokens_diverge(doc):
        doc["prefill"]["chunked"]["tokens"] = [9, 9, 9, 9]
    assert any("bit-exactness" in e
               for e in validate(_bench_doc(tmp_path, tokens_diverge)))

    def tpot_hidden(doc):
        doc["prefill"]["token"]["tpot_ms"]["p50"] = 0.0
    assert any("tpot_ms p50" in e
               for e in validate(_bench_doc(tmp_path, tpot_hidden)))

    def short_prompt(doc):
        doc["prefill"]["prompt_len"] = 64
    assert any("512" in e for e in validate(_bench_doc(tmp_path,
                                                       short_prompt)))

    def reuse_tokens_diverge(doc):
        doc["kv_reuse"]["tokens_match"] = False
    assert any("KV reuse changed" in e
               for e in validate(_bench_doc(tmp_path, reuse_tokens_diverge)))

    def reuse_no_savings(doc):
        doc["kv_reuse"]["prefill_tokens_saved"] = 0
    assert any("saved no prefill" in e
               for e in validate(_bench_doc(tmp_path, reuse_no_savings)))

    def hole_gap_lost(doc):
        doc["kv_reuse"]["substring"]["reuse"]["hit_rate"] = 0.63
    assert any("hole-skipping" in e
               for e in validate(_bench_doc(tmp_path, hole_gap_lost)))

    def reuse_degrades_tiering(doc):
        doc["kv_reuse"]["substring"]["kv_hit_steady"] = 0.05
    assert any("degraded tiering" in e
               for e in validate(_bench_doc(tmp_path,
                                            reuse_degrades_tiering)))

    def off_arm_has_stats(doc):
        doc["kv_reuse"]["off"]["reuse"] = \
            doc["kv_reuse"]["prefix"]["reuse"]
    assert any("store was not disabled" in e
               for e in validate(_bench_doc(tmp_path, off_arm_has_stats)))

    def reuse_stat_missing(doc):
        del doc["kv_reuse"]["substring"]["reuse"]["tokens_saved"]
    assert any("reuse stats missing" in e
               for e in validate(_bench_doc(tmp_path, reuse_stat_missing)))

    def no_compress(doc):
        del doc["compress"]
    assert any("compress section missing" in e
               for e in validate(_bench_doc(tmp_path, no_compress)))

    def byte_ratio_blown(doc):
        doc["compress"]["bytes_ratio_int8_fp32"] = 0.5
    assert any("not paying its way" in e
               for e in validate(_bench_doc(tmp_path, byte_ratio_blown)))

    def fp_arm_not_identity(doc):
        doc["compress"]["probe"]["drift_fp32"] = 0.01
    assert any("not transparent" in e
               for e in validate(_bench_doc(tmp_path, fp_arm_not_identity)))

    def int8_drift_blown(doc):
        doc["compress"]["probe"]["drift_int8"] = 0.9
    assert any("visibly moved" in e
               for e in validate(_bench_doc(tmp_path, int8_drift_blown)))

    def compress_tokens_diverge(doc):
        doc["compress"]["tokens_match_none_fp32"] = False
    assert any("full-precision slow store changed" in e
               for e in validate(_bench_doc(tmp_path,
                                            compress_tokens_diverge)))

    def compress_hit_degraded(doc):
        doc["compress"]["arms"]["int8"]["hit_steady"]["embeddings"] = 0.5
    assert any("degraded tiering behaviour" in e
               for e in validate(_bench_doc(tmp_path, compress_hit_degraded)))

    def zero1_parity_lost(doc):
        doc["compress"]["zero1"]["update_drift"] = 0.1
    assert any("lost fp32 parity" in e
               for e in validate(_bench_doc(tmp_path, zero1_parity_lost)))

    def compress_uneven_load(doc):
        doc["compress"]["arms"]["int8"]["tokens"] = 95
    assert any("every codec" in e
               for e in validate(_bench_doc(tmp_path, compress_uneven_load)))


    def overlap_tokens_diverge(doc):
        doc["overlap"]["tokens_match"] = False
    assert any("served different bytes" in e
               for e in validate(_bench_doc(tmp_path, overlap_tokens_diverge)))

    def overlap_bytes_skipped(doc):
        doc["overlap"]["async"]["resources"]["embeddings"][
            "migration_bytes"] = 512
    assert any("not skip them" in e
               for e in validate(_bench_doc(tmp_path, overlap_bytes_skipped)))

    def overlap_stall_blown(doc):
        doc["overlap"]["async"]["stall_s"] = 0.2   # > 0.25 * sync 0.4
    assert any("blocking decode" in e
               for e in validate(_bench_doc(tmp_path, overlap_stall_blown)))

    def overlap_no_baseline(doc):
        doc["overlap"]["sync"]["stall_s"] = 0.0
    assert any("baseline" in e
               for e in validate(_bench_doc(tmp_path, overlap_no_baseline)))

    def overlap_not_achieved(doc):
        doc["overlap"]["async"]["resources"]["embeddings"][
            "overlap_bytes_per_decode_s"] = 0.0
    assert any("metering is broken" in e
               for e in validate(_bench_doc(tmp_path, overlap_not_achieved)))

    def overlap_tail_uncommitted(doc):
        doc["overlap"]["async"]["resources"]["embeddings"][
            "inflight_bytes"] = 128
    assert any("finalize barrier" in e
               for e in validate(_bench_doc(tmp_path,
                                            overlap_tail_uncommitted)))

    def inflight_not_folded(doc):
        doc["cases"][0]["resources"]["embeddings"]["inflight_bytes"] = 400
    assert any("failed to fold" in e
               for e in validate(_bench_doc(tmp_path, inflight_not_folded)))


# ---------------------------------------------------------------------------
# serve engine end-to-end (CPU fallback path in CI)
# ---------------------------------------------------------------------------

def test_serve_engine_moves_real_bytes_and_serves_parity():
    import jax
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as tr
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_smoke_config("llama3.2-3b")
    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, ServeConfig(
        max_seq=64, paged=True, page_t=4, hot_slots=8, migration_interval=4,
        resources=("embeddings",), embed_hot_slots=4))
    prompt = (np.arange(2 * 12).reshape(2, 12) * 7) % cfg.vocab
    eng.generate(prompt, n_tokens=8)
    stats = eng.tier_stats()
    for name in ("kv", "embeddings"):
        assert stats[name]["migration_bytes"] > 0, name
        assert stats[name]["last_epoch_bytes"] <= stats[name]["quota_bytes"]
    # embedding lookups match the live table bit-for-bit, hit or miss
    ids = np.array([0, 1, 2, 3])
    got = np.asarray(eng.read_rows("embeddings", ids))
    want = np.asarray(eng._embed_payload(tm.EMBED_ROWS_PER_PAGE)[ids])
    np.testing.assert_array_equal(got, want)
    # promoted KV pages carry the flushed page payload (nonzero, right shape)
    kv = np.asarray(eng.read_rows("kv", np.array([0])).astype(jnp.float32))
    assert kv.shape == (1,) + eng._kv_row_shape()
    assert np.abs(kv).sum() > 0
