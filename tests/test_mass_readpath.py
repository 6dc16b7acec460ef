"""The profiler→data-plane loop closed on device (DESIGN.md §10):

* kernel-exported per-page softmax stats match the dense reference
  (denominators AND normalized mass; full-page/dense, partial-page,
  MLA-style, soft-capped);
* the jittable ``lookup_rows`` fast path is bit-exact with the host
  ``read_rows`` verb, including the slow-fallback mask;
* the serve engine's in-jit tiered reads (embeddings, experts) and the
  kernel-mass "kv" stream leave decode output bit-identical while serving
  through the placement table.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn import ops as pa_ops
from repro.kernels.paged_attn import ref as pa_ref

# ---------------------------------------------------------------------------
# kernel page-stats export vs the dense reference
# ---------------------------------------------------------------------------


def _case(b, h, hkv, dk, dv, p, t, seed=0, full=False):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, h, dk), jnp.float32)
    kp = jax.random.normal(keys[1], (b, p, t, hkv, dk), jnp.float32)
    vp = jax.random.normal(keys[2], (b, p, t, hkv, dv), jnp.float32)
    if full:
        lens = jnp.full((b, p), t, jnp.int32)
    else:
        lens = jax.random.randint(keys[3], (b, p), 0, t + 1)
        lens = lens.at[:, 0].set(jnp.maximum(lens[:, 0], 1))
    return q, kp, vp, lens


@pytest.mark.parametrize("b,h,hkv,dk,dv,p,t,softcap,full", [
    (2, 8, 2, 64, 64, 4, 16, 0.0, True),     # dense: every page full
    (2, 8, 2, 64, 64, 4, 16, 0.0, False),    # paged: partial/empty pages
    (1, 4, 4, 32, 32, 8, 32, 30.0, False),   # soft-capped logits
    (3, 8, 1, 576 // 8, 64, 2, 8, 0.0, False),   # MLA-style dk != dv
])
def test_kernel_l_matches_ref_denominator(b, h, hkv, dk, dv, p, t, softcap,
                                          full):
    """The kernel's running (m, l) equal the dense softmax max/denominator."""
    q, kp, vp, lens = _case(b, h, hkv, dk, dv, p, t, seed=b + p, full=full)
    m, l, _, pm, pl_ = pa_ops.paged_attention_local_stats(
        q, kp, vp, lens, softcap=softcap, return_page_stats=True)
    m_ref, l_ref = pa_ref.softmax_denominator_ref(q, kp, lens,
                                                  softcap=softcap)
    np.testing.assert_allclose(np.asarray(m[..., 0]), np.asarray(m_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l[..., 0]), np.asarray(l_ref),
                               rtol=1e-5, atol=1e-6)
    # the page partials reconstruct the SAME denominator: l = Σ_p pl·e^{pm-m}
    l_re = jnp.sum(pl_ * jnp.exp(pm - jnp.swapaxes(m, 1, 2)), axis=1)
    np.testing.assert_allclose(np.asarray(l_re), np.asarray(l_ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("softcap,full", [(0.0, True), (0.0, False),
                                          (30.0, False)])
def test_kernel_page_mass_matches_ref(softcap, full):
    q, kp, vp, lens = _case(2, 8, 2, 64, 64, 5, 16, seed=7, full=full)
    out, mass = pa_ops.paged_attention(q, kp, vp, lens, softcap=softcap,
                                       return_mass=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(pa_ref.paged_attention_ref(q, kp, vp, lens,
                                              softcap=softcap)),
        rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(mass),
        np.asarray(pa_ref.page_mass_ref(q, kp, lens, softcap=softcap)),
        rtol=1e-5, atol=1e-6)
    # a softmax share: valid pages sum to 1, empty pages contribute 0
    np.testing.assert_allclose(np.asarray(mass).sum(-1), 1.0, rtol=1e-5)
    empty = np.asarray(lens) == 0
    assert (np.asarray(mass)[empty] == 0.0).all()


def test_default_raw_signature_unchanged():
    """Existing 3-tuple consumers (sharded decode, seed tests) still work."""
    q, kp, vp, lens = _case(1, 4, 2, 32, 32, 3, 8)
    out = pa_ops.paged_attention_local_stats(q, kp, vp, lens)
    assert len(out) == 3
    o = pa_ops.paged_attention(q, kp, vp, lens)
    assert o.shape == q.shape


# ---------------------------------------------------------------------------
# lookup_rows: the in-jit read fast path vs the host verb
# ---------------------------------------------------------------------------


def _tiered_memory(n_pages=32, n_slots=6, seed=0):
    from repro import tiering as tm
    spec = tm.ResourceSpec("t", n_pages=n_pages, hot_slots=n_slots,
                           quota_pages=n_slots, row_shape=(3, 4),
                           row_dtype="float32")
    mem = tm.TieredMemory.from_spec(spec)
    state = mem.init()
    rows = jax.random.normal(jax.random.PRNGKey(seed),
                             (n_pages, 3, 4), jnp.float32)
    mem.bind_data(rows)
    # promote a few pages so the fast tier actually serves hits
    mem.enqueue(np.asarray([3, 7, 11, 19], np.int64))
    stats = tm.TierStats(name="t")
    state, event = mem.migrate(state, stats)
    mem.apply_migration(event, stats)
    return mem, state, rows


def test_lookup_rows_matches_host_read_rows():
    """jitted lookup_rows == host read_rows bit-for-bit, across hits,
    misses, and the all-hit / all-miss partitions the host verb special-
    cases."""
    from repro.tiering import migrate as migrate_lib
    mem, state, _ = _tiered_memory()
    jitted = jax.jit(lambda fast, slow, table, ids:
                     migrate_lib.lookup_rows(fast, slow, table, ids))
    for ids in ([3, 7, 11, 19],          # all fast-tier hits
                [0, 1, 2, 30],           # all slow fallback
                [3, 0, 11, 30, 7, 5]):   # mixed
        ids = jnp.asarray(ids, jnp.int32)
        got = jitted(mem.buffers.fast, mem.buffers.slow,
                     state.tier.page_slot, ids)
        want = mem.read_rows(state, ids)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lookup_rows_slow_fallback_mask_and_nd_ids():
    """The fallback mask is the placement table itself: resident pages come
    from the fast buffer, everything else from the slow store — verified
    against the raw buffers, with an N-D id batch (the expert-read shape)."""
    from repro.tiering import migrate as migrate_lib
    mem, state, rows = _tiered_memory()
    table = np.asarray(state.tier.page_slot)
    ids = jnp.asarray([[3, 0], [30, 11], [7, 2]], jnp.int32)   # (3, 2)
    got = np.asarray(jax.jit(migrate_lib.lookup_rows, static_argnums=())(
        mem.buffers.fast, mem.buffers.slow, state.tier.page_slot, ids))
    assert got.shape == (3, 2, 3, 4)
    fast = np.asarray(mem.buffers.fast)
    slow = np.asarray(mem.buffers.slow)
    for i in range(3):
        for j in range(2):
            pid = int(ids[i, j])
            want = fast[table[pid]] if table[pid] >= 0 else slow[pid]
            np.testing.assert_array_equal(got[i, j], want)
    # resident pages really did serve from the fast buffer (hit mask live)
    assert table[3] >= 0 and table[11] >= 0 and table[0] < 0


def test_handle_tier_view_roundtrip():
    """ResourceHandle.tier_view feeds the same arrays lookup_rows needs."""
    from repro import tiering as tm
    from repro.tiering import migrate as migrate_lib
    mem, state, _ = _tiered_memory()
    view = mem.tier_view(state)
    assert set(view) == {"fast", "slow", "page_slot", "scale"}
    assert view["scale"] is None          # "none" codec stores no scales
    ids = jnp.asarray([3, 30], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(migrate_lib.lookup_rows(view["fast"], view["slow"],
                                           view["page_slot"], ids,
                                           scale=view["scale"])),
        np.asarray(mem.lookup_rows(state, ids)))


# ---------------------------------------------------------------------------
# serve engine: in-jit tiered reads + kernel mass stream
# ---------------------------------------------------------------------------


def _engine(arch, seed=0, **kw):
    from repro.configs.registry import get_smoke_config
    from repro.models import transformer as tr
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg = get_smoke_config(arch)
    params = tr.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, ServeEngine(cfg, params, ServeConfig(**kw))


KW = dict(max_seq=64, paged=True, page_t=4, hot_slots=16,
          migration_interval=4, resources=("embeddings",),
          embed_hot_slots=4, embed_rows_per_page=8)


def test_injit_embedding_reads_bit_exact():
    """Serving embeddings through the placement table inside the jitted
    step is bit-identical to the dense table gather — tiers are inclusive,
    so residency can only change WHERE a row is read, never its value."""
    prompt = (np.arange(2 * 10).reshape(2, 10) * 5) % 256
    _, on = _engine("llama3.2-3b", **KW)
    out_on = on.generate(prompt, n_tokens=16)
    _, off = _engine("llama3.2-3b", **KW, jit_tier_reads=False)
    out_off = off.generate(prompt, n_tokens=16)
    np.testing.assert_array_equal(out_on, out_off)
    # the in-jit path really served through the tier (placement live); 16
    # tokens leave room for a re-read after the decode-time promotions
    assert on.daemon["embeddings"].hit_rate() > 0


def test_injit_expert_reads_serve_moe_arch():
    """MoE serving with expert rows gathered in-jit through the placement
    table: same tokens as the dense-dispatch engine, expert tier live."""
    prompt = np.arange(2 * 12).reshape(2, 12) % 256
    kw = dict(max_seq=128, paged=True, page_t=8, hot_slots=4,
              migration_interval=2, resources=("experts",),
              expert_hot_slots=2)
    _, on = _engine("kimi-k2-1t-a32b", **kw)
    out_on = on.generate(prompt, n_tokens=6)
    _, off = _engine("kimi-k2-1t-a32b", **kw, jit_tier_reads=False)
    out_off = off.generate(prompt, n_tokens=6)
    np.testing.assert_array_equal(out_on, out_off)
    assert on.daemon["experts"].hit_rate() > 0


def test_moe_tiered_dispatch_matches_ep():
    """moe_apply_tiered (payload-row gather) == moe_apply_ep (dense-weight
    dispatch) for the same routing, with every page in the slow tier."""
    from repro.configs.registry import get_smoke_config
    from repro.models import moe as moe_lib
    from repro.models import transformer as tr
    cfg = get_smoke_config("kimi-k2-1t-a32b")
    params = tr.init_params(cfg, jax.random.PRNGKey(1))
    ffn = params["blocks"][cfg.pattern.index("moe")]["ffn"]
    g, e = ffn["w_in"].shape[:2]
    payload = jnp.concatenate(
        [ffn[k].reshape(g * e, -1) for k in ("w_gate", "w_in", "w_out")], -1)
    tier = {"fast": jnp.zeros((4,) + payload.shape[1:], payload.dtype),
            "slow": payload,
            "page_slot": jnp.full((g * e,), -1, jnp.int32)}
    p0 = {k: v[0] for k, v in ffn.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 1, cfg.d_model),
                          jnp.bfloat16)
    y_t, idx_t, _ = moe_lib.moe_apply_tiered(p0, x, cfg.moe.top_k,
                                             tier=tier,
                                             group_id=jnp.int32(0))
    y_e, idx_e, _ = moe_lib.moe_apply_ep(p0, x, cfg.moe.top_k)
    np.testing.assert_array_equal(np.asarray(idx_t), np.asarray(idx_e))
    np.testing.assert_allclose(np.asarray(y_t, np.float32),
                               np.asarray(y_e, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kv_kernel_mass_stream_observed():
    """The "kv" resource observes the decode kernel's softmax mass: the
    stream is live (profiler sees traffic), output tokens are identical to
    the fill-proxy engine (the stream changes PLACEMENT, never logits)."""
    prompt = (np.arange(2 * 10).reshape(2, 10) * 3) % 256
    _, kern = _engine("llama3.2-3b", **KW, kv_mass_source="kernel")
    out_k = kern.generate(prompt, n_tokens=8)
    assert kern._last_kv_mass is not None
    m = np.asarray(kern._last_kv_mass)
    assert m.shape == (2, KW["hot_slots"])
    np.testing.assert_allclose(m.sum(-1), 1.0, rtol=1e-4)
    _, fill = _engine("llama3.2-3b", **KW, kv_mass_source="fill")
    out_f = fill.generate(prompt, n_tokens=8)
    np.testing.assert_array_equal(out_k, out_f)
    assert kern.daemon["kv"].hit_rate() > 0
    with pytest.raises(ValueError):
        _engine("llama3.2-3b", **KW, kv_mass_source="bogus")


def test_lane_mode_kernel_mass_masks_inactive_lanes():
    """Lane mode: the kernel mass stream is masked exactly like the gid
    stream — an inactive lane's pages never reach the profiler."""
    from repro.serve.sched import Scheduler, Tenant
    _, eng = _engine("llama3.2-3b", **{**KW, "hot_slots": 5},
                     lanes=2, kv_segments=2)
    sched = Scheduler(eng, [Tenant("a")])
    sched.submit("a", (np.arange(6) * 7 + 1) % 256, max_new=4)
    for _ in range(6):
        sched.step()
    assert eng._last_kv_mass is not None
    # lane 1 never ran a request: its segment-mapped gids are all -1
    sv = eng._kv_lane_stream()
    assert sv is not None
    _, gids = sv
    assert (gids[1] == -1).all()
    assert eng.daemon["kv"].hit_rate() >= 0.0   # stream digested cleanly
