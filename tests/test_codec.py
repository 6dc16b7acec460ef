"""Codec subsystem tests (tiering/codec.py, DESIGN.md §14).

Property tests for the shared symmetric-int8 core (round-trip error bound,
zero-row guard, outlier rows, error-feedback accumulation), the tier-store
integration (int8 slow stores served within one quantum, wire-verbatim
copy_rows, codec="none" bit-exactness with the pre-codec path), and the
zero1 ``compress_collective`` consumer (fp32 parity + collective byte cut).

The round-trip property runs under hypothesis when available
(requirements-dev.txt; CI) and falls back to a seeded sweep locally.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.tiering as tm
from repro.optim import zero1
from repro.optim.optimizers import OptConfig
from repro.tiering import codec

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # not installed in every env; CI has it
    HAVE_HYPOTHESIS = False


def _spec(**kw):
    base = dict(name="embeddings", n_pages=32, hot_slots=6, quota_pages=4,
                sketch_width=1 << 8, row_shape=(2, 3), row_dtype="bfloat16")
    base.update(kw)
    return tm.ResourceSpec(**base)


def _check_roundtrip(rows: np.ndarray) -> None:
    """The codec contract: per-row error <= scale/2, scale = max|row|/127."""
    x = jnp.asarray(rows, jnp.float32)
    payload, scale = codec.encode_rows("int8", x)
    assert payload.dtype == jnp.int8 and scale.shape == (x.shape[0],)
    deq = np.asarray(codec.decode_rows(payload, scale, jnp.float32))
    err = np.max(np.abs(deq - rows), axis=tuple(range(1, rows.ndim)))
    bound = np.asarray(scale) / 2.0
    assert np.all(err <= bound + 1e-7), (err, bound)


# ---------------------------------------------------------------------------
# int8 core properties
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 8),
           st.floats(1e-4, 1e4))
    def test_roundtrip_bound_property(seed, n_rows, mag):
        rows = np.random.default_rng(seed).normal(
            scale=mag, size=(n_rows, 5)).astype(np.float32)
        _check_roundtrip(rows)
else:
    def test_roundtrip_bound_property():
        for seed, mag in [(0, 1.0), (1, 1e-3), (2, 1e3), (3, 40.0)]:
            rows = np.random.default_rng(seed).normal(
                scale=mag, size=(7, 5)).astype(np.float32)
            _check_roundtrip(rows)


def test_all_zero_row_quantizes_exactly():
    """The 0/0 guard: an all-zero row gets scale 1 and decodes to zeros."""
    rows = jnp.zeros((3, 4), jnp.float32)
    q, scale = codec.quantize_int8(rows, axes=(1,))
    assert np.all(np.asarray(scale) == 1.0)
    np.testing.assert_array_equal(
        np.asarray(codec.dequantize_int8(q, scale, jnp.float32)), 0.0)


def test_outlier_row_error_bounded_by_its_own_scale():
    """Per-ROW scales: one outlier row widens only its own quantum, and
    even there the error stays <= scale/2 (= outlier / 254)."""
    rows = np.full((4, 8), 0.01, np.float32)
    rows[2, 3] = 1000.0
    _check_roundtrip(rows)
    _, scale = codec.encode_rows("int8", jnp.asarray(rows))
    s = np.asarray(scale)
    assert s[2] == pytest.approx(1000.0 / 127.0)
    assert np.all(s[[0, 1, 3]] == pytest.approx(0.01 / 127.0))


def test_error_feedback_accumulation_unbiased():
    """n repeats of quantize(delta + residual) sum to n*delta within one
    quantum — the EF contract zero1's compressed collective relies on."""
    rng = np.random.default_rng(5)
    delta = jnp.asarray(rng.normal(size=(2, 256)) * 0.1, jnp.float32)
    flat = delta.reshape(-1)
    ef = jnp.zeros_like(flat)
    total = jnp.zeros_like(flat)
    n = 25
    for _ in range(n):
        applied, ef, _ = zero1.compress_delta(flat, ef, n_shards=2)
        total = total + applied
    err = float(jnp.max(jnp.abs(total - n * flat)))
    quantum = float(jnp.max(codec.symmetric_scale(delta.reshape(2, -1),
                                                  axes=(1,))))
    assert err <= quantum * 1.01 + 1e-6


def test_fp32_codec_is_identity_for_bf16():
    rows = jnp.asarray(np.random.default_rng(0).normal(size=(6, 4)),
                       jnp.bfloat16)
    payload, scale = codec.encode_rows("fp32", rows)
    assert scale is None and payload.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(codec.decode_rows(payload, None, jnp.bfloat16)),
        np.asarray(rows))


def test_wire_row_bytes_schedule():
    assert codec.wire_row_bytes("none", (2, 3), "bfloat16") == 12
    assert codec.wire_row_bytes("fp32", (2, 3), "bfloat16") == 24
    assert codec.wire_row_bytes("int8", (2, 3), "bfloat16") == 6 + 4
    with pytest.raises(KeyError):
        codec.wire_row_bytes("zstd", (2, 3), "bfloat16")


# ---------------------------------------------------------------------------
# tier-store integration
# ---------------------------------------------------------------------------

def _bound_mem(codec_name: str):
    spec = _spec(slow_codec=codec_name)
    mem = tm.TieredMemory.from_spec(spec)
    data = jnp.asarray(
        np.random.default_rng(1).normal(size=(spec.n_pages,) + spec.row_shape),
        jnp.bfloat16)
    mem.bind_data(data)
    return spec, mem, data


def test_int8_store_serves_within_one_quantum():
    """Slow-fallback reads, promoted fast-tier reads, and the in-jit
    lookup_rows path all decode within scale/2 per element."""
    spec, mem, data = _bound_mem("int8")
    state, stats = mem.init(), tm.TierStats(name="embeddings")
    scale = np.asarray(mem.buffers.scale)
    ids = np.array([3, 9, 21])
    # one int8 quantum plus the bf16 half-ulp the fast dtype re-rounds into
    bound = (scale[ids].reshape(-1, 1, 1) / 2.0
             + np.abs(np.asarray(data[ids], np.float32)) * 2.0 ** -8 + 1e-7)

    for reader in (lambda: mem.read_rows(state, ids),
                   lambda: mem.lookup_rows(state, jnp.asarray(ids))):
        err = np.abs(np.asarray(reader(), np.float32)
                     - np.asarray(data[ids], np.float32))
        assert np.all(err <= bound)

    mem.enqueue(ids.tolist())
    state, event = mem.migrate(state, stats)
    assert mem.apply_migration(event, stats) > 0
    _, hit = tm.lookup(state, jnp.asarray(ids))
    assert np.all(np.asarray(hit))
    # the fast tier holds the DECODED copy (native dtype, one-time decode)
    assert mem.buffers.fast.dtype == jnp.bfloat16
    err = np.abs(np.asarray(mem.read_rows(state, ids), np.float32)
                 - np.asarray(data[ids], np.float32))
    assert np.all(err <= bound)


def test_int8_wire_bytes_metered_not_native():
    """Quota and migration counters meter the compressed wire bytes."""
    spec, mem, _ = _bound_mem("int8")
    assert spec.wire_row_bytes == codec.wire_row_bytes(
        "int8", spec.row_shape, spec.row_dtype)
    assert mem.row_bytes == spec.wire_row_bytes
    assert spec.quota_bytes == 2 * spec.quota_pages * spec.wire_row_bytes
    state, stats = mem.init(), tm.TierStats(name="embeddings")
    mem.enqueue([1, 2, 3])
    state, event = mem.migrate(state, stats)
    moved = mem.apply_migration(event, stats)
    assert moved == 3 * spec.wire_row_bytes
    assert stats.max_epoch_bytes <= spec.quota_bytes


def test_copy_rows_preserves_wire_format():
    """The reuse-store publish verb duplicates payload AND scale verbatim:
    dst pages decode bit-identically to src pages."""
    spec, mem, _ = _bound_mem("int8")
    state = mem.init()
    src, dst = np.array([4, 7]), np.array([30, 31])
    mem.copy_rows(state, src, dst)
    np.testing.assert_array_equal(np.asarray(mem.buffers.slow)[dst],
                                  np.asarray(mem.buffers.slow)[src])
    np.testing.assert_array_equal(np.asarray(mem.buffers.scale)[dst],
                                  np.asarray(mem.buffers.scale)[src])
    np.testing.assert_array_equal(
        np.asarray(mem.read_rows(state, dst)),
        np.asarray(mem.read_rows(state, src)))


def test_write_rows_reencodes_demoted_payload():
    """Owner refresh on an int8 store re-quantizes: the slow copy decodes
    to the NEW rows within one quantum of the new per-row scale."""
    spec, mem, _ = _bound_mem("int8")
    state = mem.init()
    ids = np.array([11, 12])
    new = jnp.asarray(np.random.default_rng(2).normal(
        size=(2,) + spec.row_shape) * 3.0, jnp.bfloat16)
    mem.write_rows(state, ids, new)
    scale = np.asarray(mem.buffers.scale)[ids].reshape(-1, 1, 1)
    err = np.abs(np.asarray(mem.read_rows(state, ids), np.float32)
                 - np.asarray(new, np.float32))
    # reads come back in the fast dtype (bf16): one int8 quantum plus the
    # bf16 half-ulp of the decoded value
    bound = scale / 2.0 + np.abs(np.asarray(new, np.float32)) * 2.0 ** -8
    assert np.all(err <= bound + 1e-7)


def test_codec_none_matches_pre_codec_path_bitwise():
    """codec="none" is byte-for-byte the old data path: same buffers, same
    reads, no scale vector, native wire bytes."""
    spec_n, mem_n, data = _bound_mem("none")
    assert mem_n.buffers.scale is None
    assert mem_n.buffers.slow.dtype == data.dtype
    assert spec_n.wire_row_bytes == spec_n.row_bytes
    state = mem_n.init()
    ids = np.arange(spec_n.n_pages)
    np.testing.assert_array_equal(np.asarray(mem_n.read_rows(state, ids)),
                                  np.asarray(data))
    view = mem_n.tier_view(state)
    assert view["scale"] is None


# ---------------------------------------------------------------------------
# the zero1 consumer
# ---------------------------------------------------------------------------

def test_zero1_compressed_collective_parity_and_bytes():
    """compress_collective tracks the fp32 trajectory within EF tolerance,
    keeps m/v bitwise identical, and cuts the gather's wire bytes ~4x."""
    cfg = OptConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0,
                    total_steps=100)
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(16, 24)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(48,)), jnp.float32)}
    st_f, spec = zero1.zero1_init(params, None)
    st_c, _ = zero1.zero1_init(params, None, compress_collective=True)
    assert "ef" in st_c and jax.tree.map(jnp.shape, st_c["ef"]) == \
        jax.tree.map(jnp.shape, params)
    pf, pc = params, params
    for _ in range(5):
        grads = jax.tree.map(
            lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.1,
                                  jnp.float32), params)
        pf, st_f, om_f = zero1.zero1_update(cfg, pf, grads, st_f, spec, None)
        pc, st_c, om_c = zero1.zero1_update(cfg, pc, grads, st_c, spec, None,
                                            compress_collective=True)
    # m/v/step never see the codec — quantization is strictly post-update
    for k in ("m", "v"):
        for a, b in zip(jax.tree.leaves(st_f[k]), jax.tree.leaves(st_c[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    drift = max(float(jnp.max(jnp.abs(pf[k] - pc[k]))) for k in params)
    assert drift <= 1e-3
    assert om_f["collective_bytes"] == 4 * spec.size
    assert om_c["collective_bytes"] / om_f["collective_bytes"] <= 0.30


def test_zero1_toggle_off_threads_ef_through():
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    st, spec = zero1.zero1_init(params, None, compress_collective=True)
    grads = {"w": jnp.full((8, 8), 0.1, jnp.float32)}
    cfg = OptConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0,
                    total_steps=10)
    _, st2, _ = zero1.zero1_update(cfg, params, grads, st, spec, None,
                                   compress_collective=False)
    np.testing.assert_array_equal(np.asarray(st2["ef"]["w"]),
                                  np.asarray(st["ef"]["w"]))
