"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode and XLA:CPU accept: block
shapes off the (8, 128) tiling, kernels over fast-memory limits, programs
that do not fit HBM.  These compiles run the main path's kernels and tier
verbs at qwen1.5-4b widths through it, so a regression surfaces here
instead of on the chip.  The topology is described inside a fixture (one
process may hold the TPU library, so nothing touches it at import), and the
persistent compilation cache is off around the compiles: an entry written
for a described device cannot be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import host_offload as ho
from repro.kernels.paged_attn.paged_attn import paged_attention_raw
from repro.tiering import migrate as migrate_lib

# qwen1.5-4b (configs/qwen15_4b.py) and the chip smoke's serving geometry
D_MODEL, HEADS, KV_HEADS, HEAD_DIM, VOCAB, GROUPS = 2560, 20, 20, 128, 151936, 40
LANES, SLOTS, PAGE_T = 4, 8, 64
EMBED_RPP = 64
KV_ROW = (GROUPS, PAGE_T, KV_HEADS, 2 * HEAD_DIM)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """(device-memory, pinned-host) shardings on one described v5e chip."""
    mesh = Mesh(np.asarray(topo.devices[:1]), ("x",))
    return (NamedSharding(mesh, P(), memory_kind="device"),
            NamedSharding(mesh, P(), memory_kind=ho.SLOW_KIND))


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("page_stats", [False, True],
                         ids=["plain", "page_stats"])
def test_paged_attention_compiles_for_v5e(chip, page_stats):
    """The flash-decode kernel at qwen1.5-4b widths compiles through
    Mosaic (a tpu_custom_call), plain and with the page-stats export."""
    dev, _ = chip
    q = _arg((LANES, HEADS, HEAD_DIM), jnp.float32, dev)
    kv = _arg((LANES, SLOTS, PAGE_T, KV_HEADS, HEAD_DIM), jnp.bfloat16, dev)
    lens = _arg((LANES, SLOTS), jnp.int32, dev)
    compiled = jax.jit(lambda q, k, v, n: paged_attention_raw(
        q, k, v, n, interpret=False, return_page_stats=page_stats)
    ).lower(q, kv, kv, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _lookup(chip, n_pages):
    dev, host = chip
    args = (_arg((64, EMBED_RPP, D_MODEL), jnp.bfloat16, dev),
            _arg((n_pages, EMBED_RPP, D_MODEL), jnp.bfloat16, host),
            _arg((n_pages,), jnp.int32, dev),
            _arg((LANES, 1), jnp.int32, dev))
    return jax.jit(migrate_lib.lookup_rows).lower(*args).compile()


def test_lookup_rows_compiles_with_host_store(chip):
    """The in-jit embedding read over a pinned-host vocabulary store: the
    store stays a host argument and the device holds only the batch."""
    n_pages = -(-VOCAB // EMBED_RPP)
    ma = _lookup(chip, n_pages).memory_analysis()
    store = n_pages * EMBED_RPP * D_MODEL * 2
    assert ma.host_argument_size_in_bytes >= store
    assert ma.temp_size_in_bytes + ma.output_size_in_bytes < store // 100


def test_lookup_rows_compiles_for_store_beyond_hbm(chip):
    """A 20 GB host store — more than the chip's 16 GB of HBM — still
    compiles: the gather never brings the store onto the device."""
    n_pages = 20 * 10**9 // (EMBED_RPP * D_MODEL * 2) + 1
    ma = _lookup(chip, n_pages).memory_analysis()
    assert ma.host_argument_size_in_bytes > 16 * 2**30
    assert ma.temp_size_in_bytes + ma.output_size_in_bytes < 2**30


def _host_calls(text):
    return "HostExecute" in text or "host_compute" in text


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_epoch_copy_compiles_with_host_store(chip, codec):
    """The epoch copy over a pinned-host KV store (one page = all 40
    layers' K|V of 64 tokens, 26 MB), with its buffers donated as on the
    chip: gather of the promoted pages and demotion write-back both run
    against host memory, and the store (and the int8 scales) come back in
    pinned_host.  The pages move by DMA only; the int8 codec's 1-D scales
    are scattered by a host computation."""
    dev, host = chip
    n_pages, quota = 6 * 2048 // PAGE_T, 4
    wire = jnp.int8 if codec == "int8" else jnp.bfloat16
    ids = _arg((quota,), jnp.int32, dev)
    args = (_arg((SLOTS,) + KV_ROW, jnp.bfloat16, dev),
            _arg((n_pages,) + KV_ROW, wire, host),
            _arg((n_pages,), jnp.float32, host) if codec == "int8" else None,
            ids, ids, ids)
    # donation as on the chip (the cached jit asks the default backend)
    compiled = jax.jit(functools.partial(migrate_lib._migrate_impl, codec),
                       donate_argnums=(0, 1, 2)).lower(*args).compile()
    _, slow_out, scale_out, _, _ = compiled.output_shardings
    assert slow_out.memory_kind == ho.SLOW_KIND
    assert scale_out is None or scale_out.memory_kind == ho.SLOW_KIND
    assert _host_calls(compiled.as_text()) == (codec == "int8")
    ma = compiled.memory_analysis()
    row = int(np.prod(KV_ROW)) * 2
    assert ma.temp_size_in_bytes < (SLOTS + 2 * quota) * row


def test_kv_flush_compiles_with_host_store(chip):
    """The KV flush (``write_pages``) from the lane ring into a pinned-host
    KV store, donated as on the chip: the written pages cross to the host
    by DMA, none is read back and the store stays in host memory."""
    dev, host = chip
    n_pages, flushed = 6 * 2048 // PAGE_T, 8
    ring = (GROUPS, LANES, SLOTS, PAGE_T, KV_HEADS, HEAD_DIM)
    ids = _arg((flushed,), jnp.int32, dev)
    args = (_arg((SLOTS,) + KV_ROW, jnp.bfloat16, dev),
            _arg((n_pages,) + KV_ROW, jnp.bfloat16, host), None, ids, ids,
            ids, _arg(ring, jnp.bfloat16, dev), _arg(ring, jnp.bfloat16, dev))
    compiled = jax.jit(
        functools.partial(migrate_lib._write_pages_impl, "none"),
        donate_argnums=(0, 1)).lower(*args).compile()
    assert compiled.output_shardings[1].memory_kind == ho.SLOW_KIND
    assert not _host_calls(compiled.as_text())
    # a few layouts of the flushed pages (ring gather, K|V concat, page
    # rows), never the 192-page store
    row = int(np.prod(KV_ROW)) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * flushed * row


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_decode_step_compiles_with_host_embedding_tier(chip, monkeypatch,
                                                       codec):
    """The serving engine's lane decode step at qwen1.5-4b's published
    widths, reading embeddings through a pinned-host tier view, fits one
    v5e, runs the paged-attention kernel through Mosaic and reaches the
    host store by DMA only (no host computation inside the step), the
    int8 codec's per-page scales included."""
    from repro.configs.registry import get_config
    from repro.kernels.paged_attn import paged_attn
    from repro.models import decode as dec
    from repro.models import transformer as tr
    # the kernel asks the default backend, which is the CPU here
    monkeypatch.setattr(paged_attn, "resolve_interpret",
                        lambda interpret: bool(interpret))
    dev, host = chip
    cfg = get_config("qwen1.5-4b")

    def on(tree, sharding):
        return jax.tree.map(lambda x: _arg(x.shape, x.dtype, sharding), tree)

    params = on(jax.eval_shape(lambda: tr.init_params(
        cfg, jax.random.PRNGKey(0))), dev)
    cache = on(jax.eval_shape(lambda: dec.init_paged_cache(
        cfg, LANES, SLOTS, PAGE_T, per_lane_pos=True)), dev)
    n_pages = -(-VOCAB // EMBED_RPP)
    int8 = codec == "int8"
    view = {"fast": _arg((64, EMBED_RPP, D_MODEL), jnp.bfloat16, dev),
            "slow": _arg((n_pages, EMBED_RPP, D_MODEL),
                         jnp.int8 if int8 else jnp.bfloat16, host),
            "page_slot": _arg((n_pages,), jnp.int32, dev),
            "scale": _arg((n_pages,), jnp.float32, host) if int8 else None}

    def step(params, cache, token, view, active):
        tiered = {"embeddings": dict(view, rows_per_page=EMBED_RPP)}
        logits, new, _ = dec.decode_step_paged(
            cfg, params, cache, token, page_t=PAGE_T, return_streams=True,
            tiered=tiered, collect_mass=True)
        return logits, dec.merge_cache(cache, new, active)

    compiled = jax.jit(step).lower(
        params, cache, _arg((LANES, 1), jnp.int32, dev), view,
        _arg((LANES,), jnp.bool_, dev)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _host_calls(text)
    ma = compiled.memory_analysis()
    assert ma.host_argument_size_in_bytes >= n_pages * EMBED_RPP * D_MODEL * (
        1 if int8 else 2)
    dev_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes)
    assert dev_bytes < 15.75e9
