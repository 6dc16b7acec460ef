"""Unit tests for the repro.dist distribution layer beyond the seed tests:
compression round-trips on degenerate tensors, pspec inference fallbacks,
host-offload tier round-trips, a 1-stage pipeline, and the train step with
grad compression enabled end-to-end on the smoke config."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.dist import compression, host_offload as ho
from repro.dist.pipeline import pipeline_apply
from repro.dist.sharding import batch_pspec, cache_pspecs, param_pspecs, path_str


# ---------------------------------------------------------------------------
# compression: property-style round trips
# ---------------------------------------------------------------------------

def _roundtrip(x):
    tree = {"t": x}
    ef = compression.ef_init(tree)
    qs, ef = compression.compress_grads(tree, ef)
    return compression.decompress_grads(qs)["t"], qs, ef


@pytest.mark.parametrize("x", [
    jnp.zeros((8, 8), jnp.float32),                       # all-zero: scale=0
    jnp.full((16,), 3.5, jnp.float32),                    # constant tensor
    jnp.asarray([1e30, -1e30, 1e22], jnp.float32),        # extreme magnitude
    jnp.asarray([1e-30, -1e-30, 0.0], jnp.float32),       # tiny magnitude
    jnp.linspace(-1.0, 1.0, 64).reshape(8, 8),            # generic
])
def test_compression_roundtrip_within_one_quantum(x):
    deq, qs, ef = _roundtrip(x)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert deq.shape == x.shape
    np.testing.assert_allclose(np.asarray(deq), np.asarray(x),
                               atol=scale * 0.5 + 1e-12, rtol=0)
    # residual is exactly what the wire dropped
    np.testing.assert_allclose(np.asarray(ef["t"]),
                               np.asarray(x - deq), rtol=1e-6, atol=1e-30)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_compression_preserves_dtype(dtype):
    x = jnp.arange(16, dtype=dtype) / 16
    deq, qs, _ = _roundtrip(x)
    assert deq.dtype == dtype
    assert qs["t"]["q"].dtype == jnp.int8


def test_compression_unbiased_under_jit():
    """EF keeps the accumulated stream unbiased, also when jitted."""
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(32, 16)),
                          jnp.float32)}

    @jax.jit
    def one(ef):
        qs, ef = compression.compress_grads(g, ef)
        return compression.decompress_grads(qs), ef

    ef = compression.ef_init(g)
    total = jnp.zeros_like(g["w"])
    n = 30
    for _ in range(n):
        deq, ef = one(ef)
        total = total + deq["w"]
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.max(jnp.abs(total - n * g["w"]))) <= scale * 1.01


def test_compressed_bytes_counts_payload():
    qs, _ = compression.compress_grads(
        {"a": jnp.ones((4, 4)), "b": jnp.ones((3,))},
        compression.ef_init({"a": jnp.ones((4, 4)), "b": jnp.ones((3,))}))
    assert compression.compressed_bytes(qs) == (16 + 4) + (3 + 4)


# ---------------------------------------------------------------------------
# sharding: inference + divisibility fallback (AbstractMesh: no devices)
# ---------------------------------------------------------------------------

@pytest.fixture
def mesh24():
    return AbstractMesh((2, 4), ("data", "model"))


def test_param_pspecs_rules(mesh24):
    params = {
        "embed": {"table": jax.ShapeDtypeStruct((256, 64), jnp.bfloat16)},
        "blocks": {
            "ln1": {"scale": jax.ShapeDtypeStruct((4, 64), jnp.float32)},
            "attn": {"wq": jax.ShapeDtypeStruct((4, 64, 64), jnp.bfloat16),
                     "wo": jax.ShapeDtypeStruct((4, 64, 64), jnp.bfloat16)},
            "ffn": {"w_in": jax.ShapeDtypeStruct((4, 64, 128), jnp.bfloat16),
                    "w_out": jax.ShapeDtypeStruct((4, 128, 64), jnp.bfloat16),
                    "router": jax.ShapeDtypeStruct((64, 8), jnp.float32)},
        },
    }
    sp = param_pspecs(params, mesh24)
    assert sp["embed"]["table"] == P("model", None)
    assert sp["blocks"]["ln1"]["scale"] == P(None, None)       # norm: replicated
    assert sp["blocks"]["attn"]["wq"] == P(None, None, "model")  # column
    assert sp["blocks"]["attn"]["wo"] == P(None, "model", None)  # row
    assert sp["blocks"]["ffn"]["w_in"] == P(None, None, "model")
    assert sp["blocks"]["ffn"]["w_out"] == P(None, "model", None)
    assert sp["blocks"]["ffn"]["router"] == P(None, None)      # replicated


def test_param_pspecs_moe_expert_dim(mesh24):
    p = {"blocks": {"ffn": {
        "w_gate": jax.ShapeDtypeStruct((2, 8, 32, 64), jnp.bfloat16),
        "w_out": jax.ShapeDtypeStruct((2, 8, 64, 32), jnp.bfloat16),
    }}}
    sp = param_pspecs(p, mesh24)
    assert sp["blocks"]["ffn"]["w_gate"] == P(None, "model", None, None)
    assert sp["blocks"]["ffn"]["w_out"] == P(None, "model", None, None)


def test_param_pspecs_fallback_to_replicated(mesh24):
    """A dim that doesn't divide the mesh axis must stay unsharded."""
    p = {"w_in": jax.ShapeDtypeStruct((10, 6), jnp.float32),    # 6 % 4 != 0
         "table": jax.ShapeDtypeStruct((7, 64), jnp.float32),   # 7 % 4 != 0
         "tiny": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
    sp = param_pspecs(p, mesh24)
    assert sp["w_in"] == P(None, None)
    assert sp["table"] == P(None, None)
    assert sp["tiny"] == P(None, None)


def test_param_pspecs_fsdp_adds_data_axis(mesh24):
    p = {"w_in": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    sp = param_pspecs(p, mesh24, fsdp=True)
    assert sp["w_in"] == P("data", "model")
    # fallback: nothing left to shard over data -> column sharding only
    q = {"w_in": jax.ShapeDtypeStruct((3, 128), jnp.float32)}
    assert param_pspecs(q, mesh24, fsdp=True)["w_in"] == P(None, "model")


def test_batch_and_cache_pspecs(mesh24):
    assert batch_pspec(mesh24) == P(("data",), None)
    cache = {"blocks": {
        "k": jax.ShapeDtypeStruct((4, 2, 32, 2, 16), jnp.bfloat16),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }}
    sp = cache_pspecs(cache, mesh24)
    assert sp["blocks"]["k"] == P(None, ("data",), "model", None, None)
    assert sp["blocks"]["pos"] == P()
    paged = {"blocks": {
        "k_pages": jax.ShapeDtypeStruct((4, 2, 16, 8, 2, 16), jnp.bfloat16)}}
    sp = cache_pspecs(paged, mesh24, slot_axes=("data", "model"))
    assert sp["blocks"]["k_pages"] == P(None, None, ("data", "model"),
                                        None, None, None)


def test_path_str():
    flat = jax.tree_util.tree_flatten_with_path(
        {"blocks": [{"attn": {"wq": 1}}]})[0]
    assert path_str(flat[0][0]) == "blocks/0/attn/wq"


# ---------------------------------------------------------------------------
# host offload + pipeline on a single device
# ---------------------------------------------------------------------------

def test_host_offload_roundtrip_2d():
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(12.0).reshape(3, 4)
    y = ho.to_fast_tier(ho.to_slow_tier(x, mesh, P(None, None)),
                        mesh, P(None, None))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert isinstance(ho.supports_memory_kinds(), bool)


def test_pipeline_single_stage():
    """n_stages=1 degenerates to a plain scan over microbatches."""
    mesh = jax.make_mesh((1,), ("pod",))
    ws = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 8)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 8))

    def stage(w, h):
        return jnp.tanh(h @ w)

    with mesh:
        y = pipeline_apply(stage, ws, x, mesh=mesh, axis="pod")
    np.testing.assert_allclose(np.asarray(y), np.asarray(stage(ws[0], x)),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# train step with grad compression: end-to-end on the smoke config
# ---------------------------------------------------------------------------

def test_train_step_grad_compression_end_to_end():
    from repro.configs.registry import get_smoke_config
    from repro.core.neoprof import NeoProfParams, neoprof_init
    from repro.core.sketch import SketchParams
    from repro.models import transformer as tr
    from repro.optim.optimizers import OptConfig, make_optimizer
    from repro.train.step import TrainConfig, build_train_step

    cfg = get_smoke_config("llama3.2-3b")
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0, total_steps=10),
                       microbatches=2, remat=False, grad_compression=True)
    step = jax.jit(build_train_step(cfg, None, tcfg))

    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    opt_init, _ = make_optimizer(tcfg.opt)
    state = {"params": params, "opt": opt_init(params),
             "prof": neoprof_init(NeoProfParams(
                 sketch=SketchParams(width=tcfg.sketch_width))),
             "ef": compression.ef_init(params)}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}

    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    # error feedback is live: residuals are nonzero after a step
    ef_norm = sum(float(jnp.sum(jnp.abs(l)))
                  for l in jax.tree_util.tree_leaves(state["ef"]))
    assert ef_norm > 0.0
    assert losses[-1] < losses[0]    # compressed grads still descend


def test_state_shapes_include_ef():
    from repro.configs.registry import get_smoke_config
    from repro.train.step import TrainConfig, make_state_shapes

    cfg = get_smoke_config("llama3.2-3b")
    shapes = make_state_shapes(cfg, TrainConfig(grad_compression=True))
    assert "ef" in shapes
    pl = jax.tree_util.tree_leaves(shapes["params"])
    el = jax.tree_util.tree_leaves(shapes["ef"])
    assert [tuple(e.shape) for e in el] == [tuple(p.shape) for p in pl]
    assert all(e.dtype == jnp.float32 for e in el)


def test_train_step_zero1_compressed_collective_end_to_end():
    """build_train_step(zero1=True, compress_collective=True) jits and
    descends: the flat spec is closure-static (never in the state pytree),
    the EF residual threads through, and the collective-byte aux prices
    the int8 gather under the fp32 one."""
    from repro.configs.registry import get_smoke_config
    from repro.core.neoprof import NeoProfParams, neoprof_init
    from repro.core.sketch import SketchParams
    from repro.models import transformer as tr
    from repro.optim import zero1
    from repro.optim.optimizers import OptConfig
    from repro.train.step import TrainConfig, build_train_step

    cfg = get_smoke_config("llama3.2-3b")
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0, total_steps=10),
                       microbatches=2, remat=False, zero1=True,
                       compress_collective=True)
    step = jax.jit(build_train_step(cfg, None, tcfg))

    params = tr.init_params(cfg, jax.random.PRNGKey(0))
    opt, spec = zero1.zero1_init(params, None, compress_collective=True)
    state = {"params": params, "opt": opt,
             "prof": neoprof_init(NeoProfParams(
                 sketch=SketchParams(width=tcfg.sketch_width)))}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}

    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0]
    assert sum(float(jnp.sum(jnp.abs(e)))
               for e in jax.tree.leaves(state["opt"]["ef"])) > 0.0
    assert int(metrics["collective_bytes"]) < 4 * spec.size
