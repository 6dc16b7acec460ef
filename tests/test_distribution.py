"""Distribution-layer tests that need >1 device run in a subprocess with
forced host devices (conftest must NOT set the flag globally)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compression
from repro.optim.optimizers import OptConfig, adamw_init, adamw_update
from repro.optim import zero1

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_grad_compression_error_feedback():
    """int8+EF is unbiased over repeats: accumulated error stays bounded and
    the dequantized sum converges to the true sum."""
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                          jnp.float32)}
    ef = compression.ef_init(g)
    total_q = jnp.zeros_like(g["w"])
    n = 20
    for _ in range(n):
        qs, ef = compression.compress_grads(g, ef)
        deq = compression.decompress_grads(qs)
        total_q = total_q + deq["w"]
    err = float(jnp.max(jnp.abs(total_q - n * g["w"])))
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert err <= scale * 1.01 + 1e-6    # residual never exceeds one quantum


def test_zero1_matches_adamw():
    """ZeRO-1 update == per-tensor AdamW (single device)."""
    cfg = OptConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0, total_steps=100)
    params = {"a": jnp.ones((4, 8), jnp.float32) * 0.5,
              "b": jnp.arange(6, dtype=jnp.float32)}
    grads = {"a": jnp.full((4, 8), 0.1, jnp.float32),
             "b": jnp.linspace(-1, 1, 6, dtype=jnp.float32)}
    st_ref = adamw_init(params)
    p_ref, st_ref, _ = adamw_update(cfg, params, grads, st_ref)

    st_z, spec = zero1.zero1_init(params, None)
    p_z, st_z, _ = zero1.zero1_update(cfg, params, grads, st_z, spec, None)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]), np.asarray(p_z[k]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single():
    """8-device (2 data x 4 model) train step: loss finite and equal to the
    unsharded loss (GSPMD correctness)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_smoke_config
        from repro.models import transformer as tr
        from repro.dist.sharding import param_pspecs
        from repro.launch.mesh import make_mesh

        cfg = get_smoke_config('llama3.2-3b')
        mesh = make_mesh((2, 4), ('data', 'model'))
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
        batch = {'tokens': tokens, 'labels': tokens}

        loss_ref = tr.train_loss(cfg, params, batch, remat=False)[0]

        with mesh:
            specs = param_pspecs(params, mesh)
            ps = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                              params, specs)
            bs = jax.tree.map(lambda a: jax.device_put(
                a, NamedSharding(mesh, P('data', None))), batch)
            loss_sh = jax.jit(lambda p, b: tr.train_loss(cfg, p, b,
                                                         remat=False)[0])(ps, bs)
        err = abs(float(loss_ref) - float(loss_sh))
        assert err < 1e-2, (float(loss_ref), float(loss_sh))
        print('OK', float(loss_ref), float(loss_sh))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_ep_moe_matches_local():
    """shard_map EP MoE == single-device dispatch (same routing, no drops)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import moe as M
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        key = jax.random.PRNGKey(0)
        d, e, f, k = 32, 8, 64, 2
        p = M.moe_init(key, d, e, f, shared_f=32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d), jnp.float32)
        y_loc, idx_loc, _ = M.moe_apply_ep(p, x, k, ep_axes=None)
        with mesh:
            ep = M.EPContext(mesh=mesh, expert_axis='model', fsdp_axis='data',
                             dp_axes=('data',), capacity_factor=8.0)
            y_ep, idx_ep, _ = jax.jit(
                lambda p, x: M.moe_apply_ep(p, x, k, ep_axes=ep))(p, x)
        np.testing.assert_array_equal(np.asarray(idx_loc), np.asarray(idx_ep))
        err = float(jnp.max(jnp.abs(y_loc - y_ep)))
        assert err < 2e-2, err
        print('OK', err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_parity():
    """GPipe ppermute pipeline == sequential stage application."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.pipeline import pipeline_apply
        mesh = jax.make_mesh((4,), ('pod',))
        n_stages, m, mb, d = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d), jnp.float32) * 0.2

        def stage(w, x):
            return jnp.tanh(x @ w)

        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d), jnp.float32)
        y_ref = x
        for i in range(n_stages):
            y_ref = stage(ws[i], y_ref)
        with mesh:
            y = jax.jit(lambda ws, x: pipeline_apply(stage, ws, x, mesh=mesh,
                                                     axis='pod'))(ws, x)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-5, err
        print('OK', err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_flash_decode_combine():
    """paged attention sharded over slots == unsharded (combine correctness)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels.paged_attn import ops as pa
        mesh = jax.make_mesh((8,), ('s',))
        b, h, hkv, d, pg, t = 2, 4, 2, 32, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        kp = jax.random.normal(ks[1], (b, pg, t, hkv, d), jnp.float32)
        vp = jax.random.normal(ks[2], (b, pg, t, hkv, d), jnp.float32)
        lens = jax.random.randint(ks[3], (b, pg), 0, t + 1)
        o_ref = pa.paged_attention(q, kp, vp, lens, interpret=True)

        def body(q, kp, vp, lens):
            m, l, acc = pa.paged_attention_local_stats(q, kp, vp, lens,
                                                       interpret=True)
            return pa.combine_stats(m, l, acc, ('s',)).astype(q.dtype)

        with mesh:
            o = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P(), P(None, 's'), P(None, 's'), P(None, 's')),
                out_specs=P(), check_vma=False))(q, kp, vp, lens)
        err = float(jnp.max(jnp.abs(o - o_ref)))
        assert err < 1e-4, err
        print('OK', err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_flash_decode_page_mass_combine():
    """Kernel page-stats combine across 8 shards: the shard-assembled
    per-page softmax mass equals the unsharded kernel export AND the dense
    reference (the global pmax/psum normalizers are the output combine's
    own pair — DESIGN.md §10)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels.paged_attn import ops as pa
        from repro.kernels.paged_attn.ref import page_mass_ref
        mesh = jax.make_mesh((8,), ('s',))
        b, h, hkv, d, pg, t = 2, 4, 2, 32, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        kp = jax.random.normal(ks[1], (b, pg, t, hkv, d), jnp.float32)
        vp = jax.random.normal(ks[2], (b, pg, t, hkv, d), jnp.float32)
        lens = jax.random.randint(ks[3], (b, pg), 0, t + 1)
        o_ref, mass_ref = pa.paged_attention(q, kp, vp, lens, interpret=True,
                                             return_mass=True)

        def body(q, kp, vp, lens):
            m, l, acc, pm, pl = pa.paged_attention_local_stats(
                q, kp, vp, lens, interpret=True, return_page_stats=True)
            o, mass = pa.combine_stats(m, l, acc, ('s',),
                                       page_m=pm, page_l=pl)
            return o.astype(q.dtype), mass

        with mesh:
            o, mass = jax.jit(jax.shard_map(body, mesh=mesh,
                in_specs=(P(), P(None, 's'), P(None, 's'), P(None, 's')),
                out_specs=(P(), P(None, 's')), check_vma=False))(q, kp, vp, lens)
        err_o = float(jnp.max(jnp.abs(o - o_ref)))
        err_m = float(jnp.max(jnp.abs(mass - mass_ref)))
        err_r = float(jnp.max(jnp.abs(mass - page_mass_ref(q, kp, lens))))
        assert err_o < 1e-4, err_o
        assert err_m < 1e-5, err_m
        assert err_r < 1e-5, err_r
        sums = np.asarray(mass).sum(-1)
        assert np.allclose(sums, 1.0, rtol=1e-4), sums
        print('OK', err_m, err_r)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_paged_decode_mass_stream():
    """decode_step_paged over an 8-way slot-sharded mesh: both collect_mass
    branches lower, logits match the single-device path, and the shard-
    assembled kv_mass stream equals the local kernel export."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as tr, decode as dec
        cfg = get_smoke_config('llama3.2-3b')
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh((8,), ('s',))
        smesh = {'mesh': mesh, 'axes': ('s',)}
        tok = jnp.zeros((2, 1), jnp.int32)
        cl = dec.init_paged_cache(cfg, 2, 8, 4)
        logits_l, _, streams_l = dec.decode_step_paged(
            cfg, params, cl, tok, page_t=4, return_streams=True)
        with mesh:
            cs = dec.init_paged_cache(cfg, 2, 8, 4)
            logits_s, _, streams_s = jax.jit(
                lambda p, c, t: dec.decode_step_paged(
                    cfg, p, c, t, page_t=4, smesh=smesh,
                    return_streams=True))(params, cs, tok)
            logits_s0, _ = jax.jit(
                lambda p, c, t: dec.decode_step_paged(
                    cfg, p, c, t, page_t=4, smesh=smesh))(params, cs, tok)
        np.testing.assert_allclose(np.asarray(logits_s),
                                   np.asarray(logits_l),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(logits_s0),
                                   np.asarray(logits_s),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(streams_s['kv_mass']),
                                   np.asarray(streams_l['kv_mass']),
                                   rtol=1e-4, atol=1e-5)
        print('OK')
    """)
    assert "OK" in out


def test_host_offload_fallback():
    """Slow-tier placement is real pinned host memory on this backend too,
    and the round trip through it is value-exact."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.dist import host_offload as ho
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(8.0)
    y = ho.to_slow_tier(x, mesh, P(None))
    z = ho.to_fast_tier(y, mesh, P(None))
    assert y.sharding.memory_kind == ho.SLOW_KIND
    assert z.sharding.memory_kind == "device"
    assert float(jnp.sum(z - x)) == 0.0
    assert ho.supports_memory_kinds()


@pytest.mark.slow
def test_local_grads_compressed_psum_parity():
    """local_grads DP grad psum through the shared int8+EF core: losses
    track the fp32 reduce and the metered wire bytes drop ~4x."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.core.neoprof import NeoProfParams, neoprof_init
        from repro.core.sketch import SketchParams
        from repro.dist import compression
        from repro.models import transformer as tr
        from repro.optim.optimizers import OptConfig, make_optimizer
        from repro.launch.mesh import make_mesh
        from repro.train.step import TrainConfig, build_train_step

        cfg = get_smoke_config('llama3.2-3b')
        mesh = make_mesh((4,), ('data',))
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab)
        batch = {'tokens': tokens, 'labels': tokens}

        def run(local, compress):
            tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0,
                                             total_steps=10),
                               microbatches=2, remat=False,
                               local_grads=local, grad_compression=compress)
            opt_init, _ = make_optimizer(tcfg.opt)
            state = {'params': params, 'opt': opt_init(params),
                     'prof': neoprof_init(NeoProfParams(
                         sketch=SketchParams(width=tcfg.sketch_width)))}
            if compress:
                state['ef'] = compression.ef_init(params)
            losses, wire = [], None
            with mesh:
                step = jax.jit(build_train_step(cfg, mesh, tcfg))
                for _ in range(3):
                    state, m = step(state, batch)
                    losses.append(float(m['loss']))
                    if 'dp_psum_bytes' in m:
                        wire = int(m['dp_psum_bytes'])
            return losses, wire, state

        l_ref, _, _ = run(False, False)        # pjit-reduced baseline
        l_fp, b_fp, _ = run(True, False)       # manual fp32 psum
        l_q, b_q, st_q = run(True, True)       # manual int8+EF psum
        assert np.isfinite(l_ref + l_fp + l_q).all()
        for a, b in zip(l_ref, l_fp):          # manual == pjit (fp32, up to
            assert abs(a - b) < 1e-3, (l_ref, l_fp)   # reduction order)
        for a, b in zip(l_fp, l_q):            # int8+EF tracks fp32
            assert abs(a - b) < 5e-3, (l_fp, l_q)
        assert l_q[-1] < l_q[0]                # and still descends
        ratio = b_fp / b_q
        assert 3.5 < ratio <= 4.0, ratio
        ef_norm = sum(float(jnp.sum(jnp.abs(l)))
                      for l in jax.tree_util.tree_leaves(st_q['ef']))
        assert ef_norm > 0.0                   # error feedback is live
        print('OK', l_fp[-1], l_q[-1], ratio)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_zero1_offload_master_parity():
    """ZeRO-1 with the master/EF vectors parked on the pinned-host slow
    tier (prefetch-before-optimizer-step): bitwise identical to resident."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_smoke_config
        from repro.core.neoprof import NeoProfParams, neoprof_init
        from repro.core.sketch import SketchParams
        from repro.models import transformer as tr
        from repro.optim import zero1
        from repro.optim.optimizers import OptConfig
        from repro.launch.mesh import make_mesh
        from repro.train.step import TrainConfig, build_train_step

        cfg = get_smoke_config('llama3.2-3b')
        mesh = make_mesh((8,), ('data',))
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab)
        batch = {'tokens': tokens, 'labels': tokens}

        def run(offload):
            tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0,
                                             total_steps=10),
                               microbatches=2, remat=False, zero1=True,
                               offload_master=offload)
            opt, _ = zero1.zero1_init(params, mesh, offload=offload)
            state = {'params': params, 'opt': opt,
                     'prof': neoprof_init(NeoProfParams(
                         sketch=SketchParams(width=tcfg.sketch_width)))}
            losses = []
            with mesh:
                step = jax.jit(build_train_step(cfg, mesh, tcfg))
                for _ in range(3):
                    state, m = step(state, batch)
                    losses.append(float(m['loss']))
            return losses, state

        l_res, st_res = run(False)
        l_off, st_off = run(True)
        assert l_res == l_off, (l_res, l_off)
        for k in ('m', 'v'):
            for a, b in zip(jax.tree.leaves(st_res['opt'][k]),
                            jax.tree.leaves(st_off['opt'][k])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK', l_off[-1])
    """)
    assert "OK" in out
