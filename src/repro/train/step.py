"""Train-step builder: pjit-sharded, microbatched, NeoMem-instrumented.

build_train_step(cfg, mesh, ...) returns (step_fn, shardings) where step_fn
is jit-able with explicit in/out shardings and performs:

  1. grad-accumulation scan over microbatches (activation-memory knob),
  2. per-layer remat inside the layer-group scan,
  3. EP MoE via shard_map (models.moe.EPContext) when the config is MoE,
  4. AdamW / Adafactor / ZeRO-1 update (per opt config),
  5. optional int8+error-feedback gradient compression,
  6. NeoMem profiling: the MoE router streams from the forward pass are fed
     to the on-device NeoProf sketch INSIDE the step (zero extra host work —
     the paper's device-side offload, expressed in XLA).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.neoprof import NeoProfParams, neoprof_init, neoprof_observe
from repro.core.sketch import SketchParams
from repro.dist import compression
from repro.dist.sharding import batch_pspec, param_pspecs
from repro.models import transformer as tr
from repro.models.moe import EPContext
from repro.optim import zero1
from repro.optim.optimizers import OptConfig, make_optimizer


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    microbatches: int = 1
    remat: bool = True
    grad_compression: bool = False
    zero1: bool = False
    compress_collective: bool = False  # int8+EF ZeRO-1 delta gather (§14)
    fsdp: bool = False                 # ZeRO-3 weight sharding over 'data'
    local_grads: bool = False          # defer the DP grad all-reduce out of
                                       # the microbatch loop (§Perf cell B)
    offload_master: bool = False       # ZeRO-1 m/v/ef on the pinned-host
                                       # slow tier; prefetched back during
                                       # the backward (DESIGN.md §15)
    profile_experts: bool = True       # NeoMem router-stream profiling
    sketch_width: int = 1 << 14


def _ep_context(cfg: ArchConfig, mesh) -> EPContext | None:
    if cfg.moe is None or mesh is None:
        return None
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    return EPContext(mesh=mesh, expert_axis="model", fsdp_axis="data",
                     dp_axes=dp)


def build_train_step(cfg: ArchConfig, mesh, tcfg: TrainConfig = TrainConfig()):
    ep = _ep_context(cfg, mesh)
    opt_init, opt_update = make_optimizer(tcfg.opt)
    prof_params = NeoProfParams(sketch=SketchParams(width=tcfg.sketch_width))
    z1spec = None
    if tcfg.zero1:
        # the shard spec is trace-time static (shapes + treedef only), so
        # it lives in the closure, never in the jitted state pytree
        p_shapes = jax.eval_shape(
            lambda: tr.init_params(cfg, jax.random.PRNGKey(0)))
        z1spec = zero1.shard_spec(p_shapes, zero1._n_shards(mesh))

    def loss_fn(params, mb):
        loss, (metrics, aux) = tr.train_loss(cfg, params, mb,
                                             remat=tcfg.remat, ep_axes=ep)
        streams = aux.get("router_streams")
        return loss, (metrics, streams)

    def train_step(state, batch):
        params, opt_state, prof = state["params"], state["opt"], state["prof"]
        if tcfg.zero1 and tcfg.offload_master:
            # promote the parked master vectors FIRST: the fetch has no data
            # dependency on the grads, so XLA overlaps the host→device copy
            # with the whole backward below (prefetch-before-optimizer-step)
            opt_state = zero1.fetch_opt(opt_state, mesh, z1spec)

        def micro(carry, mb):
            gacc, lacc = carry
            (loss, (_, streams)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            gacc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), gacc, grads)
            return (gacc, lacc + loss), streams

        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # (B, ...) -> (M, B/M, ...) WITHOUT cross-shard movement: group rows
        # per DP shard first (dim0 stays DP-sharded), then swap to put the
        # microbatch axis in front.  batch.reshape(M, B/M, ...) would shuffle
        # rows across shards (all-to-all); this form is layout-local.
        m = tcfg.microbatches
        mbs = jax.tree.map(
            lambda x: x.reshape((x.shape[0] // m, m) + x.shape[1:]).swapaxes(0, 1),
            batch)

        if tcfg.local_grads and mesh is not None:
            # §Perf cell B: under plain pjit every microbatch's value_and_grad
            # ends in a full DP grad all-reduce INSIDE the scan (M x the
            # bytes).  Going manual over the DP axes keeps grads shard-local
            # through the accumulation; one psum after the loop does the job.
            dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
            # satellite of ROADMAP item 4: under grad_compression the DP
            # all-reduce itself runs through the shared int8+EF core — each
            # shard quantizes its local sum and the wire carries int8 + one
            # fp32 scale per tensor instead of fp32 everywhere
            dp_compress = tcfg.grad_compression

            def grad_loop(params_l, mbs_l, ef_l):
                z = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params_l)

                def f(carry, mb):
                    gacc, lacc = carry
                    (loss, _), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params_l, mb)
                    gacc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                        gacc, grads)
                    return (gacc, lacc + loss), None

                (gsum, lsum), _ = jax.lax.scan(f, (z, 0.0), mbs_l)
                if dp_compress:
                    gsum, ef_l = compression.compress_psum(gsum, ef_l, dp)
                else:
                    gsum = jax.lax.psum(gsum, dp)
                # each shard's loss is the mean over its own rows: the
                # global mean (and its gradient) is the mean over shards
                n = jax.lax.psum(1.0, dp)
                gsum = jax.tree.map(lambda g: g / n, gsum)
                return gsum, jax.lax.psum(lsum, dp) / n, ef_l

            pspec = jax.tree.map(lambda _: P(), params)
            mspec = jax.tree.map(lambda _: P(None, dp), mbs)
            ef_in = state["ef"] if dp_compress else jax.tree.map(
                lambda _: jnp.zeros((0,), jnp.float32), params)
            # manual over the DP axes only: the partitioner keeps the rest
            gsum, lsum, new_ef = jax.shard_map(
                grad_loop, mesh=mesh, in_specs=(pspec, mspec, pspec),
                out_specs=(pspec, P(), pspec), axis_names=frozenset(dp),
                check_vma=False)(params, mbs, ef_in)
            streams = None
        else:
            (gsum, lsum), streams = jax.lax.scan(micro, (zero_g, 0.0), mbs)
            dp_compress = False
        grads = jax.tree.map(lambda g: g / tcfg.microbatches, gsum)
        loss = lsum / tcfg.microbatches

        # NeoMem: profile the token->expert stream on-device
        if tcfg.profile_experts and cfg.moe is not None and streams is not None \
                and getattr(streams, "size", 0):
            page_stream = streams.reshape(-1)[: 8192].astype(jnp.int32)
            prof = neoprof_observe(prof, page_stream, prof_params)

        if tcfg.grad_compression and not dp_compress:
            # link-sim mode: compress AFTER the (uncompressed) reduce; under
            # local_grads the reduce itself was the compressed hop above
            qs, new_ef = compression.compress_grads(grads, state["ef"])
            grads = compression.decompress_grads(qs)
        if tcfg.zero1:
            new_params, new_opt, om = zero1.zero1_update(
                tcfg.opt, params, grads, opt_state, z1spec, mesh,
                compress_collective=tcfg.compress_collective)
            if tcfg.offload_master:
                new_opt = zero1.offload_opt(new_opt, mesh, z1spec)
        else:
            new_params, new_opt, om = opt_update(params, grads, opt_state)

        new_state = dict(state, params=new_params, opt=new_opt, prof=prof)
        if tcfg.grad_compression:
            new_state["ef"] = new_ef
        metrics = {"loss": loss, **om}
        if tcfg.local_grads and mesh is not None:
            # wire bytes ONE shard contributes to the DP grad reduce (static;
            # float32 — at published widths the count exceeds int32)
            metrics["dp_psum_bytes"] = jnp.float32(compression.psum_bytes(
                grads, compressed=dp_compress))
        return new_state, metrics

    return train_step


def make_state_shapes(cfg: ArchConfig, tcfg: TrainConfig, mesh=None):
    """abstract (ShapeDtypeStruct) train state — no allocation (dry-run)."""
    opt_init, _ = make_optimizer(tcfg.opt)
    prof_params = NeoProfParams(sketch=SketchParams(width=tcfg.sketch_width))

    def init():
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        state = {"params": params, "prof": neoprof_init(prof_params)}
        if tcfg.zero1:
            state["opt"], _ = zero1.zero1_init(
                params, None, compress_collective=tcfg.compress_collective)
        else:
            state["opt"] = opt_init(params)
        if tcfg.grad_compression:
            state["ef"] = compression.ef_init(params)
        return state

    return jax.eval_shape(init)


def state_shardings(state_shapes, mesh, fsdp: bool = False):
    """Shardings for the train state: params/opt by rule; prof replicated."""
    pspecs = param_pspecs(state_shapes["params"], mesh, fsdp=fsdp)

    def opt_specs(o):
        if isinstance(o, dict) and "s" in o:                     # Adafactor
            def fact(shape_struct, ps):
                parts = tuple(ps)
                if len(shape_struct.shape) >= 2 and shape_struct.shape[-1] > 1 \
                        and shape_struct.shape[-2] > 1:
                    return {"vr": P(*parts[:-1]),
                            "vc": P(*(parts[:-2] + parts[-1:]))}
                return {"v": ps}
            s_specs = jax.tree.map(
                fact, state_shapes["params"], pspecs,
                is_leaf=lambda x: hasattr(x, "shape") or isinstance(x, P))
            return {"s": s_specs, "step": P()}
        # AdamW, and ZeRO-1's m/v/ef trees (placed like the params here;
        # zero1_update constrains them to its own shards)
        return {k: (P() if k == "step" else pspecs) for k in o}

    specs = {
        "params": pspecs,
        "opt": opt_specs(state_shapes["opt"]),
        "prof": jax.tree.map(lambda l: P(*([None] * l.ndim)),
                             state_shapes["prof"]),
    }
    if "ef" in state_shapes:
        specs["ef"] = pspecs
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(cfg: ArchConfig, mesh, with_aux: bool):
    bspec = batch_pspec(mesh)
    out = {"tokens": NamedSharding(mesh, bspec),
           "labels": NamedSharding(mesh, bspec)}
    if with_aux:
        out["aux_embeds"] = NamedSharding(
            mesh, P(bspec[0] if len(bspec) else None, None, None))
    return out
