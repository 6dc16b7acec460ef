"""ZeRO-1: optimizer state sharded over the full device mesh.

Adam's m/v are elementwise, so each can live split across devices: every
param's m/v (fp32, in the param's own shape) is sharded over ALL mesh axes
along the first dim that divides evenly (replicated when none does — only
tiny leaves such as a bias of odd length).  The update runs shard-local on
the matching slice of the (replicated) grads; the delta is gathered back
to each param's own sharding by XLA when applied (one all-gather worth of
bytes per step — the classic ZeRO-1 trade of memory for collective).

For a 27B dense model on 256 chips this turns 216 GB of fp32 m+v into
0.84 GB/chip.  Used by the hillclimb as an alternative to Adafactor.

The state keeps each param's shape rather than one flat vector: slicing a
mesh-sharded flat vector back into leaves costs the TPU compiler time in
proportion to the vector's length (minutes per step program at a few
billion params on 4 chips), while a per-leaf shard is a plain dim split.

``compress_collective`` (DESIGN.md §14) quantizes the DELTA to int8 per
shard — one symmetric scale per mesh-device shard of each leaf — before it
is gathered back to param shardings, cutting the step's dominant
collective ~4x (int8 payload + one fp32 scale/shard vs fp32 everywhere).
A local fp32 error-feedback tree (``state["ef"]``, sharded like m/v)
carries the quantization residual into the next step, so the accumulated
applied update is unbiased — the same contract as the gradient link in
:mod:`repro.dist.compression`, sharing the same
:func:`repro.tiering.codec.quantize_int8` core.  Ordering matters: the
global-norm clip runs on the GRADIENT tree first (identical in both
modes), and quantization happens strictly after the optimizer math, so
m/v/step trajectories stay bitwise independent of the codec — only the
applied delta differs, by at most one quantum per shard.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.optim.optimizers import OptConfig, clip_by_global_norm, schedule
from repro.tiering.codec import dequantize_int8, quantize_int8

_VECTORS = ("m", "v", "ef")     # the per-param fp32 state trees


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Where each param's optimizer state is split: ``dims[i]`` is the dim
    of leaf ``i`` sharded ``n_shards`` ways (None = replicated)."""
    dims: tuple
    treedef: Any
    n_shards: int
    size: int                   # parameter count over all leaves


def shard_spec(params, n_shards: int) -> ShardSpec:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    dims = tuple(next((d for d, n in enumerate(l.shape) if n % n_shards == 0),
                      None) for l in leaves)
    return ShardSpec(dims, treedef, n_shards,
                     sum(int(np.prod(l.shape)) for l in leaves))


def _pspec(mesh, dim, ndim) -> P:
    return P(*(tuple(mesh.axis_names) if d == dim else None
               for d in range(ndim)))


def state_pspecs(spec: ShardSpec, mesh, params):
    """PartitionSpec tree (like ``params``) of the m/v/ef trees."""
    leaves = spec.treedef.flatten_up_to(params)
    return spec.treedef.unflatten(
        [_pspec(mesh, d, l.ndim) for d, l in zip(spec.dims, leaves)])


def _n_shards(mesh) -> int:
    return int(np.prod(mesh.devices.shape)) if mesh is not None else 1


def zero1_init(params, mesh, compress_collective: bool = False,
               offload: bool = False):
    n = _n_shards(mesh)
    spec = shard_spec(params, n)

    def z():
        tree = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if mesh is None:
            return tree
        return jax.tree.map(
            lambda x, ps: jax.device_put(x, NamedSharding(mesh, ps)),
            tree, state_pspecs(spec, mesh, params))

    state = {"m": z(), "v": z(), "step": jnp.zeros((), jnp.int32)}
    if compress_collective:
        # local error-feedback residual of the quantized delta collective —
        # sharded exactly like m/v, never itself gathered
        state["ef"] = z()
    if offload:
        # park the master vectors in the slow tier between steps
        # (DESIGN.md §15): the train step prefetches them back during the
        # backward (``fetch_opt``) and re-offloads after the update
        state = offload_opt(state, mesh, spec)
    return state, spec


def _opt_tiered(state, mesh, spec: ShardSpec, mover):
    """Move every master tree (m/v/ef — not the step scalar) between
    memory tiers with :mod:`repro.dist.host_offload`, each leaf under its
    own ZeRO-1 sharding.  Identity without a mesh; the move never changes
    values, only placement, so the offloaded path stays BITWISE identical
    to the resident one."""
    if mesh is None:
        return state
    out = dict(state)
    for k in _VECTORS:
        if k in state:
            out[k] = jax.tree.map(lambda x, ps: mover(x, mesh, ps), state[k],
                                  state_pspecs(spec, mesh, state[k]))
    return out


def offload_opt(state, mesh, spec: ShardSpec):
    """Demote the ZeRO-1 master/EF trees to the pinned-host slow tier."""
    from repro.dist import host_offload  # lazy: optim must stay dist-free
    return _opt_tiered(state, mesh, spec, host_offload.to_slow_tier)


def fetch_opt(state, mesh, spec: ShardSpec):
    """Promote the master/EF trees back to device memory.  Issue this
    BEFORE the gradient computation inside the jitted step: the fetch has
    no data dependency on the grads, so XLA's scheduler overlaps the
    host→device copy with the backward pass (prefetch-before-consume)."""
    from repro.dist import host_offload
    return _opt_tiered(state, mesh, spec, host_offload.to_fast_tier)


def compress_delta(delta: jax.Array, ef: jax.Array, n_shards: int
                   ) -> tuple[jax.Array, jax.Array, int]:
    """int8-quantize a flat delta per mesh shard with error feedback.

    -> (applied delta fp32, new residual, collective wire bytes).  The flat
    vector holds ``n_shards`` equal contiguous blocks, one per shard (a
    leaf's shard-major view), so the per-shard view is a plain reshape;
    each shard quantizes against its own symmetric scale — the same shape
    the gather collective moves, so the wire carries one int8 byte per
    element plus one fp32 scale per shard (~4x under fp32).
    """
    x = delta + ef
    q, scale = quantize_int8(x.reshape(n_shards, -1), axes=(1,))
    applied = dequantize_int8(q, scale, jnp.float32).reshape(-1)
    return applied, x - applied, int(q.size) + 4 * n_shards


def _shard_major(x, dim):
    """``x`` as a flat vector whose equal blocks are its shards along
    ``dim`` (the layout :func:`compress_delta` quantizes per block; one
    block for a replicated leaf, ``dim`` None)."""
    return x.reshape(-1) if dim is None else jnp.moveaxis(x, dim, 0).reshape(-1)


def _from_shard_major(flat, dim, shape):
    if dim is None:
        return flat.reshape(shape)
    moved = (shape[dim],) + shape[:dim] + shape[dim + 1:]
    return jnp.moveaxis(flat.reshape(moved), 0, dim)


def zero1_update(cfg: OptConfig, params, grads, state, spec: ShardSpec, mesh,
                 compress_collective: bool = False):
    """Shard-local AdamW; delta gathered back to param shardings.

    ``compress_collective`` requires the ``"ef"`` residual in ``state``
    (init with ``zero1_init(..., compress_collective=True)``); the delta is
    int8-quantized per shard before the gather and the residual carries to
    the next step.  The aux dict reports the gather's wire bytes in both
    modes (``collective_bytes``, a float32: at published widths the count
    exceeds int32).
    """
    step = state["step"] + 1
    lr = schedule(cfg, step)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    flat = spec.treedef.flatten_up_to
    leaves = zip(spec.dims, flat(params), flat(grads), flat(state["m"]),
                 flat(state["v"]),
                 flat(state["ef"]) if compress_collective
                 else [None] * len(spec.dims))
    new_p, new_m, new_v, new_ef, wire = [], [], [], [], 0
    for dim, p, g, m, v, ef in leaves:
        if mesh is None:
            local = replicated = lambda x: x
        else:
            local = lambda x, ps=_pspec(mesh, dim, p.ndim): \
                jax.lax.with_sharding_constraint(x, NamedSharding(mesh, ps))
            replicated = lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P()))
        g = local(g)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps) \
            + cfg.weight_decay * local(p.astype(jnp.float32))
        delta = lr * u
        new_m.append(m)
        new_v.append(v)
        if compress_collective:
            applied, res, w = compress_delta(
                _shard_major(delta, dim), _shard_major(ef, dim),
                1 if dim is None else spec.n_shards)
            delta = _from_shard_major(applied, dim, delta.shape)
            new_ef.append(local(_from_shard_major(res, dim, delta.shape)))
            wire += w
        else:
            wire += 4 * delta.size
        # the delta stays fp32 through the gather — the subtraction below
        # accumulates in fp32 and casts once, per leaf
        new_p.append((p.astype(jnp.float32) - replicated(delta))
                     .astype(p.dtype))
    unflat = spec.treedef.unflatten
    new_state = {"m": unflat(new_m), "v": unflat(new_v), "step": step}
    if compress_collective:
        new_state["ef"] = unflat(new_ef)
    elif "ef" in state:          # state threads through unchanged when the
        new_state["ef"] = state["ef"]   # mode is toggled off mid-run
    return unflat(new_p), new_state, {"gnorm": gnorm, "lr": lr,
                                      "collective_bytes": jnp.float32(wire)}
