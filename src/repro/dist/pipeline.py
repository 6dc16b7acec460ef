"""GPipe-style microbatch pipeline over one mesh axis (shard_map + ppermute).

Stage i's weights live on mesh shard i; microbatches enter at stage 0 and
flow stage-to-stage through a ``ppermute`` ring, one hop per tick — the
DMA engine of the distribution layer, overlapping stage compute with
activation movement.  The schedule is plain GPipe: m microbatches through
n stages take m + n - 1 ticks with the usual (n-1)/(m+n-1) bubble.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis: str):
    """Apply ``stage_fn(w_i, .)`` for i = 0..n-1 as a microbatch pipeline.

    stage_fn:     (stage weights, (mb, ...) activations) -> (mb, ...)
                  activations, shape- and dtype-preserving.
    stage_params: pytree with leaves stacked (n_stages, ...) — leaf i on
                  mesh shard i along ``axis``.
    x:            (n_micro, mb, ...) microbatched input, replicated.
    Returns stage_{n-1}(...stage_0(x)) per microbatch: (n_micro, mb, ...),
    replicated over the mesh.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(w_loc, x_all):
        w = jax.tree.map(lambda a: a[0], w_loc)      # this shard's stage
        idx = jax.lax.axis_index(axis)
        pad = jnp.zeros((n_stages - 1,) + x_all.shape[1:], x_all.dtype)
        feed = jnp.concatenate([x_all, pad], axis=0)   # (total, mb, ...)

        def tick(buf, t):
            # stage 0 pulls a fresh microbatch; others consume the ring
            inp = jnp.where(idx == 0, feed[t], buf)
            out = stage_fn(w, inp)
            return jax.lax.ppermute(out, axis, perm), out

        _, outs = jax.lax.scan(tick, jnp.zeros_like(x_all[0]),
                               jnp.arange(total))
        # microbatch j finishes on the last stage at tick j + n_stages - 1
        y = outs[n_stages - 1:]
        return jax.lax.psum(
            jnp.where(idx == n_stages - 1, y, jnp.zeros_like(y)), axis)

    wspec = jax.tree.map(lambda _: P(axis), stage_params)
    xspec = P(*([None] * x.ndim))
    return jax.shard_map(body, mesh=mesh, in_specs=(wspec, xspec),
                         out_specs=xspec, check_vma=False)(stage_params, x)
