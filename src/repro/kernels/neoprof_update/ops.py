"""Jitted public wrapper: full sketch_update with the Pallas fast path.

Drop-in replacement for repro.core.sketch.sketch_update (same signature and
semantics) that routes the heavy per-segment work through the TPU kernel and
keeps the cheap cross-lane reduction (min over lanes, hot filter,
first-occurrence dedup) in plain jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sketch import SketchParams, SketchState, _first_occurrence
from repro.kernels.neoprof_update import neoprof_update as ku


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def sketch_update(
    state: SketchState,
    page_ids: jax.Array,
    theta: jax.Array,
    params: SketchParams,
    interpret: bool | None = None,
) -> tuple[SketchState, jax.Array]:
    valid = page_ids >= 0
    counts = state.counts
    epochs = state.epochs.astype(jnp.int32)
    hot = state.hot.astype(jnp.int32)

    new_counts, new_epochs, est, hot_before = ku.sketch_update_pallas(
        counts, epochs, hot, page_ids, state.seeds,
        state.cur_epoch.astype(jnp.int32), params.counter_max,
        depth=params.depth, width=params.width, interpret=interpret,
    )
    est_min = jnp.min(est, axis=0)
    already_hot = jnp.all(hot_before > 0, axis=0)
    is_hot = valid & (est_min > theta)
    newly_hot = is_hot & ~already_hot & _first_occurrence(
        jnp.where(valid, page_ids, 0), valid)

    new_hot = ku.sketch_mark_hot_pallas(
        hot, page_ids, is_hot, state.seeds,
        depth=params.depth, width=params.width, interpret=interpret,
    )
    new_state = state._replace(
        counts=new_counts,
        epochs=new_epochs.astype(state.epochs.dtype),
        hot=new_hot.astype(state.hot.dtype),
        n_seen=state.n_seen + jnp.sum(valid, dtype=jnp.int32),
    )
    return new_state, newly_hot
