"""Jitted wrapper: sketch histogram via the Pallas histogram unit."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import sketch as sk
from repro.core.sketch import SketchParams, SketchState
from repro.kernels.cms_hist import cms_hist as kh


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def sketch_histogram(state: SketchState, params: SketchParams,
                     interpret: bool | None = None) -> jax.Array:
    edges = jnp.asarray(sk.hist_edges(params.counter_bits))
    return kh.hist_pallas(
        state.counts[0], state.epochs[0].astype(jnp.int32),
        state.cur_epoch.astype(jnp.int32), edges,
        width=params.width, interpret=interpret,
    )
