"""NeoProf histogram-unit Pallas kernel (paper Fig. 9).

64-bin histogram over the row-0 sketch counters, so the host reads 64 scalars
instead of W counters (the paper's argument: don't ship the sketch over the
link).  Segment-gridded compare-reduce: for each lane-aligned segment of the
counter row, bin membership is a (Wseg x 64) comparison against the static
bin edges, reduced over the segment and accumulated across the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketch import HIST_BINS
from repro.kernels.backend import resolve_interpret

DEFAULT_SEG = 512


def _hist_kernel(counts_ref, epochs_ref, meta_ref, edges_ref, out_ref, *, seg):
    k = pl.program_id(0)
    cur_epoch = meta_ref[0, 0]
    live = jnp.where(epochs_ref[0, :] == cur_epoch, counts_ref[0, :], 0)  # (Wseg,)
    lo = edges_ref[0, :]                       # (HIST_BINS,) lower edges
    hi = edges_ref[1, :]                       # (HIST_BINS,) upper edges
    member = (live[:, None] >= lo[None, :]) & (live[:, None] < hi[None, :])
    part = jnp.sum(member.astype(jnp.int32), axis=0)        # (HIST_BINS,)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0, :] += part


@functools.partial(jax.jit, static_argnames=("seg", "width", "interpret"))
def hist_pallas(
    counts_row0: jax.Array,   # (W,) int32
    epochs_row0: jax.Array,   # (W,) int32
    cur_epoch: jax.Array,     # () int32
    edges: jax.Array,         # (HIST_BINS + 1,) int32
    *, seg: int = DEFAULT_SEG, width: int = 1 << 14,
    interpret: bool | None = None,
) -> jax.Array:
    grid = width // seg
    assert grid * seg == width
    lo_hi = jnp.stack([edges[:-1], edges[1:]])               # (2, HIST_BINS)
    meta = cur_epoch.astype(jnp.int32).reshape(1, 1)
    kern = functools.partial(_hist_kernel, seg=seg)
    out = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, seg), lambda k: (0, k)),
            pl.BlockSpec((1, seg), lambda k: (0, k)),
            pl.BlockSpec((1, 1), lambda k: (0, 0)),
            pl.BlockSpec((2, HIST_BINS), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, HIST_BINS), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, HIST_BINS), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(counts_row0.reshape(1, -1), epochs_row0.reshape(1, -1), meta, lo_hi)
    return out[0]
