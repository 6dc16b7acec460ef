"""NeoProf sketch-update Pallas TPU kernel (paper Fig. 7/8, TPU-native).

Hardware adaptation (DESIGN.md §2): the ASIC pipeline's per-address
scatter-increment has no efficient TPU analogue (VMEM scatter serializes on
the VPU), so the update is re-expressed as a *segment-tiled one-hot
compare-reduce*: the sketch row is tiled into lane-aligned segments (the
grid dimension — the TPU version of the paper's K=128 memory sub-blocks),
and within a (stream-block x segment) cell the counter deltas are a bincount
computed as a reduction over the S x Wseg one-hot matrix — MXU/VPU-friendly
dense work instead of serialized scatter.

Two passes over the segment grid:
  pass A (update):  counts += bincount(h(p)); emits per-element post-update
                    counter reads (est) and pre-update hot-bit reads,
                    accumulated across segments (each element lands in
                    exactly one segment per lane).
  pass B (mark):    after the host of the kernel (ops.py) reduces est ->
                    is_hot, scatter the hot bits with the same one-hot trick.

H3 hashing (paper Eq. 5) is an unrolled 30-step XOR-select over the page-id
bits — pure VPU bit logic, identical to the hardware reduction tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketch import PAGE_ID_BITS
from repro.kernels.backend import resolve_interpret

DEFAULT_SEG = 512  # lanes per sketch segment (multiple of 128)


def _h3_all_lanes(page_ids: jax.Array, seeds: jax.Array, depth: int) -> jax.Array:
    """(S,) ids + (D, PAGE_ID_BITS) seeds -> (D, S) hashed indices."""
    h = jnp.zeros((depth, page_ids.shape[0]), jnp.int32)
    for bit in range(PAGE_ID_BITS):
        mask = ((page_ids >> bit) & 1) > 0          # (S,)
        h = jnp.where(mask[None, :], h ^ seeds[:, bit][:, None], h)
    return h


def _update_kernel(
    # scalar-prefetch style inputs arrive as plain refs (all in VMEM)
    ids_ref,      # (1, S) int32 page ids (-1 pad)
    seeds_ref,    # (D, PAGE_ID_BITS) int32
    meta_ref,     # (1, 4) int32: [cur_epoch, counter_max, valid(unused), S]
    counts_ref,   # (D, Wseg) int32   — block of the sketch segment
    epochs_ref,   # (D, Wseg) int32
    hot_ref,      # (D, Wseg) int32
    out_counts,   # (D, Wseg) int32
    out_epochs,   # (D, Wseg) int32
    est_ref,      # (D, S) int32      — accumulated across segments
    hotbefore_ref,  # (D, S) int32
    *, seg: int, depth: int,
):
    k = pl.program_id(0)
    ids = ids_ref[0, :]                              # (S,)
    valid = (ids >= 0)
    h = _h3_all_lanes(jnp.where(valid, ids, 0), seeds_ref[...], depth)  # (D,S)

    cur_epoch = meta_ref[0, 0]
    cmax = meta_ref[0, 1]

    local = h - k * seg                               # (D, S)
    in_seg = (local >= 0) & (local < seg) & valid[None, :]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (depth, ids.shape[0], seg), 2)
    onehot = (local[:, :, None] == lanes) & in_seg[:, :, None]   # (D,S,Wseg)
    onehot_i = onehot.astype(jnp.int32)

    delta = jnp.sum(onehot_i, axis=1)                 # (D, Wseg) bincount
    live = jnp.where(epochs_ref[...] == cur_epoch, counts_ref[...], 0)
    new_counts = jnp.minimum(live + delta, cmax)
    out_counts[...] = new_counts
    out_epochs[...] = jnp.full_like(epochs_ref[...], cur_epoch)

    # per-element post-update counter read + pre-update hot-bit read,
    # via the same one-hot matrix (each element is in exactly one segment)
    est_seg = jnp.sum(onehot_i * new_counts[:, None, :], axis=2)      # (D,S)
    hot_seg = jnp.sum(onehot_i * hot_ref[...][:, None, :], axis=2)    # (D,S)

    @pl.when(k == 0)
    def _init():
        est_ref[...] = jnp.zeros_like(est_ref)
        hotbefore_ref[...] = jnp.zeros_like(hotbefore_ref)

    est_ref[...] += est_seg
    hotbefore_ref[...] += hot_seg


def _mark_kernel(
    ids_ref, seeds_ref, ishot_ref,
    hot_ref, out_hot,
    *, seg: int, depth: int,
):
    k = pl.program_id(0)
    ids = ids_ref[0, :]
    valid = ids >= 0
    h = _h3_all_lanes(jnp.where(valid, ids, 0), seeds_ref[...], depth)
    local = h - k * seg
    is_hot = (ishot_ref[0, :] > 0) & valid
    in_seg = (local >= 0) & (local < seg) & is_hot[None, :]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (depth, ids.shape[0], seg), 2)
    onehot = (local[:, :, None] == lanes) & in_seg[:, :, None]
    mark = jnp.max(onehot.astype(jnp.int32), axis=1)          # (D, Wseg)
    out_hot[...] = jnp.maximum(hot_ref[...], mark)


@functools.partial(
    jax.jit, static_argnames=("seg", "depth", "width", "interpret"))
def sketch_update_pallas(
    counts: jax.Array,   # (D, W) int32
    epochs: jax.Array,   # (D, W) int32
    hot: jax.Array,      # (D, W) int32
    page_ids: jax.Array,  # (S,) int32
    seeds: jax.Array,    # (D, PAGE_ID_BITS) int32
    cur_epoch: jax.Array,  # () int32
    counter_max: int,
    *, seg: int = DEFAULT_SEG, depth: int = 2, width: int = 1 << 14,
    interpret: bool | None = None,
):
    """Pass A: returns (new_counts, new_epochs, est (D,S), hot_before (D,S))."""
    s = page_ids.shape[0]
    grid = width // seg
    assert grid * seg == width, "width must be a multiple of seg"
    meta = jnp.stack([
        cur_epoch.astype(jnp.int32), jnp.int32(counter_max),
        jnp.int32(0), jnp.int32(s)]).reshape(1, 4)
    kern = functools.partial(_update_kernel, seg=seg, depth=depth)
    return pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, s), lambda k: (0, 0)),                 # ids
            pl.BlockSpec((depth, PAGE_ID_BITS), lambda k: (0, 0)),  # seeds
            pl.BlockSpec((1, 4), lambda k: (0, 0)),                 # meta
            pl.BlockSpec((depth, seg), lambda k: (0, k)),           # counts
            pl.BlockSpec((depth, seg), lambda k: (0, k)),           # epochs
            pl.BlockSpec((depth, seg), lambda k: (0, k)),           # hot
        ],
        out_specs=[
            pl.BlockSpec((depth, seg), lambda k: (0, k)),           # counts'
            pl.BlockSpec((depth, seg), lambda k: (0, k)),           # epochs'
            pl.BlockSpec((depth, s), lambda k: (0, 0)),             # est
            pl.BlockSpec((depth, s), lambda k: (0, 0)),             # hot_before
        ],
        out_shape=[
            jax.ShapeDtypeStruct((depth, width), jnp.int32),
            jax.ShapeDtypeStruct((depth, width), jnp.int32),
            jax.ShapeDtypeStruct((depth, s), jnp.int32),
            jax.ShapeDtypeStruct((depth, s), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(page_ids.reshape(1, -1), seeds, meta, counts, epochs, hot)


@functools.partial(
    jax.jit, static_argnames=("seg", "depth", "width", "interpret"))
def sketch_mark_hot_pallas(
    hot: jax.Array,       # (D, W) int32
    page_ids: jax.Array,  # (S,) int32
    is_hot: jax.Array,    # (S,) int32/bool
    seeds: jax.Array,
    *, seg: int = DEFAULT_SEG, depth: int = 2, width: int = 1 << 14,
    interpret: bool | None = None,
):
    """Pass B: OR the hot bits of every detected-hot element's entries."""
    s = page_ids.shape[0]
    grid = width // seg
    kern = functools.partial(_mark_kernel, seg=seg, depth=depth)
    return pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, s), lambda k: (0, 0)),
            pl.BlockSpec((depth, PAGE_ID_BITS), lambda k: (0, 0)),
            pl.BlockSpec((1, s), lambda k: (0, 0)),
            pl.BlockSpec((depth, seg), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((depth, seg), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((depth, width), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(page_ids.reshape(1, -1), seeds, is_hot.astype(jnp.int32).reshape(1, -1), hot)
