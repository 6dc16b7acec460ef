"""NeoProf — the device-side profiler (paper §IV), as a JAX pytree module.

Composition (paper Fig. 6): Page Monitor (snoops the access stream — here,
the index streams the model itself computes), NeoProf Core (CM-sketch hot
page detector + hot-page buffer + histogram unit), State Monitor (bandwidth /
read-write accounting).  The host-facing command set of Table I is preserved
verbatim in :class:`NeoProfCommands` so the software stack above mirrors the
paper's driver/daemon split.

All update paths are jit-able and run *inside* the training/serving step —
the TPU analogue of device-side offload: profiling consumes no host cycles
and no extra HBM round-trips beyond the sketch working set.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import sketch as sk
from repro.core.sketch import SketchParams, SketchState
from repro.spans import pull


class NeoProfParams(NamedTuple):
    sketch: SketchParams = SketchParams()
    hot_buffer_entries: int = 1 << 12   # paper: 16K
    delta: float = 0.25                 # error-bound confidence (paper ex.)

    # Pallas acceleration for the sketch update (interpret-mode on CPU).
    use_kernel: bool = False


class StateMonitor(NamedTuple):
    """Read/Write/bandwidth accounting (paper GetNrSample/GetRdCnt/GetWrCnt).

    'Cycles' are modeled as bytes-on-the-wire normalized by tier bandwidth;
    the OS-side policy only ever consumes the *ratio* B = (rd+wr)/total, so
    any consistent unit works (the paper makes the same approximation).
    """

    rd_bytes: jax.Array   # () float32 — slow-tier bytes read this period
    wr_bytes: jax.Array   # () float32 — slow-tier bytes written this period
    total_budget: jax.Array  # () float32 — bytes the tier could have moved

    @staticmethod
    def init() -> "StateMonitor":
        z = jnp.zeros((), jnp.float32)
        return StateMonitor(z, z, jnp.ones((), jnp.float32))


class NeoProfState(NamedTuple):
    sketch: SketchState
    monitor: StateMonitor
    hot_buf: jax.Array     # (hot_buffer_entries,) int32 page ids, -1 = empty
    hot_count: jax.Array   # () int32 valid entries in hot_buf
    dropped: jax.Array     # () int32 hot pages dropped on buffer overflow
    theta: jax.Array       # () int32 current hotness threshold


def neoprof_init(params: NeoProfParams, key: jax.Array | None = None) -> NeoProfState:
    return NeoProfState(
        sketch=sk.sketch_init(params.sketch, key),
        monitor=StateMonitor.init(),
        hot_buf=jnp.full((params.hot_buffer_entries,), -1, jnp.int32),
        hot_count=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        theta=jnp.ones((), jnp.int32),
    )


def _append_hot(
    hot_buf: jax.Array, hot_count: jax.Array, dropped: jax.Array,
    page_ids: jax.Array, mask: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compact masked page ids into the fixed-capacity hot buffer."""
    cap = hot_buf.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1 + hot_count
    ok = mask & (pos < cap)
    # overflow / non-hot lanes scatter out of bounds and are dropped
    idx = jnp.where(ok, pos, cap)
    hot_buf = hot_buf.at[idx].set(page_ids, mode="drop")
    n_new = jnp.sum(ok, dtype=jnp.int32)
    n_drop = jnp.sum(mask & ~ok, dtype=jnp.int32)
    return hot_buf, hot_count + n_new, dropped + n_drop


@functools.partial(jax.jit, static_argnames=("params",))
def neoprof_observe(
    state: NeoProfState,
    page_ids: jax.Array,
    params: NeoProfParams,
    rd_bytes: jax.Array | float = 0.0,
    wr_bytes: jax.Array | float = 0.0,
    budget_bytes: jax.Array | float = 0.0,
) -> NeoProfState:
    """Feed one block of the access stream (negative ids = padding).

    This is the Page Monitor + NeoProf Core pass: sketch update, hot
    detection, hot filtering, buffer append, and State Monitor accounting.
    """
    if params.use_kernel:
        from repro.kernels.neoprof_update import ops as kops
        new_sketch, newly_hot = kops.sketch_update(
            state.sketch, page_ids.astype(jnp.int32), state.theta, params.sketch
        )
    else:
        new_sketch, newly_hot = sk.sketch_update(
            state.sketch, page_ids.astype(jnp.int32), state.theta, params.sketch
        )
    hot_buf, hot_count, dropped = _append_hot(
        state.hot_buf, state.hot_count, state.dropped,
        jnp.where(page_ids >= 0, page_ids, 0).astype(jnp.int32), newly_hot,
    )
    mon = state.monitor
    mon = StateMonitor(
        rd_bytes=mon.rd_bytes + jnp.asarray(rd_bytes, jnp.float32),
        wr_bytes=mon.wr_bytes + jnp.asarray(wr_bytes, jnp.float32),
        total_budget=mon.total_budget + jnp.asarray(budget_bytes, jnp.float32),
    )
    return state._replace(
        sketch=new_sketch, monitor=mon,
        hot_buf=hot_buf, hot_count=hot_count, dropped=dropped,
    )


class NeoProfCommands:
    """The MMIO command set of paper Table I, as a host-side façade.

    Each verb is a cheap jitted read/write against the device-resident
    state — the analogue of a single MMIO transaction.
    """

    def __init__(self, params: NeoProfParams):
        self.params = params

    # -- control -----------------------------------------------------------
    def reset(self, state: NeoProfState) -> NeoProfState:          # 0x100
        return state._replace(
            sketch=sk.sketch_clear(state.sketch),
            monitor=StateMonitor.init(),
            hot_buf=jnp.full_like(state.hot_buf, -1),
            hot_count=jnp.zeros((), jnp.int32),
            dropped=jnp.zeros((), jnp.int32),
        )

    def set_threshold(self, state: NeoProfState, theta) -> NeoProfState:  # 0x200
        return state._replace(theta=jnp.asarray(theta, jnp.int32))

    # -- hot pages ----------------------------------------------------------
    def get_nr_hotpage(self, state: NeoProfState) -> int:          # 0x300
        return int(state.hot_count)

    def get_hotpages(self, state: NeoProfState) -> jnp.ndarray:    # 0x400 (seq.)
        n = int(pull(state.hot_count, "hot_pages"))
        return pull(state.hot_buf, "hot_pages")[:n]

    def drain_hotpages(self, state: NeoProfState) -> tuple[NeoProfState, jnp.ndarray]:
        pages = self.get_hotpages(state)
        return state._replace(
            hot_buf=jnp.full_like(state.hot_buf, -1),
            hot_count=jnp.zeros((), jnp.int32),
        ), pages

    # -- state monitor ------------------------------------------------------
    def get_nr_sample(self, state: NeoProfState) -> float:         # 0x500
        return float(state.monitor.total_budget)

    def get_rd_cnt(self, state: NeoProfState) -> float:            # 0x600
        return float(state.monitor.rd_bytes)

    def get_wr_cnt(self, state: NeoProfState) -> float:            # 0x700
        return float(state.monitor.wr_bytes)

    def bandwidth_util(self, state: NeoProfState) -> float:
        m = state.monitor
        return float(pull((m.rd_bytes + m.wr_bytes)
                          / jnp.maximum(m.total_budget, 1.0), "monitor"))

    # -- histogram unit ------------------------------------------------------
    def get_hist(self, state: NeoProfState) -> jnp.ndarray:        # 0x800-0xA00
        return pull(sk.sketch_histogram(state.sketch, self.params.sketch),
                    "hist")

    def get_error_bound(self, state: NeoProfState, hist=None) -> int:
        h = self.get_hist(state) if hist is None else hist
        return int(pull(sk.error_bound_from_hist(h, self.params.sketch,
                                                 self.params.delta), "hist"))
