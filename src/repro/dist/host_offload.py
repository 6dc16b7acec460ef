"""Fast/slow memory-tier placement by JAX ``memory_kind`` — NeoMem's tiers.

Device HBM is the fast tier (DRAM in the paper), pinned host memory the
slow tier (CXL-attached memory).  ``to_slow_tier`` / ``to_fast_tier`` move
an array between them with an explicit ``device_put``, the software
equivalent of a page migration.  Every backend this repo runs on exposes
``pinned_host`` (TPU always; CPU since JAX 0.9), so the slow tier is real
host memory everywhere; a device without it is an error, never a silent
device-memory stand-in.

Inside a jit, a pinned-host store cannot be indexed with device indices.
:func:`host_take` / :func:`host_put` are the two in-jit verbs over such a
store.  Reads move row by row by DMA (dynamic slices of host memory), and
so do writes of rows with two or more dims; narrower rows are scattered by
a host computation (``compute_on("device_host")``) fed with the index
batch.  Either way only the named rows cross the host/device boundary —
never the store itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.compute_on import compute_on
from jax.memory import Space
from jax.sharding import NamedSharding

SLOW_KIND = "pinned_host"


def memory_kinds(device=None) -> tuple[str, ...]:
    """The memory kinds ``device`` (default: the first device) exposes."""
    dev = jax.devices()[0] if device is None else device
    return tuple(sorted({m.kind for m in dev.addressable_memories()}))


def supports_memory_kinds(device=None) -> bool:
    """True when the device exposes a distinct host tier to offload into."""
    kinds = memory_kinds(device)
    return SLOW_KIND in kinds and len(kinds) > 1


def to_slow_tier(x, mesh, spec):
    """Demote: place x in the slow tier (pinned host) under ``spec``."""
    dev = mesh.devices.flat[0]
    if not supports_memory_kinds(dev):
        raise RuntimeError(
            f"{dev.device_kind} exposes memory kinds {memory_kinds(dev)}, "
            f"no {SLOW_KIND!r} slow tier")
    return jax.device_put(x, NamedSharding(mesh, spec, memory_kind=SLOW_KIND))


def to_fast_tier(x, mesh, spec):
    """Promote: place x back in the fast tier (device memory)."""
    kind = mesh.devices.flat[0].default_memory().kind
    return jax.device_put(x, NamedSharding(mesh, spec, memory_kind=kind))


def on_host(x) -> bool:
    """Whether ``x`` (an array or a tracer inside a jit) lives in host
    memory — decided from its type, so it holds at trace time."""
    return jax.typeof(x).memory_space == Space.Host


def host_outputs(store) -> bool:
    """Whether the jit being traced may return ``store`` in host memory.
    XLA:CPU lowers host computation but has no pass that places a program
    OUTPUT in ``pinned_host``: there a write verb returns its store in
    device memory and :func:`rehost` places it back (a host-to-host copy
    on that backend, where both kinds are the same RAM).  Decided from the
    device kind the store's type names, so a program compiled ahead of
    time for a described TPU takes the TPU form."""
    mesh = jax.typeof(store).sharding.mesh
    kind = (jax.default_backend() if mesh.empty
            else mesh.abstract_device.device_kind)
    return kind != "cpu"


def host_take(store, idx):
    """``store[idx]`` inside a jit, for a store in either tier; ``idx``
    must be in range.

    For a pinned-host store the result — ``idx.shape + store.shape[1:]``
    — is the only thing that crosses to the device: row by row, each an
    async dynamic slice of host memory (a DMA; no host computation, so the
    read is fit for the decode step and the prefill scan)."""
    if not on_host(store):
        return store[idx]
    flat = idx.reshape(-1)
    rows = [jax.device_put(jax.lax.dynamic_index_in_dim(
        store, flat[i], 0, keepdims=False), Space.Device)
        for i in range(flat.shape[0])]
    return jnp.stack(rows).reshape(idx.shape + store.shape[1:])


def host_put(store, idx, rows):
    """``store.at[idx].set(rows, mode="drop")`` inside a jit, for a store
    in either tier (``idx`` 1-D; ids outside ``[0, len(store))``, -1
    included, are dropped lanes).

    For a pinned-host store the store never leaves host memory.  Rows of
    two or more dims cover whole (sublane, lane) tiles and go one by one
    by DMA: a dropped lane repeats the write of the last lane in range
    (the same bytes to the same row), so no written row is read back; a
    batch with no lane in range rewrites row 0 with its own bytes, the one
    row each call reads.  A TPU cannot DMA part of a lane, so narrower
    rows (the int8 codec's 1-D scales) go through a scatter run in host
    memory.  On the CPU backend either form returns the store in device
    memory (see :func:`host_outputs`); :func:`rehost` places it back."""
    if not on_host(store):
        return store.at[idx].set(rows.astype(store.dtype), mode="drop")
    rows = rows.astype(store.dtype)
    n, lanes = store.shape[0], jnp.arange(idx.shape[0])
    ok = (idx >= 0) & (idx < n)
    if store.ndim >= 3:
        last = jnp.max(jnp.where(ok, lanes, -1))
        src = jnp.where(ok, lanes, jnp.maximum(last, 0))
        row0 = jax.device_put(jax.lax.dynamic_index_in_dim(store, 0, 0),
                              Space.Device)
        for i in range(idx.shape[0]):
            row = jnp.where(last >= 0,
                            jax.lax.dynamic_index_in_dim(rows, src[i], 0),
                            row0)
            store = jax.lax.dynamic_update_index_in_dim(
                store, jax.device_put(row, Space.Host),
                jnp.where(last >= 0, idx[src[i]], 0), 0)
        return store
    idx_h = jax.device_put(jnp.where(ok, idx, n), Space.Host)
    rows_h = jax.device_put(rows, Space.Host)
    with compute_on("device_host"):
        out = store.at[idx_h].set(rows_h, mode="drop")
    return jax.device_put(out,
                          Space.Host if host_outputs(store) else Space.Device)


def rehost(x, sharding):
    """Place a write verb's returned store ``x`` back under the input
    store's ``sharding`` (a no-op wherever :func:`host_outputs` holds)."""
    if x is None or x.sharding.memory_kind == sharding.memory_kind:
        return x
    return jax.device_put(x, sharding)
