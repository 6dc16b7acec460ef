"""Mixture-of-Experts: top-k router + experts (dense-dispatch and EP paths).

Two dispatch strategies:
  * ``dense``  — einsum over all experts with a routing-weight mask.  O(E)
    compute but collective-free and fully shardable; the dry-run default for
    correctness and a clean roofline baseline.
  * ``gather`` — token-dropping capacity-based dispatch via one-hot matmuls
    (MXU-friendly), the optimized path used by the hillclimb; pairs with
    expert sharding so XLA emits all-to-alls on the `model` axis.

The router's token->expert stream is ALSO the NeoMem profiling stream: the
adapter (core/adapters/expert_cache.py) snoops `router_topk` outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import DTYPE


def moe_init(key, d, e, f, *, shared_f: int = 0, dtype=DTYPE):
    ks = jax.random.split(key, 7)
    s_in, s_out = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_in": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    if shared_f > 0:
        p["sh_gate"] = (jax.random.normal(ks[4], (d, shared_f)) * s_in).astype(dtype)
        p["sh_in"] = (jax.random.normal(ks[5], (d, shared_f)) * s_in).astype(dtype)
        p["sh_out"] = (jax.random.normal(ks[6], (shared_f, d)) * shared_f ** -0.5).astype(dtype)
    return p


def router_topk(p, x, k: int, *, bias=None):
    """Returns (weights (B,S,k) fp32, indices (B,S,k) int32, probs fp32)."""
    logits = x.astype(jnp.float32) @ p["router"]
    if bias is not None:  # aux-loss-free balancing bias (DeepSeek-V3 style)
        sel_scores = jax.nn.sigmoid(logits) + bias
    else:
        sel_scores = logits
    w, idx = jax.lax.top_k(sel_scores, k)
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.take_along_axis(jax.nn.sigmoid(logits) if bias is not None
                               else probs, idx, axis=-1)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    return gate, idx.astype(jnp.int32), probs


def moe_apply_dense(p, x, k: int, *, bias=None):
    """Collective-free dispatch: mask-weighted einsum over all experts."""
    e = p["router"].shape[1]
    gate, idx, probs = router_topk(p, x, k, bias=bias)
    # combine weights per expert: (B,S,E)
    comb = jax.nn.one_hot(idx, e, dtype=jnp.float32) * gate[..., None]
    comb = jnp.sum(comb, axis=-2)                         # (B,S,E)

    h_gate = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    h_in = jnp.einsum("bsd,edf->bsef", x, p["w_in"])
    h = jax.nn.silu(h_gate) * h_in
    y = jnp.einsum("bsef,efd->bsed", h, p["w_out"])
    out = jnp.einsum("bsed,bse->bsd", y.astype(jnp.float32), comb).astype(x.dtype)
    out = out + _shared_expert(p, x)
    return out, idx, probs


def moe_apply_gather(p, x, k: int, *, capacity_factor: float = 1.25, bias=None):
    """Capacity-based dispatch via one-hot matmuls (token-dropping)."""
    b, s, d = x.shape
    e = p["router"].shape[1]
    gate, idx, probs = router_topk(p, x, k, bias=bias)
    xt = x.reshape(b * s, d)
    gate_f = gate.reshape(b * s, k)
    idx_f = idx.reshape(b * s, k)
    cap = max(1, int(capacity_factor * b * s * k / e))

    onehot = jax.nn.one_hot(idx_f, e, dtype=jnp.float32)       # (T,k,E)
    pos = jnp.cumsum(onehot, axis=0) - onehot                  # slot within expert
    pos = jnp.sum(pos * onehot, axis=-1)                       # (T,k)
    keep = pos < cap
    disp = onehot * keep[..., None]                            # (T,k,E)
    slot_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)  # (T,k,C)
    # dispatch tensor (T, k, E, C) contracted on the fly:
    xe = jnp.einsum("td,tke,tkc->ecd", xt.astype(jnp.float32), disp, slot_oh)
    xe = xe.astype(x.dtype)                                    # (E,C,D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_out"])             # (E,C,D)
    y = jnp.einsum("ecd,tke,tkc,tk->td", ye.astype(jnp.float32), disp, slot_oh,
                   gate_f)
    out = y.reshape(b, s, d).astype(x.dtype) + _shared_expert(p, x)
    return out, idx, probs


import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class EPContext:
    """Expert-parallel execution context (pjit + shard_map hybrid).

    Experts are sharded over ``expert_axis`` (TP/EP) and their inner dim is
    FSDP-sharded over ``fsdp_axis`` for storage; compute all-gathers the
    layer's expert weights over fsdp_axis (ZeRO-3 style), dispatches local
    tokens to locally-owned experts, and psums partial outputs over
    expert_axis — collective pattern: 1 all-gather (weights, over data) +
    1 all-reduce (activations, over model) per MoE layer.
    """

    mesh: Any
    expert_axis: str = "model"
    fsdp_axis: str | None = "data"
    dp_axes: tuple = ("data",)
    capacity_factor: float = 2.0


def _capacity(t: int, k: int, e: int, cf: float) -> int:
    """Expert capacity.  Small batches (decode / smoke) get exact capacity
    (zero drops — keeps decode/prefill parity); large batches use the
    standard cf * T * k / E dropping capacity."""
    if t * k <= 4096:
        return t * k
    return max(k, int(cf * t * k / e))


def _rank_in_bins(eids: jax.Array, n_bins: int) -> jax.Array:
    """Rank of each element within its bin value (sort-based, O(N log N))."""
    n = eids.shape[0]
    order = jnp.argsort(eids, stable=True)
    sorted_e = eids[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(n_bins + 1))
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - start[sorted_e].astype(jnp.int32)
    inv = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return ranks_sorted[inv]


def _ep_local_body(x, router_w, bias, wg, wi, wo, *, k, e_total, cap,
                   expert_axis=None, fsdp_axis=None):
    """Per-device EP compute.  x: (B,S,D); wg/wi/wo: local expert shards."""
    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wi = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, fsdp_axis, axis=1, tiled=True)
    e_loc = wg.shape[0]
    midx = jax.lax.axis_index(expert_axis) if expert_axis else 0

    b, s, d = x.shape
    logits = x.astype(jnp.float32).reshape(b * s, d) @ router_w
    if bias is not None:
        sel = jax.nn.sigmoid(logits) + bias
        gate_src = jax.nn.sigmoid(logits)
    else:
        sel = logits
        gate_src = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(sel, k)                       # (T, k)
    gate = jnp.take_along_axis(gate_src, idx, axis=-1)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    t = b * s
    eid = idx.reshape(t * k).astype(jnp.int32)
    lid = eid - midx * e_loc
    mine = (lid >= 0) & (lid < e_loc)
    rank = _rank_in_bins(jnp.where(mine, lid, e_loc), e_loc)
    keep = mine & (rank < cap)
    se = jnp.where(keep, lid, 0)
    sc = jnp.where(keep, rank, 0)

    tok = jnp.arange(t * k, dtype=jnp.int32) // k
    x_flat = x.reshape(t, d)
    contrib = jnp.where(keep[:, None], x_flat[tok], 0).astype(x.dtype)
    xe = jnp.zeros((e_loc, cap, d), x.dtype).at[se, sc].add(contrib)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) \
        * jnp.einsum("ecd,edf->ecf", xe, wi)
    ye = jnp.einsum("ecf,efd->ecd", h, wo)               # (E_loc, C, D)

    y_asn = ye[se, sc].astype(jnp.float32) \
        * (keep[:, None] * gate.reshape(t * k)[:, None])
    y = jnp.sum(y_asn.reshape(t, k, d), axis=1)
    if expert_axis:
        y = jax.lax.psum(y, expert_axis)
    return y.reshape(b, s, d).astype(x.dtype), idx.reshape(b, s, k)


def _ep_resident_body(x, router_w, bias, res_map, wg, wi, wo,
                      fw_g, fw_i, fw_o, fetch_ids, *, k, e_total, cap,
                      expert_axis=None):
    """NeoMem-tiered serving dispatch (§Perf cell A).

    Only the HOT experts are HBM-resident (``wg/wi/wo``: (E_hot_loc, D, F)
    per model shard — the fast tier, populated by the expert-cache daemon);
    ``fw_*`` is the per-interval cold-fetch buffer (n_fetch experts DMA'd
    from host under the migration quota).  Tokens routed to non-resident,
    non-fetched experts take only the shared-expert path (counted as slow
    misses by the profiler).  No per-token weight collectives remain — the
    only collective is the output psum.
    """
    e_hot_loc = wg.shape[0]
    n_fetch = fw_g.shape[0]   # LOCAL fetch slots (buffer sharded over EP)
    midx = jax.lax.axis_index(expert_axis) if expert_axis else 0

    b, s, d = x.shape
    logits = x.astype(jnp.float32).reshape(b * s, d) @ router_w
    sel = jax.nn.sigmoid(logits) + (bias if bias is not None else 0.0)
    gate_src = jax.nn.sigmoid(logits) if bias is not None \
        else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(sel, k)
    gate = jnp.take_along_axis(gate_src, idx, axis=-1)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    t = b * s
    eid = idx.reshape(t * k).astype(jnp.int32)
    slot = res_map[eid]                                  # global hot slot | -1
    mine_hot = (slot >= 0) & (slot // e_hot_loc == midx)
    lslot = slot - midx * e_hot_loc
    # cold-fetched experts: each shard DMA'd its own fetch slots, so a
    # fetched token is handled by whichever shard holds the expert
    fmatch = eid[:, None] == fetch_ids[None, :]          # (T*k, n_fetch_loc)
    fslot = jnp.argmax(fmatch, axis=1)
    is_fetched = jnp.any(fmatch, axis=1) & (slot < 0)

    e_loc = e_hot_loc + n_fetch
    lid = jnp.where(mine_hot, lslot,
                    jnp.where(is_fetched, e_hot_loc + fslot, e_loc))
    keep_pre = mine_hot | is_fetched
    rank = _rank_in_bins(jnp.where(keep_pre, lid, e_loc), e_loc)
    keep = keep_pre & (rank < cap)
    se = jnp.where(keep, lid, 0)
    sc = jnp.where(keep, rank, 0)

    tok = jnp.arange(t * k, dtype=jnp.int32) // k
    x_flat = x.reshape(t, d)
    contrib = jnp.where(keep[:, None], x_flat[tok], 0).astype(x.dtype)
    xe = jnp.zeros((e_loc, cap, d), x.dtype).at[se, sc].add(contrib)

    wg_all = jnp.concatenate([wg, fw_g], axis=0)
    wi_all = jnp.concatenate([wi, fw_i], axis=0)
    wo_all = jnp.concatenate([wo, fw_o], axis=0)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg_all)) \
        * jnp.einsum("ecd,edf->ecf", xe, wi_all)
    ye = jnp.einsum("ecf,efd->ecd", h, wo_all)

    y_asn = ye[se, sc].astype(jnp.float32) \
        * (keep[:, None] * gate.reshape(t * k)[:, None])
    y = jnp.sum(y_asn.reshape(t, k, d), axis=1)
    if expert_axis:
        y = jax.lax.psum(y, expert_axis)
    return y.reshape(b, s, d).astype(x.dtype), idx.reshape(b, s, k)


def moe_apply_ep(p, x, k: int, *, bias=None, ep_axes: EPContext | None = None):
    """Expert-parallel MoE layer; single-device fallback when ep_axes=None.

    Returns (y, idx, probs=None).  The token->expert ``idx`` stream is the
    NeoMem profiling stream.
    """
    from jax.sharding import PartitionSpec as P

    e = p["router"].shape[1]

    if "residency" in p:   # NeoMem-tiered serving path (hot experts resident)
        b, s, d = x.shape
        # resident path: dispatch buffers sized to expected load (x8 head-
        # room), NOT to the no-drop bound — with E_hot+fetch local experts a
        # t*k capacity would pad the expert matmuls ~10x (measured in §Perf).
        cap = min(b * s * k, max(64, int(8.0 * b * s * k / e)))
        args = (x, p["router"], bias, p["residency"],
                p["w_gate"], p["w_in"], p["w_out"],
                p["fetch_gate"], p["fetch_in"], p["fetch_out"], p["fetch_ids"])
        if ep_axes is None:
            y, idx = _ep_resident_body(*args, k=k, e_total=e, cap=cap)
        else:
            ep = ep_axes
            body = functools.partial(_ep_resident_body, k=k, e_total=e,
                                     cap=cap, expert_axis=ep.expert_axis)
            rep3 = P(None, None, None)
            wspec = P(ep.expert_axis, None, None)
            # fetch buffers + ids are sharded over the EP axis too: each
            # shard DMA's its own cold experts under the migration quota
            y, idx = jax.shard_map(
                body, mesh=ep.mesh,
                in_specs=(rep3, P(None, None),
                          P(None) if bias is not None else None, P(None),
                          wspec, wspec, wspec, wspec, wspec, wspec,
                          P(ep.expert_axis)),
                out_specs=(rep3, rep3),
                check_vma=False,
            )(*args)
        return y + _shared_expert(p, x), idx, None

    if ep_axes is None:
        b, s, d = x.shape
        cap = _capacity(b * s, k, e, 2.0)
        y, idx = _ep_local_body(
            x, p["router"], bias, p["w_gate"], p["w_in"], p["w_out"],
            k=k, e_total=e, cap=cap)
    else:
        ep = ep_axes
        b, s, d = x.shape
        import numpy as np
        dp_size = int(np.prod([ep.mesh.shape[ax] for ax in ep.dp_axes])) \
            if ep.dp_axes else 1
        # decode / tiny batches can't be DP-sharded: replicate tokens instead
        dp_axes = ep.dp_axes if (b % max(dp_size, 1) == 0 and b >= dp_size) \
            else ()
        b_loc = b // dp_size if dp_axes else b
        cap = _capacity(b_loc * s, k, e, ep.capacity_factor)
        body = functools.partial(
            _ep_local_body, k=k, e_total=e, cap=cap,
            expert_axis=ep.expert_axis, fsdp_axis=ep.fsdp_axis)
        dp = P(dp_axes, None, None) if dp_axes else P(None, None, None)
        wspec = P(ep.expert_axis, ep.fsdp_axis, None)
        y, idx = jax.shard_map(
            body, mesh=ep.mesh,
            in_specs=(dp, P(None, None), P(None) if bias is not None else None,
                      wspec, wspec, wspec),
            out_specs=(dp, dp),
            check_vma=False,
        )(x, p["router"], bias, p["w_gate"], p["w_in"], p["w_out"])

    y = y + _shared_expert(p, x)
    return y, idx, None


def moe_apply_tiered(p, x, k: int, *, bias=None, tier, group_id):
    """NeoMem EP-resident dispatch: expert weights served from the tiered
    store INSIDE the jitted step (DESIGN.md §10).

    Instead of touching the dense (E, D, F) weight tensors, each selected
    expert's flattened [w_gate | w_in | w_out] payload row is gathered
    through the device-resident placement table
    (:func:`repro.tiering.migrate.lookup_rows`): promoted experts come from
    the HBM fast buffer, cold experts stream from the slow store in the
    same fused gather — the serving analogue of a CXL slow-tier load, a
    miss is only slower, never an error.  ``tier`` is the resource's
    ``{"fast", "slow", "page_slot"}`` view (plus the int8 codec's optional
    ``"scale"`` — cold rows dequantize inside the same fused gather,
    DESIGN.md §14); ``group_id`` the layer-group
    index (page_id = group * n_experts + expert).  Gathered compute is
    per-token (B, S, k) einsums — at decode shapes (S=1, small k) this
    touches k weight blocks per token instead of all E.

    Single-device (replicated) path only: the buffers and placement table
    are unsharded, so an EP-configured engine must NOT route here — EP
    meshes keep `moe_apply_ep`'s shard_map dispatch, whose "residency"
    params are the EP-sharded form of the same tiering (the serve engine
    gates on ``ep_axes`` accordingly).
    """
    from repro.tiering.migrate import lookup_rows

    e = p["router"].shape[1]
    gate, idx, probs = router_topk(p, x, k, bias=bias)
    _, d, f = p["w_gate"].shape
    rows = lookup_rows(tier["fast"], tier["slow"], tier["page_slot"],
                       group_id * e + idx,
                       scale=tier.get("scale"))         # (B, S, k, 3*d*f)
    rows = rows.astype(p["w_gate"].dtype)
    wg = rows[..., : d * f].reshape(idx.shape + (d, f))
    wi = rows[..., d * f: 2 * d * f].reshape(idx.shape + (d, f))
    wo = rows[..., 2 * d * f:].reshape(idx.shape + (f, d))
    h = jax.nn.silu(jnp.einsum("bsd,bskdf->bskf", x, wg)) \
        * jnp.einsum("bsd,bskdf->bskf", x, wi)
    y = jnp.einsum("bskf,bskfd->bskd", h, wo)
    out = jnp.einsum("bskd,bsk->bsd", y.astype(jnp.float32),
                     gate).astype(x.dtype)
    return out + _shared_expert(p, x), idx, probs


def _shared_expert(p, x):
    if "sh_in" not in p:
        return jnp.zeros_like(x)
    h = jax.nn.silu(x @ p["sh_gate"]) * (x @ p["sh_in"])
    return h @ p["sh_out"]


def aux_load_balance_loss(probs, idx, e: int, k: int) -> jax.Array:
    """Switch-style load-balancing loss (used when bias-free balancing off)."""
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    onehot = jax.nn.one_hot(idx.reshape(-1, k), e, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / k
    return e * jnp.sum(me * ce)
