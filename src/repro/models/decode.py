"""Serving paths: prefill, full-cache decode, NeoMem paged long-context decode.

Cache layouts (stacked by pattern group so decode scans over groups):
  * attn blocks ......... {"k","v"}: (G, B, Smax, Hkv, dh)
  * MLA blocks .......... {"c_kv","k_rope"}: (G, B, Smax, kv_lora / d_rope)
  * mamba blocks ........ {"ssm","conv"} O(1) state
  * m/sLSTM blocks ...... {"c","n","m"} O(1) state
  * paged attn blocks ... {"k_pages","v_pages"}: (G, B, n_slots, T, Hkv, dh)
                          + {"page_len": (G, B, n_slots), "page_id": ...}

The paged cache IS the NeoMem fast tier: n_slots hot page slots per layer
group; the slow tier (full history) lives host-side and is managed by the
kv_tier adapter + daemon between steps.  The newest page is appended
in-step; page promotion/demotion happens at migration intervals.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import mamba2 as m2
from repro.models import moe as moe_lib
from repro.models import xlstm as xl
from repro.models.layers import apply_norm, embed_apply, logits_apply, mlp_apply
from repro.kernels.paged_attn import ops as pa_ops
from repro.tiering.migrate import lookup_rows as _tier_lookup_rows


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _pos_col(pos: jax.Array, b: int) -> jax.Array:
    """Decode positions as a (B, 1) column: ``pos`` is the scalar lockstep
    counter (single-request serving) or a (B,) vector of per-lane positions
    (continuous batching — each lane advances independently, DESIGN.md §9)."""
    pos = jnp.asarray(pos)
    return jnp.broadcast_to(pos.reshape(-1, 1) if pos.ndim else pos, (b, 1))


def _embed_tokens(params, token, tiered):
    """Token embedding, served from the NeoMem tiered store when bound.

    With a ``tiered["embeddings"]`` view ({"fast", "slow", "page_slot",
    "rows_per_page"}), the row is gathered THROUGH the device-resident
    placement table inside the caller's jit (DESIGN.md §10): fast-buffer
    copy when the vocab row-block is promoted, slow-store fallback
    otherwise — bit-exact either way (tiers are inclusive), so the tiered
    read is a drop-in for the dense table gather."""
    tv = (tiered or {}).get("embeddings")
    if tv is None:
        return embed_apply(params["embed"], token)
    rpp = tv["rows_per_page"]
    rows = _tier_lookup_rows(tv["fast"], tv["slow"], tv["page_slot"],
                             token // rpp,
                             scale=tv.get("scale"))  # (B, 1, rpp, d)
    r = (token % rpp)[..., None, None]
    return jnp.take_along_axis(rows, r, axis=-2)[..., 0, :]


def _attn_cache(cfg, batch, smax, dtype):
    if cfg.mla is not None:
        return attn.mla_init_cache(batch, smax, cfg.mla.kv_lora, cfg.mla.d_rope, dtype)
    return attn.gqa_init_cache(batch, smax, cfg.n_kv_heads, cfg.head_dim, dtype)


def _block_cache(cfg: ArchConfig, kind: str, batch: int, smax: int, dtype):
    if kind == "mamba":
        s = cfg.ssm
        p_fake = {"out_proj": jnp.zeros((s.expand * cfg.d_model, cfg.d_model)),
                  "conv_w": jnp.zeros((s.d_conv, 1))}
        return m2.mamba2_init_cache(batch, p_fake, headdim=s.headdim,
                                    n_groups=s.n_groups, d_state=s.d_state)
    if kind == "mlstm":
        return xl.mlstm_init_cache(batch, cfg.d_model, cfg.mlstm_heads)
    if kind == "slstm":
        return xl.slstm_init_cache(batch, cfg.d_model)
    return _attn_cache(cfg, batch, smax, dtype)


def init_cache(cfg: ArchConfig, batch: int, smax: int, dtype=jnp.bfloat16):
    """Full (dense) KV cache pytree, group-stacked."""
    def one_group(_):
        return [_block_cache(cfg, kind, batch, smax, dtype) for kind in cfg.pattern]
    g = cfg.n_groups
    caches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (g,) + x.shape), one_group(0))
    out = {"blocks": caches, "pos": jnp.zeros((), jnp.int32)}
    if cfg.moe and cfg.moe.n_dense_prologue:
        out["prologue"] = [
            _block_cache(cfg, "attn", batch, smax, dtype)
            for _ in range(cfg.moe.n_dense_prologue)
        ]
    return out


def init_paged_cache(cfg: ArchConfig, batch: int, n_slots: int, page_t: int,
                     dtype=jnp.bfloat16, per_lane_pos: bool = False):
    """NeoMem fast-tier paged cache for attention blocks; O(1) SSM states.

    ``per_lane_pos=True`` makes ``pos`` a (batch,) vector so each batch row
    (a continuous-batching lane) advances independently — required by the
    request scheduler, which resets/preempts lanes mid-flight (DESIGN.md §9).
    """
    def one(kind):
        if kind in ("mamba", "mlstm", "slstm"):
            return _block_cache(cfg, kind, batch, 0, dtype)
        if cfg.mla is not None:
            dk = cfg.mla.kv_lora + cfg.mla.d_rope
            dv = cfg.mla.kv_lora
            hkv = 1
        else:
            dk = dv = cfg.head_dim
            hkv = cfg.n_kv_heads
        return {
            "k_pages": jnp.zeros((batch, n_slots, page_t, hkv, dk), dtype),
            "v_pages": jnp.zeros((batch, n_slots, page_t, hkv, dv), dtype),
            "page_len": jnp.zeros((batch, n_slots), jnp.int32),
            "cur_slot": jnp.zeros((batch,), jnp.int32),
        }
    g = cfg.n_groups
    caches = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (g,) + x.shape),
        [one(kind) for kind in cfg.pattern])
    pos = jnp.zeros((batch,) if per_lane_pos else (), jnp.int32)
    out = {"blocks": caches, "pos": pos}
    if cfg.moe and cfg.moe.n_dense_prologue:
        out["prologue"] = [one("attn") for _ in range(cfg.moe.n_dense_prologue)]
    return out


# ---------------------------------------------------------------------------
# prefill (full sequence -> cache)  — reuses the training forward for hidden
# states, then projects K/V per layer.  For dry-run purposes we lower a
# dedicated prefill that computes logits for the last token + the full cache.
# ---------------------------------------------------------------------------

def prefill(cfg: ArchConfig, params, tokens, *, aux_embeds=None, remat=True,
            ep_axes=None):
    """Returns (last-token logits, forward aux) — the dry-run lowering path.

    Uses the training forward for the full-sequence pass; it does NOT build
    a decode cache (the serve engine uses :func:`prefill_dense` /
    :func:`prefill_paged`, which fill the cache in the same pass).
    """
    from repro.models.transformer import forward
    x, aux = forward(cfg, params, tokens, aux_embeds=aux_embeds, remat=remat,
                     ep_axes=ep_axes)
    logits = logits_apply(params["embed"], x[:, -1:], cfg.final_softcap)
    # NOTE: the dry-run prefill cost is dominated by forward(); cache
    # materialization is modeled by re-projecting K/V in the serve adapter.
    return logits, aux


def merge_cache(old, new, active):
    """Commit a decode-step cache update only for ``active`` lanes.

    ``active`` is a (B,) bool mask over the batch (lane) axis; inactive
    lanes keep their OLD cache leaves — position, ring bookkeeping, page
    payloads and O(1) recurrent states all stay frozen, so a lane can sit
    out an engine step (or a chunked-prefill scan step) without drifting.
    Blocks leaves are group-stacked (G, B, ...); prologue leaves are
    (B, ...); ``pos`` must be the per-lane (B,) vector.
    """
    def mask(o, n, baxis):
        act = active.reshape((1,) * baxis + active.shape
                             + (1,) * (n.ndim - baxis - 1))
        return jnp.where(act, n, o)
    out = {"blocks": jax.tree.map(lambda o, n: mask(o, n, 1),
                                  old["blocks"], new["blocks"])}
    if jnp.ndim(new["pos"]) == 0:
        raise ValueError("merge_cache needs per-lane positions "
                         "(init_paged_cache(per_lane_pos=True))")
    out["pos"] = jnp.where(active, new["pos"], old["pos"])
    if "prologue" in old:
        out["prologue"] = jax.tree.map(lambda o, n: mask(o, n, 0),
                                       old["prologue"], new["prologue"])
    return out


def prefill_dense(cfg: ArchConfig, params, cache, tokens, *, aux_embeds=None,
                  ep_axes=None, tiered=None):
    """Single-pass dense prefill: ONE jitted scan of the decode-step body
    over the prompt, filling the cache and producing the last-token logits
    together (the prompt is never run twice).

    Returns ``(last-token logits (B, V), cache, streams)`` where
    ``streams["router"]`` stacks the per-step (G, n_moe, B, 1, k) expert
    stream on a leading prompt axis (None for dense-FFN archs) — one
    observation batch for the tiering daemon instead of S engine steps.
    """
    def body(cache, tok):
        logits, nc, streams = decode_step(
            cfg, params, cache, tok[:, None], aux_embeds=aux_embeds,
            ep_axes=ep_axes, return_streams=True, tiered=tiered)
        r = streams["router"]
        return nc, (logits[:, -1],
                    r if r is not None else jnp.zeros((0,), jnp.int32))
    cache, (logits_seq, router) = jax.lax.scan(
        body, cache, jnp.moveaxis(jnp.asarray(tokens, jnp.int32), 0, 1))
    return logits_seq[-1], cache, {
        "router": router if router.size else None}


def prefill_paged(cfg: ArchConfig, params, cache, tokens, *, page_t: int,
                  valid=None, active=None, ep_axes=None, smesh=None,
                  tiered=None, collect_mass: bool = False):
    """Chunked prefill through the paged ring: one jitted scan of the
    per-token paged decode body over a (B, C) prompt chunk.

    Each scan step IS :func:`decode_step_paged` on one token column, so the
    ring state after the chunk — page payloads, ``page_len``/``cur_slot``
    bookkeeping, per-lane positions — and the final logits are bit-exact
    with C token-at-a-time streaming calls; what the chunk removes is the
    per-token dispatch, host observation and daemon bookkeeping cost.

    ``valid`` (B, C) bool marks real tokens (False = ragged-tail padding: a
    padded step is a complete no-op for that lane, and the logits carried
    out are the last VALID step's).  ``active`` (B,) bool masks whole lanes
    — inactive lanes' cache leaves never change, so the serve engine can
    chunk-prefill one lane while other lanes' decode state sits untouched
    between their own steps (requires per-lane positions).

    Returns ``(last-valid logits (B, V) f32, cache, streams)``; streams
    stacks the per-step ``router`` / ``kv_mass`` streams on a leading chunk
    axis ((C, G, n_moe, B, 1, k) / (C, G, n_attn, B, S), or None).
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    b, _ = tokens.shape
    lane_act = None if active is None else jnp.asarray(active, bool)
    if valid is None and lane_act is None:
        step_act = None                      # every step fully live: no merge
    else:
        v = jnp.ones(tokens.shape, bool) if valid is None \
            else jnp.asarray(valid, bool)
        step_act = v if lane_act is None else v & lane_act[:, None]

    def body(carry, xs):
        cache, last = carry
        tok, act = xs
        logits, nc, streams = decode_step_paged(
            cfg, params, cache, tok[:, None], page_t=page_t, ep_axes=ep_axes,
            smesh=smesh, return_streams=True, tiered=tiered,
            collect_mass=collect_mass)
        step = logits[:, -1].astype(jnp.float32)
        if act is None:
            nc, last = nc, step
        else:
            nc = merge_cache(cache, nc, act)
            last = jnp.where(act[:, None], step, last)
        r, km = streams["router"], streams["kv_mass"]
        outs = (r if r is not None else jnp.zeros((0,), jnp.int32),
                km if km is not None else jnp.zeros((0,), jnp.float32))
        return (nc, last), outs

    xs = (jnp.moveaxis(tokens, 0, 1),
          None if step_act is None else jnp.moveaxis(step_act, 0, 1))
    last0 = jnp.zeros((b, cfg.vocab), jnp.float32)
    (cache, last), (router, kv_mass) = jax.lax.scan(body, (cache, last0), xs)
    return last, cache, {
        "router": router if router.size else None,
        "kv_mass": kv_mass if kv_mass.size else None,
    }


# ---------------------------------------------------------------------------
# single-token decode over the full cache
# ---------------------------------------------------------------------------

def _moe_block(p, cfg, h2, aux, ep_axes, tiered_moe):
    """The MoE position of a decode block: EP dispatch, or — when the serve
    engine passes the expert tier view for this position — the NeoMem
    EP-resident path: each selected expert's weight block is gathered
    through the device-resident placement table inside the jitted step
    (fast tier when promoted, slow store otherwise; DESIGN.md §10)."""
    if tiered_moe is not None:
        y, idx, _ = moe_lib.moe_apply_tiered(
            p["ffn"], h2, cfg.moe.top_k, bias=p.get("router_bias"),
            tier=tiered_moe["view"], group_id=tiered_moe["group_id"])
    else:
        y, idx, _ = moe_lib.moe_apply_ep(p["ffn"], h2, cfg.moe.top_k,
                                         bias=p.get("router_bias"),
                                         ep_axes=ep_axes)
    aux.setdefault("router_streams", []).append(idx)
    return y


def _decode_attn_block(p, cfg, kind, x_t, cache, pos, aux, ep_axes,
                       tiered_moe=None):
    h = apply_norm(cfg.norm, p["ln1"], x_t)
    window = cfg.window if kind == "attn_local" else 0
    if cfg.mla is not None:
        mla_kw = dataclasses.asdict(cfg.mla)
        o, cache = attn.mla_decode(p["attn"], h, cache, pos, h=cfg.n_heads,
                                   rope_theta=cfg.rope_theta, **mla_kw)
    else:
        o, cache = attn.gqa_decode(p["attn"], h, cache, pos, h=cfg.n_heads,
                                   hkv=cfg.n_kv_heads, dh=cfg.head_dim,
                                   rope_theta=cfg.rope_theta, window=window,
                                   softcap=cfg.attn_softcap, scale=cfg.attn_scale)
    if cfg.post_norm:
        o = apply_norm(cfg.norm, p["pn1"], o)
    x_t = x_t + o
    if kind == "cross" and aux.get("aux_embeds") is not None:
        hx = apply_norm(cfg.norm, p["lnx"], x_t)
        xo = attn.cross_apply(p["xattn"], hx, aux["aux_embeds"], h=cfg.n_heads,
                              hkv=cfg.n_kv_heads, dh=cfg.head_dim)
        x_t = x_t + (jnp.tanh(p["xgate"]) * xo.astype(jnp.float32)).astype(x_t.dtype)
    if kind == "dec" and aux.get("enc_out") is not None:
        hx = apply_norm(cfg.norm, p["lnx"], x_t)
        xo = attn.cross_apply(p["xattn"], hx, aux["enc_out"], h=cfg.n_heads,
                              hkv=cfg.n_kv_heads, dh=cfg.head_dim)
        x_t = x_t + xo
    h2 = apply_norm(cfg.norm, p["ln2"], x_t)
    if kind == "moe":
        y = _moe_block(p, cfg, h2, aux, ep_axes, tiered_moe)
    else:
        y = mlp_apply(p["ffn"], h2, cfg.mlp)
    if cfg.post_norm:
        y = apply_norm(cfg.norm, p["pn2"], y)
    return x_t + y, cache


def _decode_block(p, shared, cfg, kind, x_t, cache, pos, aux, ep_axes,
                  tiered_moe=None):
    if kind == "mamba":
        s = cfg.ssm
        h = apply_norm(cfg.norm, p["ln"], x_t)
        o, cache = m2.mamba2_decode(p["mix"], h, cache, headdim=s.headdim,
                                    n_groups=s.n_groups, d_state=s.d_state)
        return x_t + o, cache
    if kind == "mlstm":
        h = apply_norm(cfg.norm, p["ln"], x_t)
        o, cache = xl.mlstm_decode(p["mix"], h, cache, n_heads=cfg.mlstm_heads)
        return x_t + o, cache
    if kind == "slstm":
        h = apply_norm(cfg.norm, p["ln"], x_t)
        o, cache = xl.slstm_decode(p["mix"], h, cache)
        return x_t + o, cache
    if kind == "shared_attn":
        return _decode_attn_block(shared, cfg, "attn", x_t, cache, pos, aux, ep_axes)
    return _decode_attn_block(p, cfg, kind, x_t, cache, pos, aux, ep_axes,
                              tiered_moe=tiered_moe)


def _tiered_moe_for(cfg: ArchConfig, tiered, i: int, gi):
    """Expert tier view for pattern position ``i`` (group index ``gi``), or
    None.  Only the FIRST MoE position reads through the tiered store — its
    weight blocks are the payload rows the serve engine bound (DESIGN.md
    §8); later MoE positions keep their dense weights."""
    if not tiered or "experts" not in tiered:
        return None
    if "moe" not in cfg.pattern or i != cfg.pattern.index("moe"):
        return None
    return {"view": tiered["experts"], "group_id": gi}


def decode_step(cfg: ArchConfig, params, cache, token, *, aux_embeds=None,
                ep_axes=None, return_streams: bool = False, tiered=None):
    """token: (B,1) int32 -> (logits (B,1,V), new cache).

    For encoder-decoder configs (whisper) ``aux_embeds`` must be the
    PRE-ENCODED encoder output (see transformer.encode) — serving computes it
    once at prefill; re-running the encoder per token would be wasteful.

    With ``return_streams`` the result is (logits, cache, streams) where
    ``streams["router"]`` is the (G, n_moe, B, 1, k) token->expert stream —
    the NeoMem profiling stream for the serve engine's expert resource.

    ``tiered`` binds reads in THIS jitted step to the NeoMem tiered store
    (DESIGN.md §10): ``tiered["embeddings"]`` serves the token embedding
    row through the device-resident placement table, ``tiered["experts"]``
    serves the first MoE position's expert weight blocks the same way —
    no host verb, no per-step round-trip."""
    pos = cache["pos"]
    x = _embed_tokens(params, token, tiered)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
    aux: dict[str, Any] = {"aux_embeds": aux_embeds}
    if cfg.encoder_layers and aux_embeds is not None:
        aux = {"enc_out": aux_embeds, "aux_embeds": None}

    new_pro = []
    for i, lp in enumerate(params.get("prologue", [])):
        x, c = _decode_attn_block(lp, cfg, "attn", x,
                                  cache["prologue"][i], pos, aux, ep_axes)
        new_pro.append(c)

    shared = params.get("shared_attn")

    def group_body(carry, xs):
        x, = carry
        gp, gc, gi = xs
        a_local = {"aux_embeds": aux.get("aux_embeds"),
                   "enc_out": aux.get("enc_out"), "router_streams": []}
        new_gc = []
        for i, kind in enumerate(cfg.pattern):
            x, c = _decode_block(gp[i], shared, cfg, kind, x, gc[i], pos,
                                 a_local, ep_axes,
                                 tiered_moe=_tiered_moe_for(cfg, tiered, i, gi))
            new_gc.append(c)
        streams = a_local["router_streams"]
        out = jnp.stack(streams) if streams else jnp.zeros((0,), jnp.int32)
        return (x,), (new_gc, out)

    g = cfg.n_groups
    (x,), (new_blocks, router) = jax.lax.scan(
        group_body, (x,),
        (params["blocks"], cache["blocks"], jnp.arange(g, dtype=jnp.int32)))
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = logits_apply(params["embed"], x, cfg.final_softcap)
    new_cache = {"blocks": new_blocks, "pos": pos + 1}
    if new_pro:
        new_cache["prologue"] = new_pro
    if return_streams:
        return logits, new_cache, {"router": router if router.size else None}
    return logits, new_cache


# ---------------------------------------------------------------------------
# NeoMem paged decode (long_500k): attention over fast-tier hot pages only
# ---------------------------------------------------------------------------

def _append_attend_local(kp, vp, plen, cur_slot, k_new, v_new, q_eff, *,
                         scale, softcap, page_t, collect_mass):
    """Single-shard page append + flash-decode attention.

    With ``collect_mass`` the kernel additionally exports the (B, n_slots)
    per-page softmax mass — the hotness stream the "kv" tiered resource
    profiles (DESIGN.md §10); otherwise mass is None and the kernel runs
    its plain 3-output form (fill-proxy engines pay nothing extra)."""
    b = q_eff.shape[0]
    bidx = jnp.arange(b)
    off = plen[bidx, cur_slot]
    kp = kp.at[bidx, cur_slot, off].set(k_new.astype(kp.dtype))
    vp = vp.at[bidx, cur_slot, off].set(v_new.astype(vp.dtype))
    plen = plen.at[bidx, cur_slot].add(1)
    full = plen[bidx, cur_slot] >= page_t
    new_slot = jnp.where(full, (cur_slot + 1) % kp.shape[1], cur_slot)
    advanced = full & (new_slot != cur_slot)
    plen = jnp.where(
        advanced[:, None] & (jnp.arange(kp.shape[1])[None] == new_slot[:, None]),
        0, plen)
    if collect_mass:
        o, mass = pa_ops.paged_attention(q_eff, kp, vp, plen, scale=scale,
                                         softcap=softcap, return_mass=True)
    else:
        o, mass = pa_ops.paged_attention(q_eff, kp, vp, plen, scale=scale,
                                         softcap=softcap), None
    return o, kp, vp, plen, new_slot, mass


def _append_attend_sharded(kp, vp, plen, cur_slot, k_new, v_new, q_eff, *,
                           scale, softcap, page_t, smesh, collect_mass):
    """Page slots sharded over ``smesh['axes']``; per-shard kernel + combine.

    Cross-device flash-decoding: each shard attends over its resident hot
    pages and the (m, l, acc) partials are merged with a pmax/psum pair —
    the only per-step collective is O(B x H x dv).  The kernel's per-page
    partials are normalized by the SAME pair, so the (B, n_slots) global
    softmax-mass stream comes back shard-assembled for free."""
    from jax.sharding import PartitionSpec as P
    mesh, axes = smesh["mesh"], smesh["axes"]

    def body(kp, vp, plen, cur_slot, k_new, v_new, q_eff):
        n_local = kp.shape[1]
        rank = jnp.zeros((), jnp.int32)
        for ax in axes:   # linear shard rank over the slot axes
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        lo = rank * n_local
        b = q_eff.shape[0]
        bidx = jnp.arange(b)
        lslot = cur_slot - lo
        own = (lslot >= 0) & (lslot < n_local)
        safe = jnp.clip(lslot, 0, n_local - 1)
        off = plen[bidx, safe]
        sel = own[:, None, None]          # broadcast over (Hkv, d)
        kp = kp.at[bidx, safe, off].set(
            jnp.where(sel, k_new, kp[bidx, safe, off]).astype(kp.dtype))
        vp = vp.at[bidx, safe, off].set(
            jnp.where(sel, v_new, vp[bidx, safe, off]).astype(vp.dtype))
        plen = plen.at[bidx, safe].add(own.astype(jnp.int32))
        # advance decision comes from the owning shard
        full_local = jnp.where(own, plen[bidx, safe] >= page_t, False)
        full = jax.lax.psum(full_local.astype(jnp.int32), axes) > 0
        n_total = n_local * jax.lax.psum(jnp.ones((), jnp.int32), axes)
        new_slot = jnp.where(full, (cur_slot + 1) % n_total, cur_slot)
        # zero the new slot's length wherever it lives
        nls = new_slot - lo
        nown = (nls >= 0) & (nls < n_local) & full & (new_slot != cur_slot)
        plen = plen.at[bidx, jnp.clip(nls, 0, n_local - 1)].set(
            jnp.where(nown, 0, plen[bidx, jnp.clip(nls, 0, n_local - 1)]))
        stats = pa_ops.paged_attention_local_stats(
            q_eff, kp, vp, plen, scale=scale, softcap=softcap,
            return_page_stats=collect_mass)
        if collect_mass:
            m, l, acc, pg_m, pg_l = stats
            o, mass = pa_ops.combine_stats(m, l, acc, axes,
                                           page_m=pg_m, page_l=pg_l)
            return o.astype(q_eff.dtype), kp, vp, plen, new_slot, mass
        o = pa_ops.combine_stats(*stats, axes)
        return o.astype(q_eff.dtype), kp, vp, plen, new_slot

    pagespec = P(None, axes, None, None, None)
    rep = P(*([None] * 3))
    out_specs = (rep, pagespec, pagespec, P(None, axes), P(None))
    if collect_mass:
        out_specs += (P(None, axes),)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pagespec, pagespec, P(None, axes), P(None),
                  rep, rep, rep),
        out_specs=out_specs,
        check_vma=False,
    )(kp, vp, plen, cur_slot, k_new, v_new, q_eff)
    return out if collect_mass else out + (None,)


def _paged_attn_block(p, cfg, kind, x_t, cache, pos, aux, ep_axes, page_t,
                      smesh=None, tiered_moe=None, collect_mass=False):
    h = apply_norm(cfg.norm, p["ln1"], x_t)
    b = x_t.shape[0]
    if cfg.mla is not None:
        m = cfg.mla
        # build latent query: q_eff = [q_nope @ w_k_absorbed, q_rope]
        q = attn._rms(h @ p["attn"]["wq_a"], p["attn"]["q_norm"]) @ p["attn"]["wq_b"]
        q = q.reshape(b, cfg.n_heads, m.d_nope + m.d_rope)
        q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
        pos_b = _pos_col(pos, b)
        q_rope = attn.apply_rope(q_rope[:, None], pos_b, cfg.rope_theta)[:, 0]
        wkv_b = p["attn"]["wkv_b"].reshape(m.kv_lora, cfg.n_heads, m.d_nope + m.d_v)
        w_k = wkv_b[..., :m.d_nope]
        q_lat = jnp.einsum("bhd,khd->bhk", q_nope.astype(jnp.float32),
                           w_k.astype(jnp.float32))
        q_eff = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], -1)
        # new latent kv entry
        kv_a = h[:, 0] @ p["attn"]["wkv_a"]
        c_t = attn._rms(kv_a[..., :m.kv_lora], p["attn"]["kv_norm"])
        kr_t = attn.apply_rope(kv_a[:, None, None, m.kv_lora:], pos_b,
                               cfg.rope_theta)[:, 0, 0]
        k_new = jnp.concatenate([c_t, kr_t], -1)[:, None, :]   # (B,1,dk)
        v_new = c_t[:, None, :]
        scale = (m.d_nope + m.d_rope) ** -0.5
    else:
        q, k, v = attn._proj_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim)
        pos_b = _pos_col(pos, b)
        if cfg.rope_theta > 0:
            q = attn.apply_rope(q, pos_b, cfg.rope_theta)
            k = attn.apply_rope(k, pos_b, cfg.rope_theta)
        q_eff = q[:, 0]                                        # (B,H,dh)
        k_new, v_new = k[:, 0], v[:, 0]                        # (B,Hkv,dh)
        scale = (cfg.head_dim ** -0.5) if cfg.attn_scale is None else cfg.attn_scale

    # append the new K/V into the current page slot, attend over hot pages
    if cfg.mla is not None:
        k_new_p = k_new[:, 0][:, None, :]                      # (B,1,dk) hkv=1
        v_new_p = v_new[:, 0][:, None, :]
    else:
        k_new_p, v_new_p = k_new, v_new                        # (B,Hkv,dh)
    fn = _append_attend_local if smesh is None else functools.partial(
        _append_attend_sharded, smesh=smesh)
    o, kp, vp, plen, new_slot, mass = fn(
        cache["k_pages"], cache["v_pages"], cache["page_len"],
        cache["cur_slot"], k_new_p, v_new_p, q_eff.astype(jnp.float32),
        scale=scale, softcap=cfg.attn_softcap, page_t=page_t,
        collect_mass=collect_mass)                             # o: (B,H,dv)
    if mass is not None:
        # the kernel-true per-page softmax mass (B, n_slots) — the "kv"
        # resource's NeoProf stream (DESIGN.md §10)
        aux.setdefault("kv_mass_streams", []).append(mass)
    if cfg.mla is not None:
        wkv_b = p["attn"]["wkv_b"].reshape(m.kv_lora, cfg.n_heads, m.d_nope + m.d_v)
        w_v = wkv_b[..., m.d_nope:]
        o = jnp.einsum("bhk,khd->bhd", o, w_v.astype(jnp.float32))
        o = o.reshape(b, 1, cfg.n_heads * m.d_v).astype(x_t.dtype) @ p["attn"]["wo"]
    else:
        o = o.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x_t.dtype) \
            @ p["attn"]["wo"]
    if cfg.post_norm:
        o = apply_norm(cfg.norm, p["pn1"], o)
    x_t = x_t + o

    h2 = apply_norm(cfg.norm, p["ln2"], x_t)
    if kind == "moe":
        y = _moe_block(p, cfg, h2, aux, ep_axes, tiered_moe)
    else:
        y = mlp_apply(p["ffn"], h2, cfg.mlp)
    if cfg.post_norm:
        y = apply_norm(cfg.norm, p["pn2"], y)
    new_cache = dict(cache)
    new_cache.update(k_pages=kp, v_pages=vp, page_len=plen, cur_slot=new_slot)
    return x_t + y, new_cache


def decode_step_paged(cfg: ArchConfig, params, cache, token, *, page_t: int,
                      ep_axes=None, smesh=None, return_streams: bool = False,
                      tiered=None, collect_mass: bool | None = None):
    """Long-context decode over the NeoMem fast tier (hot pages only).

    ``cache["pos"]`` may be the scalar lockstep counter or a (B,) vector of
    per-lane positions (continuous batching, see :func:`init_paged_cache`).
    ``smesh``: {"mesh": Mesh, "axes": (...)} shards page slots across devices
    with cross-device flash-decode combining (production path).
    ``tiered`` as in :func:`decode_step` (in-jit embedding/expert reads).

    With ``return_streams`` the streams dict additionally carries
    ``streams["kv_mass"]``: the (G, n_attn, B, n_slots) kernel-exported
    per-page softmax mass of every paged-attention position — the
    hotness-true "kv" profiling stream (DESIGN.md §10), replacing the
    host-computed page-fill proxy.  Works for both the scalar-pos and the
    per-lane-pos (continuous-batching) cache variants.  ``collect_mass``
    (default: follow ``return_streams``) gates the kernel's page-stats
    export, so fill-proxy consumers run the plain 3-output kernel."""
    collect_mass = return_streams if collect_mass is None else collect_mass
    pos = cache["pos"]
    x = _embed_tokens(params, token, tiered)
    if cfg.embed_scale:
        x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
    aux: dict[str, Any] = {}

    new_pro = []
    for i, lp in enumerate(params.get("prologue", [])):
        x, c = _paged_attn_block(lp, cfg, "attn", x, cache["prologue"][i], pos,
                                 aux, ep_axes, page_t, smesh)
        new_pro.append(c)

    shared = params.get("shared_attn")

    def group_body(carry, xs):
        x, = carry
        gp, gc, gi = xs
        a_local: dict[str, Any] = {"router_streams": [],
                                   "kv_mass_streams": []}
        new_gc = []
        for i, kind in enumerate(cfg.pattern):
            tm = _tiered_moe_for(cfg, tiered, i, gi)
            if kind in ("mamba", "mlstm", "slstm"):
                x, c = _decode_block(gp[i], shared, cfg, kind, x, gc[i], pos,
                                     a_local, ep_axes)
            elif kind == "shared_attn":
                x, c = _paged_attn_block(shared, cfg, "attn", x, gc[i], pos,
                                         a_local, ep_axes, page_t, smesh,
                                         collect_mass=collect_mass)
            else:
                x, c = _paged_attn_block(gp[i], cfg, kind, x, gc[i], pos,
                                         a_local, ep_axes, page_t, smesh,
                                         tiered_moe=tm,
                                         collect_mass=collect_mass)
            new_gc.append(c)
        streams = a_local["router_streams"]
        out = jnp.stack(streams) if streams else jnp.zeros((0,), jnp.int32)
        masses = a_local["kv_mass_streams"]
        kv_mass = (jnp.stack(masses) if masses
                   else jnp.zeros((0,), jnp.float32))
        return (x,), (new_gc, out, kv_mass)

    g = cfg.n_groups
    (x,), (new_blocks, router, kv_mass) = jax.lax.scan(
        group_body, (x,),
        (params["blocks"], cache["blocks"], jnp.arange(g, dtype=jnp.int32)))
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = logits_apply(params["embed"], x, cfg.final_softcap)
    new_cache = {"blocks": new_blocks, "pos": pos + 1}
    if new_pro:
        new_cache["prologue"] = new_pro
    if return_streams:
        return logits, new_cache, {
            "router": router if router.size else None,
            "kv_mass": kv_mass if kv_mass.size else None,
        }
    return logits, new_cache


# ---------------------------------------------------------------------------
# content-addressed page install (cross-request KV reuse, DESIGN.md §12)
# ---------------------------------------------------------------------------

def reuse_eligible(cfg: ArchConfig) -> bool:
    """True when a lane's full per-position decode state is carried by the
    KV slow store alone — the precondition for fast-forwarding a fresh
    lane over slow-store pages (DESIGN.md §12).  The tiered KV payload
    holds only the representative paged-attention entry, so reuse needs a
    single-position pattern (no sibling rings), no O(1) recurrent states
    and no dense-prologue ring (those travel only in preempt residuals)."""
    recurrent = any(k in ("mamba", "mlstm", "slstm") for k in cfg.pattern)
    prologue = bool(cfg.moe and cfg.moe.n_dense_prologue)
    return len(cfg.pattern) == 1 and not recurrent and not prologue


def install_pages(cache, lane: int, slot_ids, rows, *, dk: int, page_t: int,
                  new_pos: int) -> None:
    """Fast-forward one lane's paged ring to ``new_pos`` by installing
    pre-computed KV page payloads.

    ``rows`` is (G, n, T, hkv, dk+dv) slow-store [K | V] payload for ring
    slots ``slot_ids``.  Bit-exact with streaming the same tokens to the
    same position: installed slots hold full pages, and the new current
    slot's fill is zeroed — the eager-advance invariant of
    `_append_attend_local` (at a page boundary ``cur_slot`` has already
    advanced onto an empty slot).  Requires `reuse_eligible`: the
    representative entry must BE the whole per-position state.
    """
    entry = next(c for c in cache["blocks"]
                 if isinstance(c, dict) and "page_len" in c)
    n_slots = entry["page_len"].shape[-1]
    slot_ids = jnp.asarray(slot_ids, jnp.int32)
    entry["k_pages"] = entry["k_pages"].at[:, lane, slot_ids].set(
        rows[..., :dk].astype(entry["k_pages"].dtype))
    entry["v_pages"] = entry["v_pages"].at[:, lane, slot_ids].set(
        rows[..., dk:].astype(entry["v_pages"].dtype))
    entry["page_len"] = entry["page_len"].at[:, lane, slot_ids].set(page_t)
    cur = (new_pos // page_t) % n_slots
    entry["cur_slot"] = entry["cur_slot"].at[:, lane].set(cur)
    entry["page_len"] = entry["page_len"].at[:, lane, cur].set(0)
    cache["pos"] = cache["pos"].at[lane].set(new_pos)


# ---------------------------------------------------------------------------
# sampling — temperature / nucleus over the lane substrate (DESIGN.md §9)
# ---------------------------------------------------------------------------

@jax.jit
def fold_lane_keys(keys: jax.Array, idx: jax.Array) -> jax.Array:
    """Vectorized per-lane key derivation: fold each lane's (2,) uint32
    request-identity key with its emitted-token index — ONE dispatch for
    the whole lane batch (the per-token scheduler hot path)."""
    return jax.vmap(jax.random.fold_in)(keys, idx)


@functools.partial(jax.jit, static_argnames=("temperature", "top_p"))
def sample_tokens(logits: jax.Array, keys: jax.Array, *,
                  temperature: float = 0.0, top_p: float = 1.0) -> jax.Array:
    """Per-lane token sampling: (L, V) logits + (L, 2) uint32 PRNG keys.

    ``temperature <= 0`` is exact argmax (the keys are ignored), so greedy
    callers pay nothing.  Otherwise logits are temperature-scaled and,
    with ``top_p < 1``, nucleus-filtered: the smallest prefix of
    descending-probability tokens whose mass reaches ``top_p`` stays (the
    top-1 token always survives), everything else is masked to -inf.

    One key per lane: the scheduler derives it from (trace seed, request
    id, position), so a lane's draw depends only on the REQUEST's identity
    and progress — replays, preemptions, and lane reassignment cannot
    change a trace's sampled tokens.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    if top_p < 1.0:
        desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p          # mass BEFORE this token < top_p
        cutoff = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
