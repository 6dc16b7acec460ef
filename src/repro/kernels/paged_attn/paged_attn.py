"""Paged decode attention over NeoMem-resident hot KV pages (Pallas TPU).

The serving hot path for tiered long-context decode (DESIGN.md §3.2): one new
query token attends over the fast-tier-resident KV *pages* selected by the
NeoMem policy.  Flash-decoding style online softmax, gridded over pages so
each page's KV block streams HBM->VMEM exactly once; (m, l, acc) running
stats live in revisited output blocks (the TPU grid is sequential over the
last axis, so read-modify-write accumulation is well-defined).

Supports GQA (q heads grouped over kv heads), per-page token counts (partial
last page), invalid-page masking (pages the tiering layer could not promote)
and gemma2-style logit soft-capping.

NeoProf mass export (DESIGN.md §10): with ``return_page_stats=True`` the
kernel additionally writes per-page PER-HEAD softmax partials — the page's
local score max ``page_m`` and local denominator ``page_l = Σ exp(s -
page_m)`` — in the SAME VMEM pass that computes the output (the hardware
analogue of NeoProf snooping access intensity at line rate: zero extra HBM
reads).  Rescaled against the global (m, l) they yield each page's true
share of the step's attention mass; that rescale lives in ``ops.page_mass``
and, for the sharded path, ``ops.combine_stats``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _paged_attn_kernel(
    len_ref,      # (B, P) int32 in SMEM — valid tokens per page (0 = invalid)
    q_ref,        # (1, Hkv, G, dk)   query heads grouped by their kv head
    k_ref,        # (1, 1, T, Hkv*dk) one page, kv heads side by side
    v_ref,        # (1, 1, T, Hkv*dv)
    m_ref,        # (1, Hkv, G, 1)  f32 running max
    l_ref,        # (1, Hkv, G, 1)  f32 running denom
    acc_ref,      # (1, Hkv, G, dv) f32 running numerator
    pm_ref=None,  # (1, 1, Hkv, G, 1) f32 page-local score max (page stats)
    pl_ref=None,  # (1, 1, Hkv, G, 1) f32 page-local denom     (page stats)
    *, scale: float, softcap: float,
):
    b, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_valid = len_ref[b, p]
    hkv, g, dk = q_ref.shape[1:]
    dv = acc_ref.shape[-1]
    t = k_ref.shape[2]
    tok = jax.lax.broadcasted_iota(jnp.int32, (g, t), 1)
    valid = tok < n_valid
    # one (G, dk) x (T, dk)^T score matmul per kv head: the GQA group's
    # queries share the head's page tile, so nothing is repeated
    for h in range(hkv):
        q = q_ref[0, h].astype(jnp.float32)                      # (G, dk)
        k = k_ref[0, 0, :, h * dk:(h + 1) * dk].astype(jnp.float32)
        v = v_ref[0, 0, :, h * dv:(h + 1) * dv].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # (G, T)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)

        m_page = jnp.max(s, axis=1, keepdims=True)               # (G, 1)
        m_prev = m_ref[0, h]
        m_cur = jnp.maximum(m_prev, m_page)
        # guard fully-masked pages: keep m finite math stable
        alpha = jnp.exp(jnp.minimum(m_prev - m_cur, 0.0))
        p_ij = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
        l_ref[0, h] = l_ref[0, h] * alpha + jnp.sum(p_ij, axis=1,
                                                    keepdims=True)
        acc_ref[0, h] = acc_ref[0, h] * alpha + jnp.dot(
            p_ij, v, preferred_element_type=jnp.float32)         # (G, dv)
        m_ref[0, h] = m_cur

        if pm_ref is not None:
            # page-local partials under the page's OWN max — rescaled to
            # the global max outside the kernel (ops.page_mass /
            # combine_stats), so this page's block never needs revisiting.
            p_loc = jnp.where(valid, jnp.exp(s - m_page), 0.0)
            pm_ref[0, 0, h] = m_page
            pl_ref[0, 0, h] = jnp.sum(p_loc, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret",
                                             "return_page_stats"))
def paged_attention_raw(
    q: jax.Array,          # (B, H, dh)
    k_pages: jax.Array,    # (B, P, T, Hkv, dk)
    v_pages: jax.Array,    # (B, P, T, Hkv, dv)
    page_lengths: jax.Array,  # (B, P) int32 — 0 marks an invalid page
    *, scale: float | None = None, softcap: float = 0.0,
    interpret: bool | None = None, return_page_stats: bool = False,
):
    """Unnormalized flash-decode stats (m, l, acc) — for cross-shard combine.

    With ``return_page_stats`` the result is (m, l, acc, page_m, page_l)
    where ``page_m``/``page_l`` are the (B, P, H) page-local softmax
    partials (see module docstring) — fully-masked pages report
    ``page_m = NEG_INF, page_l = 0``.  ``interpret=None`` takes the
    backend's answer (:func:`repro.kernels.backend.resolve_interpret`).

    Layout for the TPU tiling: page lengths ride in SMEM by scalar
    prefetch; the kv heads of a page sit side by side on the lane axis
    (a free reshape of the page layout), and every output block spans its
    array's last two dimensions.
    """
    b, h, dh = q.shape
    _, p, t, hkv, dk = k_pages.shape
    dv = v_pages.shape[-1]
    groups = h // hkv
    scale = (dh ** -0.5) if scale is None else scale
    kern = functools.partial(_paged_attn_kernel, scale=scale, softcap=softcap)

    out_specs = [
        pl.BlockSpec((1, hkv, groups, 1), lambda i, j, lens: (i, 0, 0, 0)),
        pl.BlockSpec((1, hkv, groups, 1), lambda i, j, lens: (i, 0, 0, 0)),
        pl.BlockSpec((1, hkv, groups, dv), lambda i, j, lens: (i, 0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, hkv, groups, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, groups, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, groups, dv), jnp.float32),
    ]
    if return_page_stats:
        stat = pl.BlockSpec((1, 1, hkv, groups, 1),
                            lambda i, j, lens: (i, j, 0, 0, 0))
        out_specs += [stat, stat]
        out_shape += [jax.ShapeDtypeStruct((b, p, hkv, groups, 1),
                                           jnp.float32)] * 2

    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, p),
            in_specs=[
                pl.BlockSpec((1, hkv, groups, dk),
                             lambda i, j, lens: (i, 0, 0, 0)),
                pl.BlockSpec((1, 1, t, hkv * dk),
                             lambda i, j, lens: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, t, hkv * dv),
                             lambda i, j, lens: (i, j, 0, 0)),
            ],
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
        name="paged_attn",
    )(page_lengths.astype(jnp.int32), q.reshape(b, hkv, groups, dh),
      k_pages.reshape(b, p, t, hkv * dk), v_pages.reshape(b, p, t, hkv * dv))
    m, l, acc = (o.reshape(b, h, -1) for o in outs[:3])
    if not return_page_stats:
        return m, l, acc
    return (m, l, acc) + tuple(o.reshape(b, p, h) for o in outs[3:])


def paged_attention(q, k_pages, v_pages, page_lengths, *,
                    scale=None, softcap: float = 0.0,
                    interpret: bool | None = None,
                    return_mass: bool = False):
    """Normalized paged decode attention.

    ``return_mass=True`` additionally returns the (B, P) per-page share of
    the step's softmax mass (head-averaged; masses of the valid pages sum
    to 1) — the kernel-true hotness stream for the "kv" tiered resource
    (DESIGN.md §10)."""
    if not return_mass:
        m, l, acc = paged_attention_raw(
            q, k_pages, v_pages, page_lengths,
            scale=scale, softcap=softcap, interpret=interpret)
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    m, l, acc, page_m, page_l = paged_attention_raw(
        q, k_pages, v_pages, page_lengths, scale=scale, softcap=softcap,
        interpret=interpret, return_page_stats=True)
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out, page_mass(m, l, page_m, page_l)


def page_mass(m: jax.Array, l: jax.Array,
              page_m: jax.Array, page_l: jax.Array) -> jax.Array:
    """Normalize page-local partials into per-page softmax mass.

    ``m``/``l``: (B, H, 1) global running max/denominator; ``page_m``/
    ``page_l``: (B, P, H) page-local partials.  Returns (B, P) f32 — each
    page's head-averaged share of total attention mass (valid pages sum to
    1; fully-masked pages contribute exactly 0)."""
    m_glob = jnp.swapaxes(m, 1, 2)                        # (B, 1, H)
    l_glob = jnp.swapaxes(l, 1, 2)
    mass = page_l * jnp.exp(page_m - m_glob) / jnp.maximum(l_glob, 1e-30)
    return jnp.mean(mass, axis=-1)
