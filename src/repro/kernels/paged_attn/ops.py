"""Jitted wrappers for paged decode attention.

``paged_attention``          — single-device (or replicated) call; with
    ``return_mass=True`` also yields the kernel-exported per-page softmax
    mass (the NeoProf-true "kv" hotness stream, DESIGN.md §10).
``paged_attention_local_stats`` — raw flash-decode stats; with
    ``return_page_stats=True`` additionally the page-local (m, l) partials.
    For fast-tier pages sharded across mesh axes, each shard runs this over
    its local slots (``models/decode.py::_append_attend_sharded`` — the
    cross-device flash-decoding serve path for long_500k) and merges via:
``combine_stats``            — the cross-shard combine (pmax/psum pair);
    given the page partials it also returns each LOCAL page's share of the
    GLOBAL softmax mass, normalized by the same pair.

``interpret=None`` (the default everywhere) takes the backend's answer:
compiled on a TPU, interpreted on the CPU backend
(:func:`repro.kernels.backend.resolve_interpret`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attn.paged_attn import (
    page_mass,
    paged_attention as _kernel,
    paged_attention_raw as _kernel_raw,
)

__all__ = ["paged_attention", "paged_attention_local_stats", "combine_stats",
           "page_mass"]


def paged_attention(q, k_pages, v_pages, page_lengths, *,
                    scale=None, softcap: float = 0.0, interpret=None,
                    return_mass: bool = False):
    return _kernel(q, k_pages, v_pages, page_lengths,
                   scale=scale, softcap=softcap, interpret=interpret,
                   return_mass=return_mass)


def paged_attention_local_stats(q, k_pages, v_pages, page_lengths, *,
                                scale=None, softcap: float = 0.0,
                                interpret=None,
                                return_page_stats: bool = False):
    return _kernel_raw(q, k_pages, v_pages, page_lengths,
                       scale=scale, softcap=softcap, interpret=interpret,
                       return_page_stats=return_page_stats)


def combine_stats(m, l, acc, axis_names, page_m=None, page_l=None):
    """Flash-decoding cross-shard softmax combine over ``axis_names``.

    With the kernel's page partials (``page_m``/``page_l``, each shard's
    (B, P_local, H)) the result is ``(out, mass)`` where ``mass`` is the
    (B, P_local) share of the GLOBAL attention mass held by each local
    page — the normalizers (pmax/psum) are the very pair the output
    combine already needs, so the mass export adds no extra collective.
    """
    m_glob = jax.lax.pmax(m, axis_names)
    w = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * w, axis_names)
    acc_glob = jax.lax.psum(acc * w, axis_names)
    out = acc_glob / jnp.maximum(l_glob, 1e-30)
    if page_m is None:
        return out
    return out, page_mass(m_glob, l_glob, page_m, page_l)
