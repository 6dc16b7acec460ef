"""Operations and bytes of the served work, counted from shapes.

These functions are the yardstick for ``mfu`` and ``paged_attn_roofline``:
what the algorithm needs for the tokens the window processed, whatever
implements it.  ``f`` is the ArchConfig field dict of the configuration
(:func:`bench.model.arch_fields`).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def device_peaks(kind: str) -> dict:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{PEAKS.name}")
    return table[kind]


def attended(pos, page_t: int, ring_pages: int) -> np.ndarray:
    """Keys attended by the token at position ``pos`` (0-based): the
    ring's pages, as :func:`bench.model.window_mask` defines them."""
    pos = np.asarray(pos, np.int64)
    oldest = np.maximum((pos + 1) // page_t - (ring_pages - 1), 0) * page_t
    return pos + 1 - oldest


def matmul_params(f: dict) -> int:
    """Weights one token multiplies through: the layers' projections and
    MLP, and the tied output head."""
    d, h, hkv, dh = f["d_model"], f["n_heads"], f["n_kv_heads"], f["head_dim"]
    per_layer = d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * f["d_ff"]
    return f["n_layers"] * per_layer + f["vocab"] * d


def token_flops(f: dict, keys) -> np.ndarray:
    """Forward FLOPs of tokens that attend ``keys`` positions each."""
    attn = 4 * f["n_heads"] * f["head_dim"] * f["n_layers"]
    return 2.0 * matmul_params(f) + attn * np.asarray(keys, np.float64)


def paged_attn_call(f: dict, keys) -> tuple[float, float]:
    """(FLOPs, bytes) of one ``paged_attn`` call of one layer: one query
    token per active lane, ``keys`` the keys each of those lanes attends.
    Bytes: bf16 K and V of the attended tokens, the f32 query, and the f32
    output (numerator, running max and denominator)."""
    keys = np.asarray(keys, np.float64)
    h, hkv, dh = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    flops = 4.0 * h * dh * keys.sum()
    kv = 2 * 2 * hkv * dh * keys.sum()
    q_out = keys.size * (4 * h * dh + 4 * (h * dh + 2 * h))
    return flops, kv + q_out


def paged_attn_least_s(f: dict, bodies, peaks: dict, page_t: int,
                       ring_pages: int) -> tuple[float, str]:
    """Least device time for the kernel's work over ``bodies`` (each the
    positions of the active lanes of one decode body), at the device's
    peaks, and which bound sets it."""
    t_flops = t_bytes = 0.0
    least = 0.0
    for positions in bodies:
        if not len(positions):
            continue
        fl, by = paged_attn_call(f, attended(positions, page_t, ring_pages))
        a, b = fl / peaks["bf16_flops"], by / peaks["hbm_bytes_s"]
        t_flops += a
        t_bytes += b
        least += max(a, b)
    return least * f["n_layers"], ("memory" if t_bytes >= t_flops
                                   else "compute")
