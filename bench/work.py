"""Operations and bytes of the served work, counted from shapes.

These functions are the yardstick for ``mfu`` and ``paged_attn_roofline``:
what the algorithm needs for the tokens the window processed, whatever
implements it.  The counts that depend on the architecture come from its
module, ``arch`` (``bench/arch/<model_type>.py``: ``token_flops``,
``paged_attn_call``, ``attn_layers``); ``f`` is the ArchConfig field dict
it made from the configuration (its ``fields``).
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


def model_flops(arch, f: dict, positions, page_t: int,
                ring_pages: int) -> float:
    """Forward FLOPs of the tokens processed at ``positions``, each
    attending the ring's keys."""
    keys = attended(np.asarray(positions), page_t, ring_pages)
    return float(np.sum(arch.token_flops(f, keys)))


def paged_attn_least_s(arch, f: dict, bodies, peaks: dict, page_t: int,
                       ring_pages: int) -> tuple[float, str]:
    """Least device time for the kernel's work over ``bodies`` (each the
    positions of the active lanes of one decode body) in every layer that
    calls it, at the device's peaks, and which bound sets it."""
    t_flops = t_bytes = 0.0
    least = 0.0
    for positions in bodies:
        if not len(positions):
            continue
        fl, by = arch.paged_attn_call(f, attended(positions, page_t,
                                                  ring_pages))
        a, b = fl / peaks["bf16_flops"], by / peaks["hbm_bytes_s"]
        t_flops += a
        t_bytes += b
        least += max(a, b)
    return least * arch.attn_layers(f), ("memory" if t_bytes >= t_flops
                                         else "compute")
