"""What every architecture of the benchmark shares: configuration files,
the weight key from a seed, the ring's attention window, the float32
matmul and norm helpers of the references, and the comparison of served
tokens with a reference.

What depends on the architecture (engine fields, weights, the reference's
layers, work counts) lives in one module per published ``model_type``,
``bench/arch/<model_type>.py``, which may import the helpers here.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def load_config(path: Path) -> dict:
    """The configuration as run: the file's published keys, with the value
    ``as_run`` of each departure the file lists under ``assumed``, plus
    ``serve``."""
    conf = json.loads(Path(path).read_text())
    for key, row in conf.get("assumed", {}).items():
        if isinstance(row, dict) and "as_run" in row:
            conf[key] = row["as_run"]
    return conf


def weight_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    word = np.random.SeedSequence([seed, 1]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


# ---------------------------------------------------------------------------
# what the references share
# ---------------------------------------------------------------------------

def window_mask(n: int, page_t: int, ring_pages: int) -> np.ndarray:
    """(n, n) bool: may query position i attend key position j?

    Causal, and limited to the pages the engine's ring of ``ring_pages``
    slots holds when position i is processed: the page being filled and
    the ``ring_pages - 1`` before it, where a page that fills at i has
    already given up its oldest slot.  So key j is visible from i when
    ``j // page_t >= (i + 1) // page_t - (ring_pages - 1)``.  While a
    sequence is shorter than the ring this is plain causal attention."""
    i = np.arange(n)[:, None]
    j = np.arange(n)[None, :]
    oldest = (i + 1) // page_t - (ring_pages - 1)
    return (j <= i) & (j // page_t >= oldest)


def _round(x, fmt):
    """Operand rounding of the precision under test: ``f32`` leaves x as
    it is; ``fp8`` rounds to float8_e4m3fn with one scale per row (last
    axis), the control one step below the configuration's bf16."""
    if fmt == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fmt):
    """x @ w in float32 at the highest matmul precision, the operands
    first rounded as ``fmt`` says (weights per output column)."""
    w = w.astype(jnp.float32)
    if fmt != "f32":
        x = _round(x, fmt)
        w = _round(w.T, fmt).T
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(p, x, kind, eps):
    if kind == "ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """Rotary embedding over the whole head, rotate-half convention.
    x: (S, H, dh)."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def served_gaps(arch, f: dict, eps: float, params, prompt: np.ndarray,
                out: list[int], page_t: int, ring_pages: int, n: int,
                control: bool = False) -> dict:
    """Compare one served request with the reference of ``arch``, the
    configuration's architecture module.

    The k-th output token was chosen from the logits at position
    ``len(prompt) - 1 + k``.  Returns the widest gap by which a served
    token's reference logit lies below the reference's best there
    (``gap``), and with ``control`` the same gap for the token that the
    fp8 control puts first at each of those positions (``control_gap``)."""
    out = np.asarray(out, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), out[:-1]])
    first = len(prompt) - 1
    ref = arch.forward_logits(f, eps, params, seq, page_t, ring_pages,
                              n)[first:]
    res = {"gap": float(_gap(ref, jnp.asarray(out))), "tokens": int(out.size)}
    if control:
        low = arch.forward_logits(f, eps, params, seq, page_t, ring_pages,
                                  n, fmt="fp8")[first:]
        res["control_gap"] = float(_gap(ref, jnp.argmax(low, -1)))
    return res


@jax.jit
def _gap(ref, chosen):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(best - got)


# ---------------------------------------------------------------------------
# names kept for callers outside the benchmark's files
# ---------------------------------------------------------------------------

def arch_fields(conf: dict) -> dict:
    """``fields`` of the dense decoder's module, ``bench/arch/qwen2.py``.
    Kept for ``tests/test_ring_view.py``, which builds its tiny engine with
    it; the harness finds a configuration's module by ``model_type``
    (``bench.spec.arch_module``)."""
    return _dense().fields(conf)


def make_weights(f: dict, key: jax.Array):
    """``make_weights`` of ``bench/arch/qwen2.py`` (see ``arch_fields``)."""
    return _dense().make_weights(f, key)


def _dense():
    from bench.arch import qwen2
    return qwen2
