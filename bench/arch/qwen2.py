"""The dense GQA decoder (``model_type`` ``qwen2``): its engine fields, its
seeded weights, its plain float32 reference and its work counts.

A configuration file names its published ``model_type``; the harness loads
``bench/arch/<model_type>.py`` from the cell's root (``bench.spec.arch``)
and reads everything that depends on the architecture from it:

- ``fields(conf)``: the engine's ``ArchConfig`` keyword arguments;
- ``norm_eps(conf)``;
- ``make_weights(f, key)``: bf16 weights in the engine's parameter layout;
- ``forward_logits(f, eps, params, tokens, page_t, ring_pages, n, fmt)``:
  the reference, with the ``fmt="fp8"`` control;
- ``token_flops(f, keys)``: model FLOPs of tokens attending ``keys`` keys;
- ``paged_attn_call(f, keys)``: FLOPs and bytes of one ``paged_attn`` call
  of one layer;
- ``attn_layers(f)``: how many layers call the kernel per decode body.

Nothing here imports the program except through the ``ArchConfig`` field
names the engine is built from.  The reference is a straightforward
decoder forward pass written from the published description, with the
departures the configuration file lists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import _mm, _norm, _round, _rope, window_mask

# Published keys of a dense GQA decoder config.json that the engine's
# ArchConfig takes, as (published key, ArchConfig field).
_WIDTHS = (("num_hidden_layers", "n_layers"), ("hidden_size", "d_model"),
           ("num_attention_heads", "n_heads"),
           ("num_key_value_heads", "n_kv_heads"),
           ("intermediate_size", "d_ff"), ("vocab_size", "vocab"))


def fields(conf: dict) -> dict:
    """ArchConfig keyword arguments for a configuration as run.

    Raises for a published setting the engine cannot run (the file must
    then list the departure under ``assumed`` with the value as run)."""
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: only silu "
                         f"(SwiGLU) is served")
    if conf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("partial rotary embeddings are not served")
    if not conf.get("tie_word_embeddings", True):
        raise ValueError("untied output embeddings are not served")
    f = {field: int(conf[key]) for key, field in _WIDTHS}
    f["head_dim"] = int(conf.get("head_dim") or
                        conf["hidden_size"] // conf["num_attention_heads"])
    f["norm"] = "ln" if "layer_norm_eps" in conf else "rms"
    f["qkv_bias"] = bool(conf.get("use_qkv_bias",
                                  conf.get("model_type") == "qwen2"))
    f["rope_theta"] = float(conf["rope_theta"])
    return dict(f, name=conf["name"], family="dense", mlp="swiglu",
                tie_embeddings=True)


def norm_eps(conf: dict) -> float:
    return float(conf.get("layer_norm_eps", conf.get("rms_norm_eps", 1e-6)))


# ---------------------------------------------------------------------------
# weights: drawn on the device from the seed, bf16 as served
# ---------------------------------------------------------------------------

def make_weights(f: dict, key: jax.Array):
    """bf16 weights of a dense decoder in the engine's parameter layout
    (group-stacked blocks), drawn in ONE jitted call.  Norm scales and
    biases are drawn too (not ones and zeros), so the reference checks the
    paths that read them."""
    @jax.jit
    def draw(key):
        d, f_, v = f["d_model"], f["d_ff"], f["vocab"]
        h, hkv, dh, n = f["n_heads"], f["n_kv_heads"], f["head_dim"], \
            f["n_layers"]
        ks = (jax.random.fold_in(key, i) for i in range(1 << 20))

        def w(shape, fan_in, dtype=jnp.bfloat16):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)

        def norm(shape):
            p = {"scale": 1.0 + 0.1 * jax.random.normal(next(ks), shape)}
            if f["norm"] == "ln":
                p["bias"] = 0.1 * jax.random.normal(next(ks), shape)
            return p

        attn = {"wq": w((n, d, h * dh), d), "wk": w((n, d, hkv * dh), d),
                "wv": w((n, d, hkv * dh), d), "wo": w((n, h * dh, d), h * dh)}
        if f["qkv_bias"]:
            for b, width in (("bq", h * dh), ("bk", hkv * dh),
                             ("bv", hkv * dh)):
                attn[b] = w((n, width), 100.0)     # rms 0.1
        block = {"ln1": norm((n, d)), "ln2": norm((n, d)), "attn": attn,
                 "ffn": {"w_in": w((n, d, f_), d), "w_gate": w((n, d, f_), d),
                         "w_out": w((n, f_, d), f_)}}
        return {"embed": {"table": w((v, d), d)}, "blocks": [block],
                "final_norm": norm((d,))}

    return draw(key)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("f", "eps", "fmt"))
def _layer(x, p, mask, *, f, eps, fmt):
    """One decoder layer on (S, d) float32 activations."""
    h, hkv, dh = f["n_heads"], f["n_kv_heads"], f["head_dim"]
    s = x.shape[0]
    a = _norm(p["ln1"], x, f["norm"], eps)
    q = _mm(a, p["attn"]["wq"], fmt) + p["attn"].get("bq", 0.0)
    k = _mm(a, p["attn"]["wk"], fmt) + p["attn"].get("bk", 0.0)
    v = _mm(a, p["attn"]["wv"], fmt) + p["attn"].get("bv", 0.0)
    q = _rope(q.reshape(s, h, dh), f["rope_theta"])
    k = _rope(k.reshape(s, hkv, dh), f["rope_theta"])
    v = v.reshape(s, hkv, dh)
    rep = h // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", _round(q, fmt), _round(k, fmt),
                    precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
    sc = jnp.where(mask[None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _round(pr, fmt), _round(v, fmt),
                   precision=jax.lax.Precision.HIGHEST).reshape(s, h * dh)
    x = x + _mm(o, p["attn"]["wo"], fmt)
    b = _norm(p["ln2"], x, f["norm"], eps)
    g = _mm(b, p["ffn"]["w_gate"], fmt)
    u = _mm(b, p["ffn"]["w_in"], fmt)
    return x + _mm(jax.nn.silu(g) * u, p["ffn"]["w_out"], fmt)


@functools.partial(jax.jit, static_argnames=("f", "eps", "fmt"))
def _head(x, fin, table, *, f, eps, fmt):
    """(S, d) final activations -> (S, V) float32 logits (tied head)."""
    return _mm(_norm(fin, x, f["norm"], eps), table.T, fmt)


def forward_logits(f: dict, eps: float, params, tokens: np.ndarray,
                   page_t: int, ring_pages: int, n: int, fmt: str = "f32"):
    """Logits (S, V) of the reference over ``tokens`` (S,), layer by layer
    so that only one layer's weights are upcast at a time.  Every sequence
    is padded to ``n`` (the configuration's max_seq; padding sits after
    the tokens, which a causal mask never lets them see), so a run
    compiles one shape."""
    s = len(tokens)
    toks = np.zeros(n, np.int32)
    toks[:s] = tokens
    mask = jnp.asarray(window_mask(n, page_t, ring_pages))
    x = params["embed"]["table"][jnp.asarray(toks)].astype(jnp.float32)
    blocks = params["blocks"][0]
    frozen = _Frozen(f)
    for layer in range(f["n_layers"]):
        p = jax.tree.map(lambda a: a[layer], blocks)
        x = _layer(x, p, mask, f=frozen, eps=eps, fmt=fmt)
    return _head(x, params["final_norm"], params["embed"]["table"],
                 f=frozen, eps=eps, fmt=fmt)[:s]


class _Frozen(dict):
    """A hashable dict, so the architecture fields can be a static jit
    argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


# ---------------------------------------------------------------------------
# work counted from shapes (the yardstick of mfu and paged_attn_roofline)
# ---------------------------------------------------------------------------

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


def attn_layers(f: dict) -> int:
    """Layers that call ``paged_attn`` in one decode body: all of them."""
    return f["n_layers"]
