"""Model side of the benchmark: configuration files, seeded weights, and the
plain float32 reference that decides ``correct``.

Nothing here imports the program except its ``ArchConfig`` schema, which
the engine under test is built from.  The weights are the benchmark's own
(drawn on the device from the seed, in the parameter layout the engine
reads), and the reference is a straightforward decoder forward pass
written from the published description, with the departures the
configuration file lists.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# Published keys of a dense GQA decoder config.json that the engine's
# ArchConfig takes, as (published key, ArchConfig field).
_WIDTHS = (("num_hidden_layers", "n_layers"), ("hidden_size", "d_model"),
           ("num_attention_heads", "n_heads"),
           ("num_key_value_heads", "n_kv_heads"),
           ("intermediate_size", "d_ff"), ("vocab_size", "vocab"))


def load_config(path: Path) -> dict:
    """The configuration as run: the file's published keys, with the value
    ``as_run`` of each departure the file lists under ``assumed``, plus
    ``serve``."""
    conf = json.loads(Path(path).read_text())
    for key, row in conf.get("assumed", {}).items():
        if isinstance(row, dict) and "as_run" in row:
            conf[key] = row["as_run"]
    return conf


def arch_fields(conf: dict) -> dict:
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
    fields = {field: int(conf[key]) for key, field in _WIDTHS}
    fields["head_dim"] = int(conf.get("head_dim") or
                             conf["hidden_size"] // conf["num_attention_heads"])
    fields["norm"] = "ln" if "layer_norm_eps" in conf else "rms"
    fields["qkv_bias"] = bool(conf.get("use_qkv_bias",
                                       conf.get("model_type") == "qwen2"))
    fields["rope_theta"] = float(conf["rope_theta"])
    return dict(fields, name=conf["name"], family="dense", mlp="swiglu",
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


def weight_key(seed: int) -> jax.Array:
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    word = np.random.SeedSequence([seed, 1]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


# ---------------------------------------------------------------------------
# the plain reference
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


def served_gaps(f: dict, eps: float, params, prompt: np.ndarray,
                out: list[int], page_t: int, ring_pages: int, n: int,
                control: bool = False) -> dict:
    """Compare one served request with the reference.

    The k-th output token was chosen from the logits at position
    ``len(prompt) - 1 + k``.  Returns the widest gap by which a served
    token's reference logit lies below the reference's best there
    (``gap``), and with ``control`` the same gap for the token that the
    fp8 control puts first at each of those positions (``control_gap``)."""
    out = np.asarray(out, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), out[:-1]])
    first = len(prompt) - 1
    ref = forward_logits(f, eps, params, seq, page_t, ring_pages, n)[first:]
    res = {"gap": float(_gap(ref, jnp.asarray(out))), "tokens": int(out.size)}
    if control:
        low = forward_logits(f, eps, params, seq, page_t, ring_pages, n,
                             fmt="fp8")[first:]
        res["control_gap"] = float(_gap(ref, jnp.argmax(low, -1)))
    return res


@jax.jit
def _gap(ref, chosen):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(best - got)
