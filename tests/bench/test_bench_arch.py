"""The dense decoder's architecture module (bench/arch/qwen2.py) draws the
same weights and computes the same reference logits as the harness did
before its architecture moved into that module, bit for bit, on the tiny
configuration on the CPU; and no other file of the harness knows the
architecture."""
import hashlib
import re

import jax
import numpy as np
import pytest

import bench_cells
from bench import model
from bench.arch import qwen2

SEED = 2**33 + 5
# sha256 (first 16 hex digits) of every weight leaf drawn from SEED, as
# the harness drew them before the dense decoder moved into its module
WEIGHTS = {
    "['blocks'][0]['attn']['bk']": "2a7a2e0516b9ff58",
    "['blocks'][0]['attn']['bq']": "66eac22cdd8e1b92",
    "['blocks'][0]['attn']['bv']": "2faf3d5448d77ecf",
    "['blocks'][0]['attn']['wk']": "7dcc78c54f83441b",
    "['blocks'][0]['attn']['wo']": "5cd3adb6f2d7339c",
    "['blocks'][0]['attn']['wq']": "73de7f984b5b4768",
    "['blocks'][0]['attn']['wv']": "f49cd58e054e86bc",
    "['blocks'][0]['ffn']['w_gate']": "49e6c589d04d94a5",
    "['blocks'][0]['ffn']['w_in']": "7189c373d15dec7e",
    "['blocks'][0]['ffn']['w_out']": "b39b8aac3da051b1",
    "['blocks'][0]['ln1']['scale']": "232b241a66db557d",
    "['blocks'][0]['ln2']['scale']": "4a88a65e0810b27d",
    "['embed']['table']": "bdf14c7e7175380a",
    "['final_norm']['scale']": "9f9d8a73188cf8dc",
}
# ... and of the (70, 1024) float32 reference logits over SEQUENCE, which
# outgrows the tiny ring (4 pages of 8), so the window mask is in play
LOGITS = "7a28d7548f0f4090"
SEQUENCE = (np.arange(70) * 37 + 11) % 1024


def _digest(x) -> str:
    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def tiny():
    conf = dict(bench_cells.TINY_CONFIG, name="tiny")
    f = qwen2.fields(conf)
    return conf, f, qwen2.make_weights(f, model.weight_key(SEED))


def test_weights_are_bit_identical_to_before_the_move(tiny):
    _, _, params = tiny
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {jax.tree_util.keystr(p): _digest(x) for p, x in leaves} == \
        WEIGHTS


def test_reference_logits_are_bit_identical_to_before_the_move(tiny):
    conf, f, params = tiny
    geo = conf["serve"]
    logits = qwen2.forward_logits(f, qwen2.norm_eps(conf), params, SEQUENCE,
                                  geo["page_t"], geo["ring_pages"],
                                  geo["max_seq"])
    assert logits.shape == (70, 1024) and logits.dtype == np.float32
    assert _digest(logits) == LOGITS


# what only an architecture module may name: a GQA width or a layer's
# weights, and (but for bench/model.py's two delegates to the dense module,
# kept for a caller outside the benchmark) an architecture module itself
_WIDTHS = r"n_kv_heads|d_ff|\bw(q|k|v|o)\b|w_gate|w_in\b|w_out"
_MODULES = r"bench\.arch|bench/arch/qwen2|qwen2\."


def test_only_architecture_modules_know_the_architecture():
    files = [p for p in (bench_cells.REPO / "bench").rglob("*.py")
             if p.parent.name != "arch"]
    assert len(files) > 10
    named = {}
    for p in files:
        words = _WIDTHS if p.name == "model.py" else f"{_WIDTHS}|{_MODULES}"
        found = {m.group(0) for m in re.finditer(words, p.read_text())}
        if found:
            named[p.name] = sorted(found)
    assert named == {}
