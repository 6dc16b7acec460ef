"""A cell defined only by new files under a root -- a configuration, a
traffic mix, a limit and entries in BENCHMARK.json, and an architecture
module -- is found by name and runs end to end through the harness (on the
CPU, at a tiny size)."""
import json

import pytest

import bench_cells
from bench import model, serve
from bench.spec import load_cell
from repro.serve.engine import ServeConfig


def test_new_cell_runs_from_its_files_alone(tmp_path):
    root = bench_cells.tiny_root(tmp_path)
    out = bench_cells.drive(root, seed=2**33 + 3, seconds=1.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"tok_s", "itl_p95_ms", "ttft_p50_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] >= 2 and out["failed"] == 0
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["compared"]["tokens_compared"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_traced_run_reports_the_per_layer_metrics(tmp_path):
    root = bench_cells.tiny_root(tmp_path)
    out = bench_cells.drive(root, seed=4, seconds=1.0, trace=True)
    assert out["correct"] is True
    # host-side readers find their numbers on the CPU too; the trace has
    # no TPU plane and the CPU reports no memory peak, so the kernel's
    # roofline and the HBM peak stay silent rather than read 0
    assert {"lane_occupancy", "decode_call_ms", "embed_hit_rate",
            "mfu"} <= set(out["metrics"])
    assert not {"paged_attn_roofline", "hbm_peak_gb"} & set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


# -- an architecture added by files alone -----------------------------------

# A module that exists only under the temporary root: the dense decoder's
# functions under a model_type of its own.
NEW_ARCH = '''"""The dense decoder under another model_type."""
from bench.arch.qwen2 import (attn_layers, fields, forward_logits,  # noqa
                              make_weights, norm_eps, paged_attn_call,
                              token_flops)
'''
# The same module with its reference computed from fp8 operands: the
# program's tokens then lie 0.23-0.49 below its best on six seeds (CPU, this
# size), over the tiny limit of 0.1.
PERTURBED_ARCH = NEW_ARCH + '''from bench.arch import qwen2  # noqa: E402


def forward_logits(f, eps, params, tokens, page_t, ring_pages, n, fmt="f32"):
    return qwen2.forward_logits(f, eps, params, tokens, page_t, ring_pages,
                                n, fmt="fp8")
'''


def _arch_root(tmp_path, model_type, module=None):
    """The tiny root with its configuration naming ``model_type``, and
    ``module`` as ``bench/arch/<model_type>.py`` there (none if None)."""
    root = bench_cells.tiny_root(tmp_path)
    conf_path = root / "bench/configs/tiny.json"
    conf = json.loads(conf_path.read_text())
    conf["model_type"] = model_type
    conf_path.write_text(json.dumps(conf))
    if module is not None:
        (root / f"bench/arch/{model_type}.py").write_text(module)
    return root


def test_new_architecture_runs_from_its_module_alone(tmp_path):
    root = _arch_root(tmp_path, "tiny_decoder", NEW_ARCH)
    assert not (bench_cells.REPO / "bench/arch/tiny_decoder.py").exists()
    out = bench_cells.drive(root, seed=2**34 + 5, seconds=1.0)
    assert out["correct"] is True, out["compared"]
    assert out["compared"]["tokens_compared"]["value"] > 0
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_the_modules_own_reference_decides_correct(tmp_path):
    root = _arch_root(tmp_path, "tiny_decoder", PERTURBED_ARCH)
    out = bench_cells.drive(root, seed=2**34 + 5, seconds=1.0)
    gap = out["compared"]["max_logit_gap"]
    assert out["correct"] is False, gap
    assert gap["value"] > gap["limit"]


def test_a_model_type_without_a_module_fails_before_weights(tmp_path,
                                                            monkeypatch):
    def drawn(*_):
        raise AssertionError("weights drawn")
    monkeypatch.setattr(model, "weight_key", drawn)
    root = _arch_root(tmp_path, "no_such_arch")
    with pytest.raises(FileNotFoundError, match="bench/arch/no_such_arch.py"):
        bench_cells.drive(root, seed=1, seconds=1.0)


def test_the_serve_block_names_the_tiers():
    geo = load_cell("qwen1.5-4b.chat").conf["serve"]
    scfg = serve.serve_config(geo)
    assert scfg.resources == ("embeddings",)
    assert (scfg.expert_hot_slots, scfg.expert_quota) == (
        ServeConfig.expert_hot_slots, ServeConfig.expert_quota)
    scfg = serve.serve_config(dict(geo, resources=["embeddings", "experts"],
                                   expert_hot_slots=8, expert_quota=16))
    assert scfg.resources == ("embeddings", "experts")
    assert (scfg.expert_hot_slots, scfg.expert_quota) == (8, 16)
