"""Helpers for the benchmark's tests: a tiny cell defined only by files
under a temporary root, and a CPU drive of the harness."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CONFIG = {
    "source": "tiny test model", "model_type": "qwen2", "hidden_act": "silu",
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 1024,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "serve": {"lanes": 2, "page_t": 8, "ring_pages": 4, "max_seq": 128,
              "kv_segments": 3, "kv_quota": 2, "embed_rows_per_page": 8,
              "embed_hot_pages": 8, "embed_quota": 4,
              "migration_interval": 4, "prefill_chunk": 16},
}
# prompts both streamed (<= 16) and chunked (> 16); outputs both inside and
# past the 32-token ring
TINY_TRAFFIC = {
    "prompt": {"median": 14, "sigma": 0.5, "min": 6, "max": 28},
    "output": {"median": 14, "sigma": 0.6, "min": 4, "max": 40},
    "zipf_a": 1.1, "pool": 16, "warmup_steps": 4, "check_requests": 3,
    "check_tokens": 40,
}
TINY_CELL = "tiny.mixed"


# the tiny cell's limit, set as a cell's is: sound runs read gaps of 0 to
# 0.034 over ten seeds, the fp8 control 0.28 and more (CPU, this size)
TINY_LIMIT = 0.1


def tiny_root(tmp: Path, limit: float = TINY_LIMIT, **traffic) -> Path:
    """A checkout-like root: the repo's BENCHMARK.json and bench/, plus a
    tiny configuration, traffic (``TINY_TRAFFIC`` with ``traffic``'s keys
    over it) and limit, and a cell naming them."""
    root = Path(tmp) / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "tinymix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (root / "bench/traffic/tinymix.json").write_text(json.dumps(dict(TINY_TRAFFIC, **traffic)))
    (root / f"bench/limits/{TINY_CELL}.json").write_text(
        json.dumps({"max_logit_gap": limit}))
    return root


def drive(root: Path, seed: int = 7, seconds: float = 1.0,
          trace: bool = False) -> dict:
    """One harness run of the tiny cell on the CPU (no chip check)."""
    from bench import serve
    from bench.spec import load_cell
    cell = load_cell(TINY_CELL, root)
    return serve.run(cell, seed, seconds, trace, time.perf_counter(),
                     "TPU v5 lite")
