"""A cell defined only by new files under a root -- a configuration, a
traffic mix, a limit and entries in BENCHMARK.json -- is found by name and
runs end to end through the harness (on the CPU, at a tiny size)."""
import bench_cells


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
