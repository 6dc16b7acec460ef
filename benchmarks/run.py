"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract.
``--quick`` shrinks streams 4x for CI-speed runs.
"""
from __future__ import annotations

import argparse
import sys
import traceback


MODULES = [
    "fig03_tier_gap",
    "fig04_profiling_tradeoff",
    "fig11_main_speedup",
    "fig12_ratio_sweep",
    "fig13_traffic",
    "fig14_policy_dynamics",
    "fig15_sensitivity",
    "fig16_convergence",
    "kernel_bench",
    "serve_bench",
    "traffic_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="substring filter")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    failures = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        print(f"# === benchmarks.{name} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(quick=args.quick)
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
