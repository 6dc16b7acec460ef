"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic, limits and metric readers are files under ``bench/``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number the correctness check
compared, beside its limit.  Those numbers are also the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero before printing a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def chips_or_exit(need: int):
    """The TPU devices, or exit non-zero when there are fewer than ``need``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        raise SystemExit(f"bench: cell needs {need} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def enable_cache() -> None:
    """JAX's persistent compilation cache at its fixed place in the
    checkout, ``.jax_cache``, whatever the environment names, for every
    program however quick.  The program's own entry point
    (repro.launch.cache) is given that directory."""
    import os

    import jax
    from repro.launch.cache import ENV, enable_compile_cache
    os.environ[ENV] = str(ROOT / ".jax_cache")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    devs = chips_or_exit(cell.chips)
    enable_cache()
    from bench import serve
    out = serve.run(cell, args.seed, args.seconds, bool(args.trace),
                    T_START, devs[0].device_kind)
    dev = devs[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devs), **out["device"]}
    compared = out.pop("compared")
    out["compared"] = compared
    for name, row in compared.items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
