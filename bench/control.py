"""The control and the program's readings for a cell's correctness limit,
one process, several seeds.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it makes one run of the cell as ``run.py`` does (profiler
off) with the fp8 control put in the program's place: at every position
the check compares, the token the control puts first is judged by the
same rule and limit as a served token.  It prints one JSON line per seed:
the control's verdict (``correct``, which has to be false) and its widest
gap below the float32 reference's best (``control_gap``), and the
program's own verdict and gap on the same run (``program_correct``,
``gap``).  The limit in ``bench/limits/<cell>.json`` lies between the
largest ``gap`` and the smallest ``control_gap``.  Needs the chips the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench.run import chips_or_exit, enable_cache
    from bench.spec import load_cell
    cell = load_cell(args.workload, ROOT)
    devs = chips_or_exit(cell.chips)
    enable_cache()
    from bench import serve
    t_start = T_START
    for seed in args.seeds:
        out = serve.run(cell, seed, args.seconds, False, t_start,
                        devs[0].device_kind, control=True)
        prog = out["program"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "control_gap": out["compared"]["max_logit_gap"]["value"],
            "program_correct": prog["correct"],
            "gap": prog["compared"]["max_logit_gap"]["value"],
            "tokens": prog["compared"]["tokens_compared"]["value"],
            "limit": out["compared"]["max_logit_gap"]["limit"],
            "metrics": out["metrics"]}), flush=True)
        gc.collect()
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
