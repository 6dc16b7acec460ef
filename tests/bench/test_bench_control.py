"""The control: the reference computed one precision step below the
configuration's bf16 (fp8 operands) and put in the program's place makes
the run come out not correct, by the same rule and limit, while the
program's own tokens pass on the same run, on three seeds.  Here at a tiny
size on the CPU; bench/control.py makes the same runs on the chip at each
cell's own size."""
import time

import bench_cells
from bench import serve
from bench.spec import load_cell


def test_control_fails_where_the_program_passes(tmp_path):
    # as many tokens compared as a cell's check compares
    root = bench_cells.tiny_root(tmp_path, check_requests=8,
                                 check_tokens=200)
    cell = load_cell(bench_cells.TINY_CELL, root)
    limit = cell.limits["max_logit_gap"]
    for seed in (1, 3, 2**33 + 1):
        out = serve.run(cell, seed, 1.0, False, time.perf_counter(),
                        "TPU v5 lite", control=True)
        gap = out["compared"]["max_logit_gap"]
        assert out["correct"] is False, gap
        assert gap["value"] > gap["limit"] == limit
        prog = out["program"]
        assert prog["correct"] is True, prog
        assert prog["compared"]["max_logit_gap"]["value"] <= limit
