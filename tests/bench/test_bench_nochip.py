"""Without a TPU the harness exits non-zero and prints no result; in a
directory that holds only BENCHMARK.json and the benchmark's own files
(no program) it does the same."""
import json
import os
import shutil
import subprocess
import sys

from bench_cells import REPO


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen1.5-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_refuses_without_a_chip():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    _no_result(proc)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
