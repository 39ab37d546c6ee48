"""A run that finds no TPU, or no program beside the benchmark, exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lm_ingest.bulk",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    _no_result(p)
    assert "no repro package" in p.stderr
