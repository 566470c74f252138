"""The command refuses to measure without a chip and without the program."""

import os
import shutil
import subprocess
import sys

from chipbench import cells

RUN = ["benchmarks/chip/run.py", "--workload", "qwen3-0.6b.chat",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + RUN, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_on_the_cpu_and_prints_no_result():
    p = _run(cells.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
