"""The command refuses to run without a chip, and without the program."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "solve-s17-k16", "--seed", str(2**31 + 9), "--seconds", "1",
        "--trace", "0"]


def run_in(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_non_zero_without_a_result():
    out = run_in(ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_in(tmp_path)
    assert out.returncode != 0 and out.stdout == ""
