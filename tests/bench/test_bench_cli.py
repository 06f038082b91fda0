"""The command refuses to report without the accelerator, and without the
program beside it."""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ("--workload", "smollm_360m.chat", "--seed", "3", "--seconds", "1",
        "--trace", "0")


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_exits_nonzero_with_no_result(tmp_path, where):
    root = ROOT
    if where == "benchmark_only":
        root = tmp_path
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, str(root / "bench/run.py"), *ARGS],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
