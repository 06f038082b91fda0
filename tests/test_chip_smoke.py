"""``chip_smoke.py`` off the chip: it refuses to report without a TPU or
outside a checkout, and its phases pass at smoke size on the CPU (where
the streamed FFN runs as its jnp reference, so no kernel is expected)."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_smoke_config
from repro.runtime.residency import TrafficProfile, compile_residency_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
ARGV = (
    "--arch", "smollm_360m", "--requests", "4", "--batch", "2",
    "--prompt-len", "24", "--gen-len", "3", "--max-len", "64",
    "--prefill-chunk", "16", "--seed", "0",
)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_exits_nonzero_without_result(tmp_path, where):
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        timeout=120, env=env, cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_phase_a_at_smoke_size(chip_smoke, capsys):
    chip_smoke.phase_a(get_smoke_config("smollm_360m"), argv=ARGV,
                       probes=(0, 3))
    out = capsys.readouterr().out
    assert out.count("phase A request") == 4


def test_phase_b_at_smoke_size(chip_smoke, capsys):
    cfg = get_smoke_config("smollm_360m")
    q2 = dataclasses.replace(cfg, w_bits=2)
    traffic = TrafficProfile(lanes=2, prompt_len=24, gen_len=3)
    whole = compile_residency_plan(
        q2, vmem_budget_bytes=2**30, traffic=traffic
    ).resident_bytes
    chip_smoke.phase_b(
        cfg, argv=ARGV, budget_mib=whole / 2 / 2**20, probes=(0, 3),
        expect_kernel=False,
    )
    out = capsys.readouterr().out
    assert "1 of 2 layers" in out
    # float32 smoke weights: the streamed reference and the resident path
    # do the same math, so every request is token-identical
    assert "4 of 4 requests generate the same tokens" in out
