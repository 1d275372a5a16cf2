"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script refuses a device that is not a TPU; here the phases behind that
check run directly, on qwen3-0.6b's smoke config in bf16 and small kernel
shapes in interpret mode, with a wider deadline margin for a shared host.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config

_SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernels_phase_interpreted(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "KERNEL_SHAPES", {
        "matmul": (256, 128, 256),
        "flash": (2, 256, 64),
        "scan": (1, 32, 128, 16),
    })
    smoke.check_kernels(seed=0, interpret=True)
    out = capsys.readouterr().out
    for name in ("matmul", "flash", "scan"):
        assert f"kernel {name}" in out


def test_serving_phase_admits_and_meets_deadlines(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "SERVE",
                        smoke.ServeConfig(max_context=64, batch=2))
    monkeypatch.setattr(smoke, "PROMPT_LEN", 16)
    monkeypatch.setattr(smoke, "NEW_TOKENS", 4)
    monkeypatch.setattr(smoke, "MARGIN", 10.0)
    monkeypatch.setattr(smoke, "MIN_DURATION_S", 1.0)
    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"),
                              dtype="bfloat16")
    smoke.serve(cfg, seed=0)
    out = capsys.readouterr().out
    assert "logits bfloat16 vs float32" in out
    assert "admission: admitted=True" in out
    assert "missed=0" in out


def test_refuses_cpu_and_prints_no_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(_SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.startswith("{") or not json.loads(line).get("ok")
