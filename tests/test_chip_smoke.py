"""``chip_smoke.py`` off the chip: its phases at a tiny size on the CPU
(the selection kernel in Pallas interpret mode), and its refusal to run
anywhere but on a TPU inside a checkout."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.kernels import fed_select

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fed_select, "AUTODETECT_OVERRIDE", "interpret")


def test_paper_phase_tiny(smoke, interpret):
    out = smoke.phase_paper(rounds=2, chunk_size=1, expect_path="interpret")
    assert out["select_path"] == "interpret"
    assert out["masks_equal"] and out["rates_bitwise_equal"]
    assert out["n_selected"] == 2 * 10          # M = 10 per round


def test_million_phase_tiny(smoke, interpret):
    out = smoke.phase_million(n_clients=1_000, rounds=2, chunk_size=1,
                              kernel_n=1_000, expect_path="interpret")
    assert out["n_selected"] == 2 * 10          # K = 10 per round
    assert 0.0 <= out["r_range"][0] <= out["r_range"][1] <= 1.0
    kern = out["kernel"]
    assert kern["select_path"] == "interpret" and kern["masks_equal"]
    assert max(kern["max_ulps"].values()) == 0   # bitwise off the chip


def test_phase_fails_on_a_wrong_select_path(smoke):
    # off-TPU the autodetect runs the fused reference, not the kernel
    with pytest.raises(smoke.SmokeFailure, match="'ref'"):
        smoke.phase_million(n_clients=1_000, rounds=2, chunk_size=1,
                            kernel_n=1_000)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err
    assert captured.out == ""                    # no result line


def _run_alone(tmp_path, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=600)


def test_script_alone_refuses_and_prints_no_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {"PYTHONPATH": ""}                   # not even an installed repro
    out = _run_alone(tmp_path, env_extra=env)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "src/repro missing" in out.stderr


def test_four_chips_phase_on_four_virtual_devices():
    # the mesh path is one program across devices: rehearse it on four
    # virtual CPU devices in a child process (the device count is fixed
    # when JAX starts)
    code = f"""
import json, sys
sys.path.insert(0, {str(ROOT)!r})
import chip_smoke
print(json.dumps(chip_smoke.phase_four_chips(n_clients=1000, rounds=4,
                                             chunk_size=2)))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["masks_equal"] and res["rates_bitwise_equal"]
    assert set(res["meshes"]) == {"1", "(4,)", "(2,2)"}
    for label in ("(4,)", "(2,2)"):
        assert min(res["meshes"][label]["client_axis_devices"]) == 4
