"""chip_smoke.py off the chip: it refuses the CPU, and its phases run at a
reduced config with their own assertions."""
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
REDUCED = "qwen2-0.5b-reduced"


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.phase_train(REDUCED, batch=8, seq=32)


def test_train_phase_reduced(trained):
    assert len(trained["step_s"]) == chip_smoke.STEPS
    assert trained["final_loss"] < trained["first_loss"]


def test_serve_phase_reduced():
    out = chip_smoke.phase_serve(REDUCED, requests=3, prompt_len=8,
                                 max_new=4, max_batch=2)
    assert out["tokens_out"] == 12


def test_predict_phase_reduced(calibration_store, trained):
    out = chip_smoke.phase_predict(calibration_store, trained["step_s"][1:],
                                   REDUCED, batch=8, seq=32)
    assert set(out) == {"forward", "train_dp1_tp1"}
    assert all(meas > 0 for _, meas in out.values())


def test_mesh_phase_on_four_virtual_devices():
    """The --chips 4 comparison (2x2 mesh vs one device) on 4 CPU devices."""
    code = textwrap.dedent("""
        import chip_smoke
        chip_smoke.phase_mesh("qwen2-0.5b-reduced", batch=8, seq=32)
        print("MESH_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout
