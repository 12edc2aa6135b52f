"""chip_smoke.py: its phases pass on CPU at a tiny size, and the script
itself refuses to run anywhere but on a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    log = mod.CompileLog()
    yield mod, log
    log.close()


def test_fleet_phase_agrees_with_oracle_at_small_size(smoke):
    mod, log = smoke
    out = mod.fleet_phase(log, n_requests=300)
    assert out["max_rel_delta"] < 1e-9
    assert out["drain_signatures"] >= 1


def test_serve_phase_completes_every_request_at_small_size(smoke):
    from repro.configs import get_config
    mod, log = smoke
    out = mod.serve_phase(log, get_config("yi-6b").reduced(), n_requests=4,
                          b_short=16, window_long=96, prompt_lens=(8, 40),
                          max_new=4)
    assert out["programs"]          # the decode and prefill steps compiled


@pytest.mark.parametrize("case", ["cpu", "forced_kernel", "alone"])
def test_script_fails_without_a_tpu(case, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_FORCE_KERNEL", None)
    script, want = SCRIPT, "platform 'cpu'"
    if case == "forced_kernel":
        env["REPRO_FORCE_KERNEL"] = "interpret"
        want = "REPRO_FORCE_KERNEL"
    elif case == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
        env.pop("PYTHONPATH", None)
        want = "ModuleNotFoundError"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert want in proc.stderr
    assert '"ok"' not in proc.stdout
