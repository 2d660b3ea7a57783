"""chip_smoke.py's phases at smoke size on the CPU — the chip runs the same
functions at full width — and its refusal to report success off-TPU."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMALL = dict(max_len=256, new_tokens=8, prompt_lens=(40, 160))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_smoke_size(chip_smoke):
    cfg = get_config(chip_smoke.ARCH).smoke()
    out = chip_smoke.one_chip_phases(cfg, n_slots=4, n_requests=6,
                                     require_kernels=False,
                                     log=lambda line: None, **SMALL)
    assert set(out) == {f"w={w},kv={kv}" for w, kv, _ in chip_smoke.RUNS}
    for run in out.values():
        assert run["check"]["tokens"] == 6 * SMALL["new_tokens"]
        assert run["check"]["beyond_tolerance"] == 0
        assert run["stats"]["prefix_hits"] >= 2
        assert run["stats"]["cow_copies"] >= 1
        # off-TPU the engine runs the jnp paths: no Pallas kernel compiled
        assert run["kernels"] == {"decode": [], "chunk": []}


def test_four_chip_phase_on_virtual_devices():
    """The --chips 4 path on four virtual CPU devices (the device count must
    be forced before JAX starts, hence the child process)."""
    script = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro.configs import get_config
out = cs.four_chip_phase(get_config(cs.ARCH).smoke(), n_slots=8,
                         n_requests=12, log=lambda line: None, **{SMALL!r})
assert out["migrations"] >= 1 and out["pool_devices"] == [0, 1, 2, 3], out
print("FOUR_CHIP_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_CHIP_OK" in r.stdout


def test_refuses_ok_off_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
