"""``chip_smoke.py`` on the CPU: its phases at ``-smoke`` size (the kernels
interpreted), and ``main`` refusing to run without a TPU."""
import os
import subprocess
import sys

import chip_smoke
from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_engine_phase_smoke(tmp_path):
    failures = chip_smoke.phase_engine(
        get_config("phi3-mini-3.8b-smoke"), chip_smoke.SMOKE_TRAFFIC,
        out_dir=tmp_path, mosaic=False)
    assert failures == []
    assert (tmp_path / "registry.jsonl").exists()


def test_engine_phase_requires_mosaic_kernels(tmp_path, capsys):
    """On the CPU the pallas executables hold no ``tpu_custom_call``:
    with ``mosaic=True`` that is a failed check, never a pass."""
    failures = chip_smoke.phase_engine(
        get_config("phi3-mini-3.8b-smoke"), chip_smoke.SMOKE_TRAFFIC,
        out_dir=tmp_path, mosaic=True)
    assert failures and all("tpu_custom_call" in f for f in failures)


def test_kernel_phase_smoke():
    assert chip_smoke.phase_kernels(chip_smoke.SMOKE_WIDTHS) == []


def test_four_chip_phase_on_virtual_devices():
    code = ("import chip_smoke\n"
            "from repro.configs import get_config\n"
            "f = chip_smoke.phase_four_chips(get_config("
            "'phi3-mini-3.8b-smoke'), steps=2, batch=4, seq=16)\n"
            "assert f == [], f\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "2x2 mesh: losses" in out.stdout


def test_four_chip_phase_needs_four_devices():
    failures = chip_smoke.phase_four_chips(
        get_config("phi3-mini-3.8b-smoke"), steps=1, batch=2, seq=8)
    assert failures and "4 devices" in failures[0]


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out
