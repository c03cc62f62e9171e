"""chip_smoke.py: refuses to run without a TPU, and its phases pass on CPU at
the reduced size (the checks themselves are what runs on the chip)."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def _run(args, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *args], cwd=str(cwd), env=env,
                          capture_output=True, text=True, timeout=600)


def _ok_line(stdout: str) -> bool:
    return any('"ok"' in line for line in stdout.splitlines())


def test_fails_without_tpu():
    proc = _run([str(ROOT / "chip_smoke.py")], ROOT)
    assert proc.returncode != 0
    assert not _ok_line(proc.stdout)
    assert "platform=cpu" in proc.stdout


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _ok_line(proc.stdout)


def test_one_chip_phase_reduced():
    cfg = chip_smoke.get_arch(chip_smoke.ARCH).reduced()
    assert chip_smoke.one_chip(cfg, jax.devices()[0]) == []


def test_four_chip_phase_reduced():
    script = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, ".")
        import jax, chip_smoke
        cfg = chip_smoke.get_arch(chip_smoke.ARCH).reduced()
        print("RESULT " + json.dumps(chip_smoke.four_chips(cfg, jax.devices()[:4])))
    """)
    proc = _run(["-c", script], ROOT, {
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "replica -> device: 0->0, 1->1, 2->2, 3->3" in proc.stdout
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    assert json.loads(line[len("RESULT "):]) == []
