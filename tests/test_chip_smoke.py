"""``chip_smoke.py``'s contract off the chip: without a TPU it refuses to
run (non-zero exit, no ``"ok": true`` line), alone in a directory it
refuses too, and ``--rehearse`` drives every single-chip phase at tiny
size with the Pallas kernels interpreted."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(*args, cwd=ROOT, script=ROOT / "chip_smoke.py", timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _claims_ok(stdout: str) -> bool:
    return any('"ok": true' in line for line in stdout.splitlines())


def test_refuses_without_a_tpu():
    out = _run()
    assert out.returncode != 0
    assert not _claims_ok(out.stdout)
    assert "no TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", alone)
    out = _run(cwd=tmp_path, script=alone)
    assert out.returncode != 0
    assert not _claims_ok(out.stdout)


def test_rehearsal_passes_at_tiny_size():
    out = _run("--rehearse")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    phases = [ln for ln in lines if ln.startswith("[phase]")]
    assert len(phases) == 15 and all(" PASS " in ln for ln in phases)
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
