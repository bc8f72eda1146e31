"""The benchmark's command: no result without a TPU, none without the
program, none for a cell that does not exist."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "sarcos-ppitc.fit", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, args=ARGS, **env):
    command = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("PYTHONPATH", None)
    command = [sys.executable if c in ("python", "python3") else c
               for c in command]
    return subprocess.run(command + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_unknown_cell_exits_nonzero():
    args = list(ARGS)
    args[1] = "no-such.cell"
    out = _run(ROOT, args)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
