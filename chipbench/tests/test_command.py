"""The command refuses to measure anything but the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen3_sweep",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_bare_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
