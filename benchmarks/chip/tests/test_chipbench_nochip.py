"""Without a TPU, or without the program, a run exits nonzero and
prints no result line."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
ARGS = ["benchmarks/chip/run.py", "--workload", "f1_xmap_e10", "--seed",
        "3000000019", "--seconds", "1", "--trace", "0"]


def _run(cwd, **env):
    out = subprocess.run([sys.executable] + ARGS, cwd=cwd, text=True,
                         capture_output=True, timeout=300,
                         env=dict(os.environ, **env))
    return out


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_exits_nonzero_on_the_cpu():
    out = _run(ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode == 2, out.stderr[-2000:]
    assert not _has_result(out.stdout)
    assert "TPU" in out.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert not _has_result(out.stdout)
