"""``bench/run.py`` refuses to run anywhere but on a TPU, and in a
directory holding only the benchmark's own files, and then prints no
result."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "divimp2z-steady", "--seed", "4294967297",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.strip().startswith("{") for line in out.splitlines())


def test_run_exits_nonzero_off_the_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not falling back" in p.stderr


@pytest.mark.parametrize("keep", [["BENCHMARK.json", "bench"]])
def test_run_fails_with_only_the_benchmark_files(tmp_path, keep):
    for name in keep:
        src = ROOT / name
        if src.is_dir():
            shutil.copytree(src, tmp_path / name,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, tmp_path / name)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
