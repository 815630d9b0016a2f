"""``bench/run.py`` refuses to run where it cannot measure."""
import os
import pathlib
import shutil
import subprocess
import sys

from bench import harness as hs

ARGS = ["--workload", "power-dash-steady", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(root: pathlib.Path, **env):
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *ARGS],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, **env})


def test_refuses_a_cpu_and_names_the_platform(tmp_path):
    # A copy of the checkout's benchmark and program, so that the compile
    # cache the run pins lands in the copy.
    for part in ("bench", "src"):
        shutil.copytree(hs.ROOT / part, tmp_path / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(hs.ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "'cpu'" in out.stderr and "tpu" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copytree(hs.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(hs.ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""
