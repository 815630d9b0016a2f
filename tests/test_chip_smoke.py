"""``chip_smoke.py`` off the chip: it refuses the CPU, and its phases and
checks pass at a tiny size with the Pallas kernels interpreted."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_before_any_phase(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert run.returncode != 0
    assert "'cpu'" in run.stderr and "'tpu'" in run.stderr
    assert "phase=" not in run.stdout
    for line in run.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_phases_pass_interpreted(smoke, monkeypatch, capsys):
    from repro.core.types import BuildParams

    monkeypatch.setattr(smoke, "N_GENERATED", 40)
    monkeypatch.setattr(smoke, "WAVE", 16)
    smoke.run_phases(n_rows=10_000, params=BuildParams(n_samples=2_000),
                     mode="pallas")
    lines = capsys.readouterr().out.splitlines()
    phases = [ln.split()[0] for ln in lines if ln.startswith("phase=")]
    assert phases == ["phase=ingest"] * 2 + ["phase=serve"] * 2 + [
        "phase=construction"]
    serve = [ln for ln in lines if ln.startswith("phase=serve")]
    assert all("wave_errors=0" in ln and "outside_tol=0" in ln
               for ln in serve)
    assert "group_by=1" in serve[1]


@pytest.mark.parametrize("got,want,bad", [
    (1.0, 1.0 + 5e-5, 0),                  # inside rtol=1e-4
    (1.0, 1.0 + 5e-4, 3),                  # outside, in all three fields
    (None, 1.0, 3),                        # a null where the host has one
    ({"AA": 1.0}, {"AA": 1.0, "DL": 2.0}, 4),   # a missing group
])
def test_compare_counts_answers_outside_tolerance(smoke, got, want, bad):
    # compare() is what gates the kernel's answers against mode="numpy".
    from repro.core.query import QueryResult

    def res(v):
        if isinstance(v, dict):
            return QueryResult(None, None, None,
                               groups={k: (x, x, x) for k, x in v.items()})
        return QueryResult(v, v, v)

    assert smoke.compare([res(got)], [res(want)])[0] == bad
