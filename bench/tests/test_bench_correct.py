"""What decides ``correct`` fails where it should, at a size a test run can
hold: a whole run of a cell (set-up, window, reference) on the CPU, with
the harness's look for a chip skipped, once as the program is, once with
the control in its place and once with each fault the cell can have
planted in the timed path. The limits are the cell's own."""
import dataclasses

import numpy as np
import pytest

from bench import harness as hs
from bench import serving

NAME = "power-dash-steady"
ROWS = 40_000
SECONDS = 2.0


def small(cell):
    """The cell at test size: every row in the synopsis's sample (the
    stated sample is larger than the table), traffic slowed to what the
    CPU serves."""
    cell.traffic["rate_per_s"] = 150
    return cell


def control_params(cell, n_samples: int = ROWS) -> dict:
    """The control's sample, as the same share of the test's sample as
    the configuration's control is of the stated one."""
    share = (cell.config["control"]["build_params"]["n_samples"]
             / cell.config["build_params"]["n_samples"])
    return {"n_samples": max(1, int(n_samples * share))}


def run_cell(seed: int, control: bool = False, mode: str = "numpy",
             rate: float | None = None) -> tuple[dict, list]:
    """The compared numbers and the report lines of one small run."""
    cell = small(hs.cell(NAME))
    if rate is not None:
        cell.traffic["rate_per_s"] = rate
    kind = hs.load_module(hs.BENCH / "kinds" / f"{cell.kind}.py",
                          "bench_kind_" + cell.kind)
    params = control_params(cell) if control else {"n_samples": ROWS}
    _, checks, lines = serving.run(cell, kind, seed, SECONDS, False,
                                   hs.now(), rows=ROWS, mode=mode,
                                   build_params=params, require_tpu=False,
                                   check_workers=2)
    return checks, lines


def altered_answers(monkeypatch):
    """Every answer the engine produces is 25 % too large."""
    from repro.core.query import QueryEngine

    orig = QueryEngine.execute_plan

    def execute_plan(self, plan, *a, **kw):
        res = orig(self, plan, *a, **kw)
        if res.estimate is None:
            return res
        return dataclasses.replace(res, estimate=res.estimate * 1.25)

    monkeypatch.setattr(QueryEngine, "execute_plan", execute_plan)


def lost_answers(monkeypatch):
    """Half the statements fail in execution, every time they are tried."""
    from repro.core.query import QueryEngine

    orig = QueryEngine.execute_plan

    def execute_plan(self, plan, *a, **kw):
        if hash(repr(plan.tree)) % 2:
            raise RuntimeError("planted fault")
        return orig(self, plan, *a, **kw)

    monkeypatch.setattr(QueryEngine, "execute_plan", execute_plan)


def test_sound_run_is_correct_and_compiles_nothing_in_the_window():
    # The fused path in its jitted reference form: the warm-up has to have
    # compiled every launch the window makes.
    checks, lines = run_cell(5, mode="ref")
    assert hs.passed(checks), checks
    assert checks["window_compilations"]["value"] == 0
    assert any(line.startswith("window: compilations=0 ") for line in lines)


def test_control_is_refused():
    checks, _ = run_cell(5, control=True)
    assert not hs.passed(checks), checks


def after_warmup(monkeypatch, fault):
    """Plant ``fault`` once set-up has warmed the server: the window runs
    broken, the warm-up as the program is."""
    warm = serving.warm

    def warm_then_break(*a, **kw):
        sent = warm(*a, **kw)
        fault(monkeypatch)
        return sent

    monkeypatch.setattr(serving, "warm", warm_then_break)


@pytest.mark.parametrize("fault", [altered_answers, lost_answers])
def test_fault_is_refused(fault, monkeypatch):
    after_warmup(monkeypatch, fault)
    checks, _ = run_cell(5)
    assert not hs.passed(checks), checks


def test_fault_in_the_fused_launch_is_refused(monkeypatch):
    """Each fused launch hands back no weightings (its output lost), while
    per-query execution is sound. The median sees a fault of the fused path
    in proportion to that path's share of the statements: at 1,000
    statements/s the CPU's waves group about two thirds of them."""
    from repro.core.fastpath import FastPath

    orig = FastPath.batch

    def batch(self, *a, **kw):
        out = orig(self, *a, **kw)
        if out is None:
            return out
        return [tuple(np.zeros_like(w) for w in triple) for triple in out]

    after_warmup(monkeypatch, lambda mp: mp.setattr(FastPath, "batch", batch))
    checks, lines = run_cell(5, mode="ref", rate=1000)
    assert not hs.passed(checks), (checks, lines)


def test_window_compilation_is_refused(monkeypatch):
    """A warm-up that sends nothing leaves the window to compile."""
    import jax

    jax.clear_caches()          # nothing compiled by an earlier test
    monkeypatch.setattr(serving, "warm", lambda *a, **kw: 0)
    checks, _ = run_cell(5, mode="ref")
    assert checks["window_compilations"]["value"] > 0
    assert not hs.passed(checks), checks
