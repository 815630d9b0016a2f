"""What decides ``correct`` in the rebuild cell fails where it should: a
whole run at test size on the CPU, as the program is, with the control in
its place, and with each fault a build can have planted in it."""
import pytest

from bench import harness as hs
from bench.tests.test_bench_correct import control_params

NAME = "power-rebuild"
ROWS = 600_000        # a year of minutes, so every probe's month filter bites
SAMPLE = 40_000        # the cell's own limits hold at this size too


def run_cell(seed: int, params: dict) -> dict:
    cell = hs.cell(NAME)
    kind = hs.load_module(hs.BENCH / "kinds" / "rebuild.py",
                          "bench_kind_rebuild")
    _, checks, _ = kind.run(cell, seed, 1.0, False, hs.now(), rows=ROWS,
                            build_params=params, require_tpu=False,
                            check_workers=2)
    return checks


def altered_synopsis(monkeypatch):
    """Every value summary of the 1-D histograms the build produces (bin
    midpoints, weighted centres, extrema) is 30 % too large."""
    import repro.aqp.engine as engine

    orig = engine.build_pairwise_hist

    def build(*a, **kw):
        ph = orig(*a, **kw)
        ph.hists = [h._replace(**{f: getattr(h, f) * 1.3 for f in
                                  ("c", "cminus", "cplus", "vmin", "vmax")})
                    for h in ph.hists]
        return ph

    monkeypatch.setattr(engine, "build_pairwise_hist", build)


def unchanged_synopsis(monkeypatch):
    """A rebuild that returns at once, leaving the synopsis in place."""
    from repro.aqp.engine import AQPFramework

    monkeypatch.setattr(AQPFramework, "ingest_compressed",
                        lambda self, compressed, columns: self)


def test_sound_run_is_correct():
    checks = run_cell(5, {"n_samples": SAMPLE})
    assert hs.passed(checks), checks


def test_control_is_refused():
    checks = run_cell(5, control_params(hs.cell(NAME), SAMPLE))
    assert not hs.passed(checks), checks


@pytest.mark.parametrize("fault", [altered_synopsis, unchanged_synopsis])
def test_fault_is_refused(fault, monkeypatch):
    fault(monkeypatch)
    checks = run_cell(5, {"n_samples": SAMPLE})
    assert not hs.passed(checks), checks
