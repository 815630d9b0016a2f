"""Shared machinery of one benchmark run.

``run.py`` parses the command line and hands the cell to its traffic kind
(``serving.run`` for the serving kinds, the kind's own ``run`` otherwise).
Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    bench/configs/<config>.json     the deployment (rows, build parameters)
    bench/data/<generator>.py       its seeded data generator
    bench/traffic/<traffic>.json    the traffic mix, naming its ``kind``
    bench/kinds/<kind>.py           the generator (or driver) of that kind
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/peaks.json                device peaks, keyed by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import time

import numpy as np

from bench import statements as stm
from bench.reference import ExactTable, errors

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SAMPLE_ROWS = 100_000          # rows of the seeded sample traffic is vetted on
STREAMS = {"sample": 2, "traffic": 3, "order": 4, "warm": 5}


class BenchError(Exception):
    """The run cannot produce a result (no chip, bad manifest, ...)."""


# ------------------------------------------------------------------ files

def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"missing module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return load_json(path)


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # manifest entries this cell reports
    per_layer: list

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def cell(name: str, man: dict | None = None) -> Cell:
    man = man if man is not None else manifest()
    found = [w for w in man["workloads"] if w["name"] == name]
    if not found:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = [c for c in man["configs"] if c["name"] == w["config"]]
    if not conf:
        raise BenchError(f"workload {name!r} names unknown config "
                         f"{w['config']!r}")
    config = load_json(ROOT / conf[0]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in man["end_to_end"] if mine(m)],
                [m for m in man["per_layer"] if mine(m)])


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), STREAMS[stream]]))


def content_seed(config: dict) -> int:
    """The seed of a cell's statements and arrivals: the configuration's
    ``data_seed``, the same in every run. The run's ``--seed`` only orders
    them (``serving.shuffled``): seeds that drew their own statements read
    a median error that moved by a quarter from seed to seed (PERF.md), so
    they changed the work, and the set the check compares, not its order.
    """
    return int(config["data_seed"])


def generate_table(config: dict, rows: int | None = None) -> dict:
    """The configuration's table, drawn by its generator from its own
    ``data_seed``: the deployment's data, the same in every run. (Tables
    drawn from the run's seed build synopses of different sizes, so they
    would change the work from seed to seed; see PERF.md.)"""
    gen = load_module(BENCH / "data" / f"{config['generator']}.py",
                      f"bench_data_{config['generator']}")
    n = int(rows if rows is not None else config["rows"])
    table = gen.generate(n, int(config["data_seed"]))
    return {c: table[c] for c in config["columns"]}


# ------------------------------------------------------------------ device

def pin_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that nothing is shared with
    another checkout and only a cell's first run there compiles. Call it
    before JAX is imported: JAX reads the variable then, and the program's
    ``use_compile_cache`` takes the directory it names."""
    path = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them. Refuses anything but
    a TPU with at least ``chips`` devices when ``require_tpu``."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"JAX platform is {dev.platform!r}, not 'tpu': "
                         "the benchmark runs only on a TPU")
    if require_tpu and len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak() -> int:
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts, between ``start`` and ``stop``, JAX compilations (backend
    compiles and programs loaded from the persistent cache) and, apart,
    re-traces of already compiled functions (host work, no compile)."""

    def __init__(self):
        import jax

        self.on = False
        self.count = self.traces = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def _duration(self, event, duration, **_):
        if not self.on:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def start(self):
        self.count = self.traces = 0
        self.on = True

    def stop(self) -> int:
        self.on = False
        return self.count


# ------------------------------------------------------------------ sample

class Sample:
    """A seeded uniform sample of the table: literal quantiles come from it
    and generated statements are vetted on it, so set-up never scans the
    whole table per statement."""

    def __init__(self, table: dict, seed: int, rows: int = SAMPLE_ROWS):
        n = len(next(iter(table.values())))
        r = rng(seed, "sample")
        idx = r.choice(n, size=min(rows, n), replace=False)
        self.rows = len(idx)
        self.exact = ExactTable({c: np.asarray(v)[np.sort(idx)]
                                 for c, v in table.items()})
        # A random tenth of the sample: a statement that selects enough of
        # its rows selects enough of the sample, so most are vetted there.
        self.part = ExactTable({c: np.asarray(v)[np.sort(idx[:rows // 10])]
                                for c, v in table.items()})
        self._sorted = {}
        self._decimals = {}
        for c, x in self.exact.num.items():
            fin = x[self.exact.finite[c]]
            self._sorted[c] = np.sort(fin)
            self._decimals[c] = stm.decimals(fin)

    def quantile(self, col: str, q: float) -> float:
        """The ``q`` quantile of ``col``, rounded to the column's own
        quantization (so literals look like the data)."""
        xs = self._sorted[col]
        v = xs[min(len(xs) - 1, int(q * len(xs)))]
        return round(float(v), self._decimals[col])

    def count(self, where) -> int:
        return int(np.count_nonzero(self.exact.mask(where)))

    def selects(self, where, agg: str, rows: int) -> bool:
        """Whether ``where`` selects at least ``rows`` sample rows that hold
        a value of ``agg`` (a column, or ``*``)."""
        for ex in (self.part, self.exact):
            mask = ex.mask(where)
            if agg in ex.finite:
                mask &= ex.finite[agg]
            if np.count_nonzero(mask) >= rows:
                return True
        return False

    def literals(self, col: str, u: np.ndarray) -> np.ndarray:
        """Quantiles ``u`` (array) of ``col``, rounded like the column."""
        xs = self._sorted[col]
        idx = np.minimum((u * len(xs)).astype(int), len(xs) - 1)
        return np.round(xs[idx], self._decimals[col])

    def vetted(self, preds, m: int, rng, q_lo: float, q_hi: float,
               min_rows: int, rounds: int = 64) -> np.ndarray:
        """``m`` literal vectors for the AND of ``preds`` (``[(col, op)]``),
        each literal a quantile drawn uniformly from ``[q_lo, q_hi]``,
        keeping only vectors that select at least ``min_rows`` sample rows.
        """
        out = np.empty((0, len(preds)))
        for _ in range(rounds):
            need = m - len(out)
            if need <= 0:
                break
            u = rng.uniform(q_lo, q_hi, size=(need, len(preds)))
            lits = np.stack([self.literals(c, u[:, p])
                             for p, (c, _) in enumerate(preds)], axis=1)
            keep = counts(self.part, preds, lits) >= min_rows
            rest = np.flatnonzero(~keep)
            keep[rest] = counts(self.exact, preds, lits[rest]) >= min_rows
            out = np.concatenate([out, lits[keep]])
        if len(out) < m:
            raise BenchError(f"cannot draw {m} statements over {preds} that "
                             f"select {min_rows} of {self.rows} sample rows")
        return out[:m]

    def categories(self, col: str) -> list:
        return [str(c) for c in self.exact.coded(col)[0]]


def counts(ex: ExactTable, preds, lits: np.ndarray,
           chunk: int = 256) -> np.ndarray:
    """Rows of ``ex`` selected by the AND of ``preds`` (``[(col, op)]``)
    for each literal vector (row of ``lits``)."""
    ops = {"<": np.less, "<=": np.less_equal, ">": np.greater,
           ">=": np.greater_equal, "=": np.equal, "!=": np.not_equal}
    out = np.empty(len(lits), np.int64)
    for lo in range(0, len(lits), chunk):
        block = lits[lo:lo + chunk]
        mask = np.ones((len(block), ex.n), bool)
        for p, (c, op) in enumerate(preds):
            with np.errstate(invalid="ignore"):
                mask &= ops[op](ex.num[c][None, :], block[:, p:p + 1])
            mask &= ex.finite[c][None, :]
        out[lo:lo + chunk] = mask.sum(axis=1)
    return out


# ------------------------------------------------------------------ checks

def compared(numbers: dict, limits: dict) -> dict:
    """Each number the traffic file gives a limit, beside that limit
    (``value <= limit`` passes)."""
    out = {}
    for name, limit in limits.items():
        if name not in numbers:
            raise BenchError(f"a limit for {name!r}, which the run does not "
                             f"read (it reads {sorted(numbers)})")
        out[name] = {"value": numbers[name], "limit": limit}
    return out


def func_medians(errs: list, stmts: list) -> dict:
    """Median relative error (%) of the statements of each aggregate, keyed
    ``<func>_err_p50_pct`` in lower case (one error per statement)."""
    out = {}
    for func in sorted({st.func for st in stmts}):
        mine = [e for e, st in zip(errs, stmts) if st.func == func]
        out[f"{func.lower()}_err_p50_pct"] = float(np.median(mine))
    return out


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def rel_errors(pairs) -> list[float]:
    """Relative errors (%) of ``(answer, exact)`` pairs; an answer is a
    ``QueryResult``-like object or None (never answered or failed)."""
    out = []
    for res, exact in pairs:
        if res is None:
            est = None if not isinstance(exact, dict) else {}
        elif res.groups is not None:
            est = {str(g): t[0] for g, t in res.groups.items()}
        else:
            est = res.estimate
        out.extend(errors(est, exact))
    return out


# ------------------------------------------------------------------ output

def emit(result: dict, checks: dict, lines: list[str] | None = None):
    """Last lines of stderr: each compared number beside its limit; last
    line of stdout: the result with ``checks`` as its final key."""
    for line in lines or ():
        print(line, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result, allow_nan=False), flush=True)


def finite(x: float, cap: float = 1e12) -> float:
    """JSON has no infinity: a metric beyond any limit prints as ``cap``."""
    return cap if not math.isfinite(x) else x


def now() -> float:
    return time.perf_counter()
