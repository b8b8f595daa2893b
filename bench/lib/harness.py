"""The run harness: cell lookup, device checks, clocks, spans, result line.

A cell is one ``workloads`` entry of ``BENCHMARK.json``.  Its files are
found by name: ``bench/configs/<config>.json`` (the model or learner as it
is run) beside its plain reference ``bench/configs/<config>.ref.py``,
``bench/traffic/<traffic>.json`` (the generator's parameters),
``bench/workloads/<cell>.json`` (the driver, the engine settings and the
limits of the correctness check), ``bench/drivers/<driver>.py`` and one
reader ``bench/metrics/<metric>.py`` per per-layer metric.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------

def read_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


def load_file_module(path: pathlib.Path, name: str):
    """Import a Python file by path (file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one cell's run reads, resolved from its name."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    workload: dict
    end_to_end: List[str]
    per_layer: List[str]

    def reference(self):
        return load_file_module(BENCH / "configs" / f"{self.config_name}.ref.py",
                                f"bench_ref_{self.config_name}")

    def driver(self):
        return importlib.import_module(
            f"bench.drivers.{self.workload['driver']}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell: str, bench: Optional[dict] = None) -> Cell:
    bench = benchmark() if bench is None else bench
    entries = {w["name"]: w for w in bench["workloads"]}
    if cell not in entries:
        raise KeyError(f"no cell {cell!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    w = entries[cell]
    return Cell(
        name=cell, chips=int(w["chips"]), config_name=w["config"],
        config=read_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        workload=read_json(BENCH / "workloads" / f"{cell}.json"),
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if applies(m, cell)],
        per_layer=[m["name"] for m in bench["per_layer"]
                   if applies(m, cell)])


def metric_reader(name: str) -> Callable:
    mod = load_file_module(BENCH / "metrics" / f"{name}.py",
                           f"bench_metric_{name.replace('.', '_')}")
    return mod.read


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices JAX found; raises
    :class:`NoChip` without a TPU or with fewer chips than asked for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device so far."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def use_compile_cache() -> str:
    """JAX's persistent compilation cache in a fixed directory of the
    checkout (or ``$JAX_COMPILATION_CACHE_DIR``), holding every program,
    however quick to compile, so that only a cell's first run compiles."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts and times JAX's backend compiles, split by whether the
    persistent cache served them, between :meth:`take` calls."""

    def __init__(self):
        import jax

        self._flag: Optional[str] = None
        self.reset()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def reset(self) -> None:
        self.secs = 0.0
        self.count = 0
        self.missed: List[Tuple[str, float]] = []
        self.hits = 0

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._flag = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            self._flag = "miss"

    def _on_dur(self, event, duration, **kw):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        self.secs += duration
        self.count += 1
        if self._flag == "hit":
            self.hits += 1
        else:
            self.missed.append((str(kw.get("fun_name", "?")), duration))
        self._flag = None

    def take(self) -> dict:
        missed: Dict[str, list] = {}
        for name, secs in self.missed:
            acc = missed.setdefault(name, [0, 0.0])
            acc[0] += 1
            acc[1] += secs
        out = {"compiles": self.count, "compile_s": self.secs,
               "cache_hits": self.hits, "missed": missed}
        self.reset()
        return out


# ---------------------------------------------------------------------------
# spans: harness host spans, on the profiler's clock when tracing
# ---------------------------------------------------------------------------

class Spans:
    """Host spans the harness opens around calls into the program.

    Each span is kept in memory as ``(name, start, end)`` on the
    ``perf_counter`` clock and, while a trace is being recorded, also
    written into it as a ``bench.<name>`` annotation."""

    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.spans: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def of(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name]


# ---------------------------------------------------------------------------
# one run's outcome
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a driver hands back to ``run.py``."""

    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Check]
    device: dict
    layer: dict = dataclasses.field(default_factory=dict)
    summary: object = None              # trace.Summary of a traced run
    error: Optional[str] = None         # the check could not be made

    @property
    def correct(self) -> bool:
        return (self.error is None and bool(self.checks)
                and all(c.ok for c in self.checks.values()))


def quantile(values, q: float) -> float:
    """The ``q`` quantile (linear interpolation), as numpy's default."""
    import numpy as np

    return float(np.quantile(np.asarray(values, np.float64), q))


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def check_lines(checks: Dict[str, Check]) -> List[str]:
    return [f"check {'ok  ' if c.ok else 'FAIL'} {name} {c.value!r} "
            f"limit {c.limit!r}" for name, c in checks.items()]
