"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the chip check, the compile cache, per-layer metric
readers, and the result line.

A cell is one entry of ``workloads``. Its configuration is
``configs/<config>.json`` (with the plain reference ``reference/<family>.py``),
its traffic ``traffic/<traffic>.json``, read by the driver ``<kind>.py`` that
the mix names, its correctness limits ``limits/<workload>.json``, and each
per-layer metric ``metrics/<metric>.py``, a reader with ``read(run)``.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(checkout: str = CHECKOUT) -> dict:
    return _read_json(os.path.join(checkout, "BENCHMARK.json"))


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(workload: str, bench: Optional[dict] = None) -> Cell:
    """The cell named ``workload``, with every file it names."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _read_json(os.path.join(CHECKOUT, configs[w["config"]]["file"]))
    return Cell(
        name=workload, chips=int(w["chips"]), cfg=cfg,
        mix=_read_json(os.path.join(BENCH_DIR, "traffic",
                                    w["traffic"] + ".json")),
        limits=_read_json(os.path.join(BENCH_DIR, "limits",
                                       workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def metric_reader(name: str) -> Callable:
    """``read`` of ``metrics/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in ``peaks.json`` is an error."""
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def accelerator(chips: int):
    """The first ``chips`` TPU devices, or None (with the reason on standard
    error) where JAX finds no TPU or fewer chips."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no devices: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); the "
              "benchmark runs only on a chip", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:chips]


def use_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so only a cell's first run there compiles.

    Eviction is off: with a size limit from the environment, JAX scans the
    directory's access-time files before each write, and on a TPU host one
    missing file made every write fail, so no run found its programs."""
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the programs compiled while it is open (a warm window has 0),
    and the persistent cache's hits and misses over the whole run."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.open = False
        self.cache: dict = {}

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.open and event == self.EVENT:
            self.count += 1

    def _event(self, event: str, **kw) -> None:
        prefix = "/jax/compilation_cache/"
        if event.startswith(prefix):
            key = event[len(prefix):]
            self.cache[key] = self.cache.get(key, 0) + 1

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        jax.monitoring.register_event_listener(self._event)
        return self


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run of a cell."""
    end_to_end: dict                 # end-to-end metric name → value
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    facts: dict                      # what the per-layer readers need


@dataclasses.dataclass
class TracedRun:
    """What a per-layer reader sees."""
    cell: Cell
    trace: object
    facts: dict
    peaks: dict


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: List[Check]
    breakdown: Optional[dict] = None

    def line(self) -> dict:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in self.checks}
        return out

    def check_lines(self) -> List[str]:
        return [f"check {c.name}: {c.value!r} <= {c.limit!r} "
                f"{'ok' if c.ok else 'FAIL'}" for c in self.checks]


def driver(cell: Cell):
    return importlib.import_module(f"bench.{cell.mix['kind']}")


def run_cell(cell: Cell, devices, *, seed: int, seconds: float, trace: bool,
             t0: float, control: bool = False) -> Result:
    """One run of ``cell`` on ``devices``: its driver measures and checks;
    here the metrics of the run's kind are picked and the line is made."""
    from bench.trace import Trace

    use_compile_cache()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        out: Outcome = driver(cell).run(cell, devices, seed=seed,
                                        seconds=seconds, trace_dir=trace_dir,
                                        t0=t0, control=control)
        tr = (Trace.from_dir(trace_dir, out.facts.get("hlo"))
              if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    breakdown = None
    if trace:
        run = TracedRun(cell, tr, out.facts, peaks(dev.device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    return Result(correct=all(c.ok for c in out.checks),
                  attempted=out.attempted, failed=out.failed, metrics=metrics,
                  device=device, checks=out.checks, breakdown=breakdown)
