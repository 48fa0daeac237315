"""The general part of a benchmark run: find a cell's pieces by name, hold
the chip check, time set-up and the window, take the trace, call the
readers and print the result line. It holds no code of any one cell.

A driver (``bench/drivers/<name>.py``, named by the traffic file's
``driver`` key) exposes ``run(cell) -> Outcome``. It builds the program,
warms up every shape its traffic uses, runs its first steps, enters
``cell.window()`` for the measured window, and after the window checks
what the timed path produced against the configuration's plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

from bench import trace as T
from bench.hlo import op_kinds

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, beside its limit: the
    run is correct where every number is finite and at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""
    e2e: Dict[str, float]              # end-to-end metrics of the window
    attempted: int
    failed: int
    checks: List[Check]
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def e2e_names(self) -> List[str]:
        return [m["name"] for m in self.end_to_end
                if self.name in m.get("workloads", [self.name])]

    def layer_names(self) -> List[str]:
        mine = set(self.e2e_names())
        return [m["name"] for m in self.per_layer
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> CellSpec:
    """The cell called ``name`` in ``BENCHMARK.json``, its configuration file
    and its traffic file, found by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    return CellSpec(name, w["chips"], config, traffic, bench["end_to_end"],
                    bench["per_layer"])


def driver_of(spec: CellSpec):
    return importlib.import_module(f"bench.drivers.{spec.traffic['driver']}")


def reference_of(config: Dict[str, Any]):
    return importlib.import_module(f"bench.configs.{config['reference']}")


def reader_of(metric: str) -> Callable:
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks_of(kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def require_devices(chips: int):
    """The devices a cell runs on; raises :class:`NoDevice` off the chip."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Cell:
    """One run of one cell, as a driver sees it."""

    def __init__(self, spec: CellSpec, seed: int, seconds: float,
                 trace: bool, devices, t_start: float):
        self.spec = spec
        self.name = spec.name
        self.config = spec.config
        self.traffic = spec.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.reference = reference_of(spec.config)
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak_bytes = 0
        self.trace_dir: Optional[str] = None

    def span(self, name: str):
        """A host span of the benchmark's own, written into the profiler's
        trace in a traced run (the idle gaps are named by such spans)."""
        if self.trace:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it opens; in a traced run
        the profiler runs for exactly its length; the memory peak is read
        where it closes, before any reference runs."""
        import jax
        w = Window(self.seconds)
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_",
                                              dir=os.environ.get("TMPDIR"))
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=T.profile_options())
        self.setup_s = time.perf_counter() - self.t_start
        w.t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(T.WINDOW_SPAN) if self.trace \
                    else contextlib.nullcontext():
                yield w
                w.t1 = time.perf_counter()
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        self.window_s = w.t1 - w.t0
        self.memory_peak_bytes = memory_peak(self.devices)
        self.t_closed = time.perf_counter()


class Window:
    """The clock of the measured window."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0 = self.t1 = 0.0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def open(self) -> bool:
        return self.elapsed() < self.seconds


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads from."""
    cell: Cell
    outcome: Outcome
    peaks: Dict[str, float]
    trace: Any = None                   # bench.trace.Trace
    window: Optional[tuple] = None      # (start, end) on the trace clock


def result_line(cell: Cell, outcome: Outcome, devices,
                ctx: Context) -> Dict[str, Any]:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": cell.memory_peak_bytes}
    metrics: Dict[str, Dict[str, Any]] = {}
    units = {m["name"]: m["unit"]
             for m in cell.spec.end_to_end + cell.spec.per_layer}
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in outcome.checks) and bool(outcome.checks),
        "attempted": outcome.attempted, "failed": outcome.failed,
        "metrics": metrics, "device": device}
    if cell.trace:
        device["busy_s"] = T.busy(ctx.trace, ctx.window)
        device["window_s"] = (ctx.window[1] - ctx.window[0]) / 1e9
        for name in cell.spec.layer_names():
            value = reader_of(name)(ctx)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": units[name]}
        kinds = {}
        for text in outcome.facts.get("hlo", {}).values():
            kinds.update(op_kinds(text))
        line["breakdown"] = T.breakdown(
            ctx.trace, ctx.window, label=lambda n: kinds.get(n, n))
    else:
        values = dict(outcome.e2e, setup_s=cell.setup_s)
        for name in cell.spec.e2e_names():
            metrics[name] = {"value": float(values[name]),
                             "unit": units[name]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def run_cell(spec: CellSpec, seed: int, seconds: float, trace: bool,
             t_start: float, out=None, err=None) -> int:
    """Run ``spec`` once and print its result line; returns the exit code."""
    out = out or sys.stdout
    err = err or sys.stderr
    try:
        devices = require_devices(spec.chips)
        peaks = peaks_of(devices[0].device_kind)
    except NoDevice as e:
        print(f"bench: {e}; no result", file=err)
        return 1
    cell = Cell(spec, seed, seconds, trace, devices, t_start)
    outcome = driver_of(spec).run(cell)
    if cell.setup_s is None:
        raise RuntimeError(f"driver {spec.traffic['driver']} never opened "
                           f"its window")
    print(f"bench: set-up {cell.setup_s:.3f} s, window {cell.window_s:.3f} s, "
          f"check {time.perf_counter() - cell.t_closed:.3f} s",
          file=err)
    ctx = Context(cell, outcome, peaks)
    if trace:
        ctx.trace = T.load(cell.trace_dir)
        shutil.rmtree(cell.trace_dir, ignore_errors=True)
        ctx.window = T.window_of(ctx.trace)
    line = result_line(cell, outcome, devices, ctx)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return 0


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    enable_cache()
    import warnings
    from repro.kernels.ops import KernelFallbackWarning
    # a kernel that falls back to its jnp oracle takes another path than
    # the one the cell measures
    warnings.simplefilter("error", KernelFallbackWarning)
    try:
        return run_cell(spec, args.seed, args.seconds, bool(args.trace),
                        t_start)
    except Exception:
        traceback.print_exc()
        return 1


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, given to the program through the variable it honours; every
    program is cached, however fast it compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()
