"""One cell's run: the device check, the set-up, the measured window,
the trace, the comparison with the plain reference, and the result line.

A driver (``bench/drivers/<driver>.py``) exposes ``run(h)``: it builds
its inputs from ``h.seed``, warms up, runs its timed loop inside
``with h.window():``, calls ``h.read_memory()`` once the window has
closed, frees the program's state, compares with the reference and
returns a :class:`Result`.  The harness adds ``setup_s``, reduces the
trace under ``--trace 1`` and prints the line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Dict, List, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: the benchmark's own scratch inside the checkout (git-ignored): the
#: empty tune cache the program is pointed at, and the trace of a
#: ``--trace 1`` run, deleted once it is read
OUT = os.path.join(ROOT, "bench_out")
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE = os.path.join(ROOT, ".jax_cache")


class DeviceError(RuntimeError):
    """No listed accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run is
    correct only where every ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Result:
    #: end-to-end metrics measured in the window: name -> (value, unit)
    metrics: Dict[str, Tuple[float, str]]
    attempted: int
    failed: int
    checks: List[Check]
    #: counts for the per-layer readers (work done in the traced window)
    work: dict = dataclasses.field(default_factory=dict)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(workload, config) dicts of a cell, found by the cell's name."""
    workload = load_json("workloads", f"{name}.json")
    return workload, load_json("configs", f"{workload['config']}.json")


def driver_module(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def metric_readers() -> dict:
    """{name: module} for every ``bench/metrics/<name>.py``; a metric's
    name may hold dots, so each file is loaded by its path."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        if name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out


def peaks_for(kind: str) -> dict:
    """The peaks of a device kind; a kind not in the table is an error."""
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in bench/peaks.json "
                          f"(listed: {sorted(table)})")
    return table[kind]


def prepare_env() -> None:
    """Before JAX is imported: the compile cache in the checkout, an
    empty tune cache (no leftover file decides what runs), and the
    program's sources on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    os.makedirs(OUT, exist_ok=True)
    tune = os.path.join(OUT, "tune.json")
    if os.path.exists(tune):
        os.remove(tune)
    os.environ["REPRO_TUNE_CACHE"] = tune
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def find_devices(chips: int):
    """The first ``chips`` accelerators and their peaks.  Refuses a CPU,
    a device kind missing from the peaks table, and too few chips."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise DeviceError("JAX found no accelerator (platform 'cpu')")
    peaks = peaks_for(devs[0].device_kind)
    if len(devs) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX found "
                          f"{len(devs)}")
    return devs[:chips], peaks


class Harness:
    """What a driver sees of the harness."""

    def __init__(self, workload, config, *, seed, seconds, trace, t_start,
                 devices, peaks, control=False):
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.t_start, self.devices, self.peaks = t_start, devices, peaks
        self.control = bool(control)
        self.setup_s = None
        self.memory_peak_bytes = 0
        # one per process: concurrent test runs must not share it
        self.trace_dir = os.path.join(OUT, f"trace-{os.getpid()}")

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def window_seconds(self) -> float:
        """How long the timed loop runs: ``--seconds``, or under
        ``--trace 1`` the short steady stretch that is traced."""
        if self.trace:
            return min(self.seconds,
                       float(self.traffic.get("trace_seconds", self.seconds)))
        return self.seconds

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it begins; under
        ``--trace 1`` the profiler records it, Python tracer off."""
        import jax

        from bench import trace as trace_lib
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self.span(trace_lib.WINDOW_SPAN):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()

    def read_memory(self) -> None:
        """Peak device memory on the fullest chip, read once the window
        has closed and before the reference runs."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak


def _num(x):
    x = float(x)
    return x if math.isfinite(x) else None


def run_cell(workload, config, *, seed, seconds, trace, t_start, devices,
             peaks, control=False) -> dict:
    """Run one cell and return its result line (a dict, keys in order)."""
    from bench import trace as trace_lib

    h = Harness(workload, config, seed=seed, seconds=seconds, trace=trace,
                t_start=t_start, devices=devices, peaks=peaks,
                control=control)
    res = driver_module(workload["driver"]).run(h)
    correct = bool(res.checks) and all(c.ok for c in res.checks) \
        and res.failed == 0
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": h.memory_peak_bytes}
    line = {"correct": correct, "attempted": int(res.attempted),
            "failed": int(res.failed)}
    breakdown = None
    if trace:
        summary = trace_lib.Summary(trace_lib.load(h.trace_dir))
        shutil.rmtree(h.trace_dir, ignore_errors=True)
        ctx = SimpleNamespace(trace=summary, work=res.work, peaks=peaks,
                              end_to_end=set(res.metrics),
                              driver=workload["driver"])
        metrics = {}
        for name, mod in metric_readers().items():
            value = mod.read(ctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": mod.UNIT}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
    else:
        metrics = {name: {"value": float(v), "unit": unit}
                   for name, (v, unit) in res.metrics.items()}
        metrics["setup_s"] = {"value": h.setup_s, "unit": "s"}
    line["metrics"] = metrics
    line["device"] = device
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": _num(c.value), "limit": c.limit}
                      for c in res.checks}
    return line


def emit(line: dict) -> None:
    """Each number compared beside its limit as the last lines on
    standard error, then the result as the last line on standard out."""
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
