"""One run of one cell: set-up, the measured window, the check, the line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds everything else by name:

  ``bench/configs/<config>.json``    sizes, route, guarantees and limits
  ``bench/traffic/<traffic>.json``   which driver, and its parameters
  ``bench/drivers/<driver>.py``      the loop the window drives
  ``bench/layer_metrics/<m>.py``     the reader of per-layer metric ``m.*``

so a later change adds a cell, a configuration, a traffic mix or a metric
by adding files and entries, and edits none.

A driver module defines ``Cell(cfg, traffic, seed, devices, span)``, whose
constructor is the set-up (data, program, warm-up of every shape the window
uses), with ``evals_per_call``, ``call()`` (one timed call, ending when its
result is ready), ``release()`` (copy what the check needs to the host and
drop the program's state), ``check() -> (checks, failed)`` with
``checks = {name: (value, limit)}``, and ``work()`` (the level, ``p`` and
box counts the rooflines read).  A reader module defines
``read(inputs: LayerInputs) -> float | None``; ``None`` leaves the metric
out of the line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from . import peaks, trace_events

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SetupError(RuntimeError):
    """The run cannot be measured here (no chip, too few chips, a shape
    the configuration does not state)."""


# --- finding things by name --------------------------------------------------

def load_json(path: str) -> dict:
    with open(path if os.path.isabs(path) else os.path.join(ROOT, path)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json("BENCHMARK.json")


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def traffic_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def driver_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "drivers", f"{name}.py")


def reader_file(metric: str) -> str:
    """A per-layer metric ``q.split`` is read by ``layer_metrics/q.py``."""
    return os.path.join(BENCH_DIR, "layer_metrics",
                        f"{metric.split('.')[0]}.py")


def load_module(path: str, prefix: str):
    name = f"bench_{prefix}_{os.path.splitext(os.path.basename(path))[0]}"
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bm: dict, cell: str, kind: str) -> list[dict]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics ``cell``
    reports: those that list it, and those that list no cells (for a
    per-layer metric: when the cell reports the metric it moves)."""
    e2e = {m["name"] for m in cell_metrics(bm, cell, "end_to_end")} \
        if kind == "per_layer" else set()
    out = []
    for m in bm[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


# --- host spans and compile counting -----------------------------------------

@contextlib.contextmanager
def span(name: str):
    """A host span, written into the profiler's trace when one is taken."""
    import jax
    with jax.profiler.TraceAnnotation(trace_events.SPAN_PREFIX + name):
        yield


class CompileCounter:
    """Counts XLA compilations and lowerings while it is active."""

    def __init__(self):
        self.compiles = self.lowerings = 0

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# --- the per-layer readers' inputs -------------------------------------------

@dataclasses.dataclass
class LayerInputs:
    trace: trace_events.Trace
    calls: int              # timed calls in the traced window
    evals: int              # FMM evaluations in the traced window
    counts: np.ndarray      # particles per leaf box
    level: int
    p: int
    peak: dict
    chips: int


# --- one run -----------------------------------------------------------------

def _say(line: str, stream=None) -> None:
    print(line, file=stream or sys.stdout, flush=True)


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        config: dict | None = None) -> dict:
    """Run one cell and return its result line (also printed last).

    ``require_chip=False`` skips the look for a TPU, and ``config``
    replaces the cell's configuration file: the harness's own tests drive a
    whole run on the CPU, at a small size, with them."""
    import jax

    t_entry = time.perf_counter()
    bm = load_benchmark()
    cell = find(bm["workloads"], cell_name, "workload")
    cfg = config or load_json(find(bm["configs"], cell["config"],
                                   "config")["file"])
    traffic = load_json(traffic_file(cell["traffic"]))
    chips = int(cell["chips"])
    n = 1 << cfg["level"]
    _say(f"cell={cell_name} config={cell['config']} "
         f"traffic={cell['traffic']} level={cfg['level']} "
         f"slots={cfg['slots']} tree=({n}, {n}, {cfg['slots']}) "
         f"particles={cfg['particles']} p={cfg['p']} chips={chips} "
         f"seed={seed}")

    devices = jax.devices()
    t_backend = time.perf_counter()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise SetupError(f"JAX finds no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX has "
                         f"{len(devices)}")
    devices = devices[:chips]
    driver = load_module(driver_file(traffic["driver"]), "driver")
    cellrun = driver.Cell(cfg, traffic, seed, devices, span)
    setup_s = time.perf_counter() - t_start
    _say(f"setup: seconds={setup_s!r} imports_s={t_entry - t_start!r} "
         f"backend_s={t_backend - t_entry!r} "
         f"cell_s={t_start + setup_s - t_backend!r}")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    calls = 0
    with CompileCounter() as cc:
        if trace:
            jax.profiler.start_trace(log_dir)
        with span(trace_events.WINDOW_SPAN):
            t0 = time.perf_counter()
            while True:
                cellrun.call()
                calls += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
    _say(f"window: calls={calls} seconds={window_s!r} "
         f"compiles={cc.compiles} lowerings={cc.lowerings}")

    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    cellrun.release()
    gc.collect()
    t_check = time.perf_counter()
    checks, failed = cellrun.check()
    _say(f"check: seconds={time.perf_counter() - t_check!r}")
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(memory)}
    breakdown = None
    if trace:
        try:
            tr = trace_events.load(log_dir)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = trace_events.mean_busy_s(tr)
        device["window_s"] = tr.window_ns / 1e9
        work = cellrun.work()
        inputs = LayerInputs(trace=tr, calls=calls,
                             evals=calls * cellrun.evals_per_call,
                             counts=work["counts"], level=work["level"],
                             p=work["p"], peak=peaks.peak(dev.device_kind),
                             chips=chips)
        values = {m["name"]: load_module(reader_file(m["name"]),
                                         "reader").read(inputs)
                  for m in cell_metrics(bm, cell_name, "per_layer")}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(bm, cell_name, "per_layer")
                   if values[m["name"]] is not None}
        breakdown = trace_events.breakdown(tr)
    else:
        values = {"setup_s": setup_s, traffic["per_call"]: window_s / calls}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bm, cell_name, "end_to_end")}
    result = {"correct": bool(correct), "attempted": calls,
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        _say(f"check {k} {v!r} limit {lim!r}", sys.stderr)
    _say(json.dumps(result))
    return result
