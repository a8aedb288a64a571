"""One run of one cell: set-up, the measured window, the traced window's
reduction, the correctness check, and the result line.

A driver (``drivers/<traffic["driver"]>.py``) owns what is particular to
a kind of traffic.  It exposes ``Driver(config, traffic, seed, devices)``
with ``setup()``, ``window(seconds) -> dict``, ``programs()`` (the
compiled programs the window ran), ``free()`` and
``checks(control=False) -> list[Check]``.  ``window`` returns the
cell's end-to-end values under their metric names in ``"metrics"``,
``"attempted"``/``"failed"`` counts, and ``"counters"`` for the
per-layer readers (``metrics/<name>.py``, each a ``read(ctx)``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


# the traced run measures at most this long on one chip, and this over
# the number of chips on several (a trace holds every chip's ops): its
# trace is read within the run's time limit and stays a few hundred MB
# on disk
TRACE_SECONDS = 10.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Check:
    """One compared number: the run is correct only if value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def load_module(path: str, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(
        name or "chipbench_" + os.path.basename(path)[:-3].replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def find_cell(workload: str, root: str = ROOT) -> Dict[str, Any]:
    """The workload entry of BENCHMARK.json with its configuration and
    traffic files loaded, and the metric entries that apply to it."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[cell["config"]]
    return dict(
        cell=cell,
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       cell["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"]
                    if workload in m.get("workloads", [workload])],
        per_layer=[m for m in bench["per_layer"]
                   if workload in m.get("workloads", [workload])],
        run_seconds=bench["run_seconds"])


def require_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's first device is "
                            f"{devices[0].platform!r}; this benchmark runs "
                            "only on a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} TPU chips, JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def configure_jax(root: str = ROOT) -> str:
    """Persistent compile cache at a fixed directory inside the checkout,
    every program cached whatever its compile time."""
    import jax

    cache = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileMeter:
    """Backend compile seconds and persistent-cache hits (listeners on
    jax.monitoring; copied from the repo's chip smoke script)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.secs, self.compiles, self.hits)


def span(name: str):
    """Host span on the profiler's clock (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name)


def bytes_in_use(devices) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def memory_peak_bytes(devices, live_bytes: int, programs, log=print) -> int:
    """Peak device memory of the fullest chip: the allocator's own peak,
    or, where larger, the bytes live when the window began plus the
    largest working set of the window's programs (temporaries, and
    outputs not written over donated inputs) by the compiler's
    ``memory_analysis``.  The allocator's peak need not count the
    temporaries a program runs with."""
    alloc = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices)
    work = 0
    for p in programs:
        m = p.memory_analysis()
        work = max(work, int(m.temp_size_in_bytes + m.output_size_in_bytes
                             - m.alias_size_in_bytes))
    log(f"memory: allocator peak {alloc}, live at the window's start "
        f"{live_bytes}, largest program working set {work}")
    return max(alloc, live_bytes + work)


def _tree_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def run_cell(found: Dict[str, Any], seed: int, seconds: float, trace: bool,
             devices, t_start: float, control: bool = False,
             trace_dir: Optional[str] = None,
             keep_trace: bool = False, log=print) -> Dict[str, Any]:
    """Drive one run; returns the result object (without printing)."""
    import jax

    from . import kernels
    from . import peaks as peaks_mod
    from . import trace as trace_mod

    cell, config, traffic = found["cell"], found["config"], found["traffic"]
    drv_mod = load_module(os.path.join(BENCH_DIR, "drivers",
                                       traffic["driver"] + ".py"))
    meter = CompileMeter()
    drv = drv_mod.Driver(config, traffic, seed, devices)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    before = meter.snapshot()
    log(f"setup_s {setup_s:.4f} (compile {before[0]:.2f} s in "
        f"{before[1]} programs, {before[2]} cache hits)")

    # collect now, and no collection inside the window: a collector
    # pause there would read as the program's time
    gc.collect()
    gc.disable()
    live_bytes = bytes_in_use(devices)
    tdir = None
    if trace:
        tdir = trace_dir or os.path.join(
            ROOT, ".bench_out", "trace", cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        jax.profiler.start_trace(tdir)
    if trace:
        seconds = min(seconds, TRACE_SECONDS / len(devices))
    with span("window") if trace else contextlib.nullcontext():
        out = drv.window(seconds)
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t0:.2f} s: "
            f"{_tree_bytes(tdir)} bytes")
    gc.enable()
    after = meter.snapshot()
    window_compiles = after[1] - before[1]
    log(f"window: {out['window_s']:.4f} s, compiles inside it "
        f"{window_compiles} ({after[0] - before[0]:.3f} s)")
    programs = drv.programs()
    mem_peak = memory_peak_bytes(devices, live_bytes, programs, log=log)

    dev = devices[0]
    result: Dict[str, Any] = {}
    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    summary = None
    if trace:
        t0 = time.perf_counter()
        raw: Dict[str, str] = {}
        summary = trace_mod.reduce(trace_mod.load(tdir, raw))
        log(f"trace reduced in {time.perf_counter() - t0:.2f} s")
        shutil.rmtree(tdir, ignore_errors=True)
        if keep_trace:
            # the reduction and the kernels' HLO text, not the raw trace
            os.makedirs(tdir, exist_ok=True)
            with open(os.path.join(tdir, "summary.json"), "w") as fh:
                json.dump({"top_ops": summary.top_ops(60),
                           "gaps": summary.top_gaps(30),
                           "busy_s": summary.busy_s,
                           "window_s": summary.window_s,
                           "kernels": raw}, fh, indent=1)
        device["busy_s"] = summary.busy_mean_s
        device["window_s"] = summary.window_s
        calls = kernels.merge(kernels.pallas_calls(p.as_text())
                              for p in programs)
        log("Pallas calls: " + json.dumps(calls, sort_keys=True))
        ctx = dict(summary=summary, counters=out.get("counters", {}),
                   config=config, traffic=traffic, cell=cell,
                   kernels=calls, peaks=peaks_mod.peaks(dev.device_kind),
                   devices=devices)
        for m in found["per_layer"]:
            reader = load_module(os.path.join(BENCH_DIR, "metrics",
                                              m["name"] + ".py"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in found["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    del programs
    drv.free()
    t0 = time.perf_counter()
    checks: List[Check] = drv.checks(control=control)
    checks.append(Check("failed_answers", float(out["failed"]), 0.0))
    log(f"reference check took {time.perf_counter() - t0:.2f} s")
    log("readings " + json.dumps(getattr(drv, "readings", {})))
    result["correct"] = all(c.ok for c in checks)
    result["attempted"] = int(out["attempted"])
    result["failed"] = int(out["failed"])
    result["metrics"] = metrics
    result["device"] = device
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
    result["window_compiles"] = window_compiles
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
