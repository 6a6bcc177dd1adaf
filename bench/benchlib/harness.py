"""One run of one cell: set-up, the measured window, the check, the result.

The run is driven by the cell's traffic ``kind`` (``benchlib/kinds``); the
harness owns what every kind shares: the device check, the compilation
cache, the compile clock, the tracer, the per-layer readers and the result
line.  :func:`run_cell` is what ``bench/run.py`` calls; tests call it on the
CPU with ``require_chip=False`` and smaller sizes in ``overrides``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import shutil
import sys
import time

import jax

from benchlib import spec as spec_mod
from benchlib import trace_reduce

CACHE_DIR = spec_mod.ROOT / ".jax_cache"
TRACE_DIR = spec_mod.ROOT / ".bench_trace"
#: a ``--trace 1`` run measures a window of at most this many seconds, all
#: of it under the profiler (a whole window's trace would be too large)
TRACE_SECONDS = 8.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Counts JAX's compile events (tracing, lowering, backend compile or
    cache fetch) and their seconds between two :meth:`take` calls."""

    def __init__(self):
        self.secs = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs
            self.events += 1

    def take(self) -> tuple[float, int]:
        out = (self.secs, self.events)
        self.secs, self.events = 0.0, 0
        return out


class Tracer:
    """Host spans, and the profiler over the whole window of a traced run.
    With tracing off every span is a null context.  ``names`` holds every
    span name opened under the profiler, for the trace reduction."""

    def __init__(self, on: bool, trace_dir):
        self.on = on
        self.dir = trace_dir
        self.active = False
        self.names: set = set()
        self._win = None

    def span(self, name: str):
        if self.active:
            self.names.add(name)
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def start(self):
        if not self.on:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        # no Python tracer (it records every call: a large file, and a
        # host slowed several-fold); the spans are host annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._win = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._win.__enter__()
        self.active = True

    def stop(self):
        """After the window: writing the trace takes seconds, so it never
        happens inside it."""
        if self.active:
            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


@dataclasses.dataclass
class RunData:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""
    cell: spec_mod.Cell
    peaks: dict
    window_s: float          # host clock
    counters: dict           # the driver's counts over the window
    samples: dict            # the driver's host-clock samples in the window
    trace: trace_reduce.TraceSummary | None


def _device_info(chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(chips: int) -> int | None:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _use_cache():
    """A fixed directory inside the checkout, so that only the first run
    of a cell there compiles; every program is kept, however quick."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True, overrides=None,
             variant: str | None = None, log=print) -> dict:
    """Set up, measure and check one cell; returns the result line.

    ``overrides`` ({"config": {...}, "traffic": {...}}) replaces keys of the
    cell's files (smaller sizes for a CPU rehearsal); ``variant`` selects a
    driver's control path (``bench/control.py``)."""
    cell = spec_mod.find_cell(workload)
    if overrides:
        cell = dataclasses.replace(
            cell, config={**cell.config, **overrides.get("config", {})},
            traffic={**cell.traffic, **overrides.get("traffic", {})})
    device = _device_info(cell.chips, require_chip)
    peaks = spec_mod.peaks(device["kind"]) if require_chip else None
    if require_chip:
        _use_cache()
    clock = CompileClock()
    tracer = Tracer(trace, TRACE_DIR / workload)
    drv = spec_mod.driver(cell.traffic["kind"]).Driver(
        cell, seed, variant=variant, peaks=peaks, log=log)
    drv.setup()
    setup_s = time.perf_counter() - t0
    compile_s, compile_events = clock.take()
    log(f"setup: {setup_s} s, of which {compile_s} s in {compile_events} "
        f"compile events")

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    tracer.start()
    c0 = dict(drv.counters())
    w0 = time.perf_counter()
    e2e = drv.window(seconds, tracer)
    window_s = time.perf_counter() - w0
    tracer.stop()
    counters = {k: v - c0.get(k, 0) for k, v in drv.counters().items()}
    _, window_compiles = clock.take()
    log(f"window: {window_s} s, {window_compiles} compile events inside it "
        f"(should be 0); counters {counters}")
    device["memory_peak_bytes"] = _memory_peak(cell.chips)

    summary = None
    if trace:
        summary = trace_reduce.reduce_file(
            trace_reduce.latest_xplane(str(tracer.dir)), cell.chips,
            tracer.names)
        shutil.rmtree(tracer.dir, ignore_errors=True)   # tens of MB
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    drv.release()
    checks = drv.check()
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if trace:
        data = RunData(cell=cell, peaks=peaks or {}, window_s=window_s,
                       counters=counters, samples=drv.samples,
                       trace=summary)
        metrics = {}
        for m in cell.per_layer:
            v = spec_mod.reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    out = {"correct": correct, "attempted": drv.attempted,
           "failed": drv.failed, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return out


def main(args, t0: float) -> int:
    def log(msg):
        print(msg, flush=True)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t0=t0, log=log)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    except spec_mod.SpecError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
