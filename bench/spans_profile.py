"""Split a cell's sweep by the program's own spans, on the chip.

    python3 bench/spans_profile.py --workload <cell> [<cell> ...] --seed <n>
        [--seconds 4] [--rounds 2] [--keep-trace <path.xplane.pb.gz>]

For each cell, after the cell's set-up (warm-up included), ``--rounds``
rounds of three windows of ``--seconds`` each: tracing off; the profiler
on as a ``--trace 1`` run of ``bench/run.py`` has it (the benchmark's two
spans only); the profiler on with the program's spans on as well
(``repro.runtime.spans``).  The sweep times of the three say what the
profiler and the program's spans cost.  The last traced window is reduced:
self time per span and per group of spans (``GROUPS``) per sweep, the
device's busy time, its time per compiled program, the idle gaps named by
the innermost span over them, the share of idle time under a program span,
and the bytes the scan engine moved per sweep.  Prints one JSON line per
cell; the window's results are checked as ``bench/run.py`` checks them.

``--keep-trace`` also records one warm sweep of the first cell, traced with
the program's spans, and writes its trace there (gzip).  All cells share
one process, so the chip starts once.
"""

import argparse
import gzip
import json
import pathlib
import shutil
import statistics
import sys
import time
import timeit

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

#: the benchmark's own spans around the replay (``kinds/replay_sweep.py``)
ROOTS = ("batch_fault_axes", "cost_program_scenarios")
#: per-layer groups of the program's spans; each reads the group's self
#: time per sweep
GROUPS = {
    "replay_bind_ms_per_sweep": ("replay.prepare", "replay.bind",
                                 "replay.degrade"),
    "replay_results_ms_per_sweep": ("replay.results",),
    "transport_host_ms_per_sweep": ("replay.run", "transport.level",
                                    "transport.waits",
                                    "transport.collective",
                                    "transport.link_consts"),
    "scan_call_ms_per_sweep": ("scan.maxplus", "scan.running_max",
                               "scan.call"),
    "scan_fetch_ms_per_sweep": ("scan.fetch",),
}


def _timed_window(drv, seconds, tracer) -> list:
    """Sweep times of one window (at least one sweep)."""
    times = []
    end = time.perf_counter() + seconds
    n = 0
    while True:
        t = time.perf_counter()
        drv._sweep(n % len(drv.pool), tracer)
        now = time.perf_counter()
        times.append(now - t)
        n += 1
        if now >= end:
            return times


def _quartiles(v) -> dict:
    q = statistics.quantiles(v, n=4) if len(v) > 1 else [v[0]] * 3
    return {"n": len(v), "q1": q[0], "median": q[1], "q3": q[2]}


def _span_off_ns() -> float:
    """Host cost of one span while tracing is off (enter and exit)."""
    from repro.runtime.spans import span

    def one():
        with span("off"):
            pass
    n = 200_000
    return timeit.timeit(one, number=n) / n * 1e9


def _traced(drv, seconds, tracer, program_spans: bool) -> list:
    from repro.runtime import spans
    drv.sweeps.clear()
    tracer.start()
    if program_spans:
        spans.start()
    try:
        return _timed_window(drv, seconds, tracer)
    finally:
        spans.stop()
        tracer.stop()


def reduce_trace(path: str, chips: int, names, sweeps: int) -> dict:
    """The numbers of one traced window of ``sweeps`` sweeps."""
    from benchlib import span_times, trace_reduce
    host = span_times.read_host_spans(path, names)
    window = [(s, e) for n, s, e, _ in host if n == trace_reduce.WINDOW_SPAN]
    if len(window) != 1:
        raise ValueError(f"{len(window)} {trace_reduce.WINDOW_SPAN!r} "
                         f"spans in {path}")
    window = window[0]
    table = span_times.self_times(
        [sp for sp in host if sp[0] != trace_reduce.WINDOW_SPAN], window)
    per = 1e3 / sweeps
    out = {
        "window_s": (window[1] - window[0]) * 1e-9, "sweeps": sweeps,
        "spans_per_sweep": sum(c for c, _, _ in table.values()) / sweeps,
        "spans": {n: [c / sweeps, tot * per, own * per]
                  for n, (c, tot, own) in sorted(table.items())},
        "groups": {g: sum(table.get(n, [0, 0.0, 0.0])[2] for n in members)
                   * per for g, members in GROUPS.items()},
    }
    out["groups"]["roots_self_ms_per_sweep"] = sum(
        table.get(n, [0, 0.0, 0.0])[2] for n in ROOTS) * per
    own = sum(own for _, _, own in table.values())
    sweep = sum(table[n][1] for n in ROOTS if n in table)
    out["sweep_ms"] = sweep * per
    out["self_sum_over_sweep"] = own / sweep if sweep else None
    out["self_sum_over_window"] = own / out["window_s"]
    ops_by_chip, modules, _ = trace_reduce.read_events(path, chips, names)
    if any(ops_by_chip.values()):
        s = trace_reduce.reduce_events(
            ops_by_chip, modules,
            [sp[:3] for sp in host if sp[0] != trace_reduce.WINDOW_SPAN],
            window)
        idle = span_times.idle_intervals(ops_by_chip[min(ops_by_chip)],
                                         window)
        _, under = trace_reduce.union_ns(
            (st, en) for n, st, en, _ in host
            if n not in ROOTS and n != trace_reduce.WINDOW_SPAN)
        idle_ns = sum(b - a for a, b in idle)
        out.update(
            busy_s=s.busy_s, idle_share=s.idle_share,
            programs=s.programs, device_ops=s.device_ops,
            idle_gaps=s.idle_gaps,
            idle_under_program_share=span_times.intersection_ns(
                idle, under) / idle_ns if idle_ns else None)
    return out


def profile_cell(workload: str, seed: int, seconds: float, rounds: int, *,
                 trace_dir, require_chip: bool = True, overrides=None,
                 keep_trace=None, log=print) -> dict:
    import dataclasses

    from benchlib import harness, spec, trace_reduce
    from repro.runtime import spans
    cell = spec.find_cell(workload)
    if overrides:
        cell = dataclasses.replace(
            cell, config={**cell.config, **overrides.get("config", {})},
            traffic={**cell.traffic, **overrides.get("traffic", {})})
    device = harness._device_info(cell.chips, require_chip)
    clock = harness.CompileClock()
    drv = spec.driver(cell.traffic["kind"]).Driver(cell, seed, log=log)
    drv.setup()
    clock.take()
    eng = drv.engine
    tracer = harness.Tracer(True, trace_dir)
    times = {"off": [], "profiler": [], "spans": []}
    for _ in range(rounds):
        drv.sweeps.clear()
        times["off"] += _timed_window(drv, seconds, None)
        times["profiler"] += _traced(drv, seconds, tracer, False)
        b0 = (eng.bytes_in, eng.bytes_out, sum(eng.dispatches.values()))
        times["spans"] += _traced(drv, seconds, tracer, True)
    sweeps = len(drv.sweeps)
    names = set(tracer.names) | spans.names()
    out = {"workload": workload, "seed": seed, "device": device,
           "sweep_s": {k: _quartiles(v) for k, v in times.items()},
           "bytes_in_per_sweep": (eng.bytes_in - b0[0]) / sweeps,
           "bytes_out_per_sweep": (eng.bytes_out - b0[1]) / sweeps,
           "dispatches_per_sweep":
               (sum(eng.dispatches.values()) - b0[2]) / sweeps,
           "compile_events_in_windows": clock.take()[1],
           "span_off_ns": _span_off_ns()}
    out["trace"] = reduce_trace(trace_reduce.latest_xplane(str(tracer.dir)),
                                cell.chips, names, sweeps)
    checks = drv.check()
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    if keep_trace is not None:
        _traced(drv, 0.0, tracer, True)
        path = trace_reduce.latest_xplane(str(tracer.dir))
        pathlib.Path(keep_trace).write_bytes(
            gzip.compress(pathlib.Path(path).read_bytes(), mtime=0))
    shutil.rmtree(tracer.dir, ignore_errors=True)
    drv.release()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--keep-trace")
    args = ap.parse_args(argv)
    from benchlib import harness
    harness._use_cache()
    for i, w in enumerate(args.workload):
        out = profile_cell(
            w, args.seed, args.seconds, args.rounds,
            trace_dir=harness.TRACE_DIR / f"spans-{w}",
            keep_trace=args.keep_trace if i == 0 else None,
            log=lambda m: print(m, flush=True))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
