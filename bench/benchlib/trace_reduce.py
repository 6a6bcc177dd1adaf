"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU the device planes are ``/device:TPU:<n>``; their line ``XLA Ops``
holds one event per operation run on the chip, and ``XLA Modules`` one per
compiled program executed.  The host plane ``/host:CPU`` holds the spans
that the benchmark writes with ``jax.profiler.TraceAnnotation`` around its
calls into each layer.  All events carry start and end in nanoseconds on
one clock.

:func:`reduce_events` does the arithmetic on plain intervals, so it is
tested without a trace; :func:`reduce_file` reads the events out of a file.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the span that the harness holds open over the whole traced window
WINDOW_SPAN = "traced_window"

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float                  # length of the traced window (host)
    busy_s: float                    # union of op intervals, mean per chip
    chips: int
    programs: dict                   # program name -> [executions, device s]
    device_ops: list                 # [[op name, device s]], longest first
    idle_gaps: list                  # [[span name, s]], longest first

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)


def idle_share_percent(run):
    """The per-layer reader shared by the ``device_idle_share.*`` metrics."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def union_ns(intervals) -> tuple[int, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _op_name(name: str) -> str:
    """``%copy.67 = bf16[32,2048]{...} copy(...)`` -> ``copy.67 (copy)``:
    the TPU names each op event by its whole HLO instruction."""
    lhs, eq, rhs = name.partition(" = ")
    kind = re.search(r" ([a-z][\w-]*)\(", rhs) if eq else None
    if kind is None:
        return name
    return f"{lhs.lstrip('%')} ({kind.group(1)})"


def _module_name(name: str) -> str:
    """``jit_kernel(12)`` -> ``jit_kernel``: one entry per
    program, whatever run id the profiler appends."""
    return name.split("(", 1)[0]


def _covering_span(spans, t: float) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside any span"


def reduce_events(ops_by_chip: dict, modules: list, spans: list,
                  window_ns: tuple[int, int]) -> TraceSummary:
    """``ops_by_chip``: chip -> [(name, start_ns, end_ns)] of device ops;
    ``modules``: [(name, start_ns, end_ns)] of program executions on the
    chips; ``spans``: [(name, start_ns, end_ns)] host spans;
    ``window_ns``: the traced window on the same clock.  Intervals are
    clipped to the window."""
    w0, w1 = window_ns
    if w1 <= w0:
        raise ValueError(f"empty traced window {window_ns}")

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                if e > w0 and s < w1]

    busy, per_op = 0, {}
    first_merged = None
    chips = max(len(ops_by_chip), 1)
    for chip in sorted(ops_by_chip):
        ops = clip(ops_by_chip[chip])
        tot, merged = union_ns((s, e) for _, s, e in ops)
        busy += tot
        if first_merged is None:
            first_merged = merged
        for n, s, e in ops:
            n = _op_name(n)
            per_op[n] = per_op.get(n, 0) + (e - s)
    programs: dict = {}
    for n, s, e in clip(modules):
        ent = programs.setdefault(_module_name(n), [0, 0.0])
        ent[0] += 1
        ent[1] += (e - s) * 1e-9
    gaps = []
    edges = [w0] + [t for iv in (first_merged or []) for t in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, _covering_span(spans, (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / chips * 1e-9, chips=chips,
        programs=programs,
        device_ops=[[n, t * 1e-9] for n, t in ops_top],
        idle_gaps=[[n, t * 1e-9] for t, n in gaps[:TOP]])


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_events(path: str, chips: int, span_names):
    """Pull device ops, program executions and the host spans named in
    ``span_names`` out of a trace file; only the first ``chips`` TPU planes
    count."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops_by_chip: dict = {}
    modules: list = []
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            if chip >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops_by_chip[chip] = [(e.name, e.start_ns, e.end_ns)
                                         for e in line.events]
                elif line.name == MODULES_LINE:
                    modules += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name in span_names
                          or e.name == WINDOW_SPAN]
    return ops_by_chip, modules, spans


def reduce_file(path: str, chips: int, span_names) -> TraceSummary:
    """Reduce one trace over the span :data:`WINDOW_SPAN` that it holds;
    a device idle gap is named by the innermost of the host spans
    ``span_names`` (those the run opened) that covers its middle."""
    ops_by_chip, modules, spans = read_events(path, chips, span_names)
    if not any(ops_by_chip.values()):
        raise ValueError(f"no device operation in {path}")
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(window) != 1:
        raise ValueError(f"{len(window)} {WINDOW_SPAN!r} spans in {path}")
    spans = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    return reduce_events(ops_by_chip, modules, spans, window[0])
