"""Self time of the program's nested host spans in a profiler trace, and
how much of the device's idle time lies under them.

The program opens spans around its calls into each layer
(``repro.runtime.spans``) while tracing is on; they nest on one host
thread line of the trace.  A span's self time is its duration less what
its child spans on the same line cover, so the self times of a line's
spans partition the time its outermost spans cover.  Spans on another
line never subtract.  :func:`self_times` does this on plain intervals, so
it is tested without a trace; :func:`read_host_spans` reads them out of a
file, with the line each lies on.
"""

from __future__ import annotations

from benchlib import trace_reduce


def read_host_spans(path: str, names) -> list:
    """``[(name, start_ns, end_ns, line)]`` of the host events named in
    ``names`` or :data:`trace_reduce.WINDOW_SPAN`; ``line`` is the host
    thread line's name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, line.name)
                    for e in line.events
                    if e.name in names or e.name == trace_reduce.WINDOW_SPAN]
    return out


def self_times(spans, window_ns) -> dict:
    """``{name: [count, total_s, self_s]}`` over ``window_ns`` of spans
    ``(name, start_ns, end_ns, line)``, each clipped to the window.  Spans
    of one line nest (a host thread closes the inner span first)."""
    w0, w1 = window_ns
    by_line: dict = {}
    for name, s, e, line in spans:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            by_line.setdefault(line, []).append((s, -e, name))
    out: dict = {}

    def close(ent):
        name, _, dur, own = ent
        acc = out.setdefault(name, [0, 0.0, 0.0])
        acc[0] += 1
        acc[1] += dur * 1e-9
        acc[2] += own * 1e-9

    for evs in by_line.values():
        evs.sort()
        stack: list = []            # [name, end, duration, self] open
        for s, neg_e, name in evs:
            e = -neg_e
            while stack and stack[-1][1] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3] -= min(e, stack[-1][1]) - s
            stack.append([name, e, e - s, e - s])
        while stack:
            close(stack.pop())
    return out


def intersection_ns(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    ``[start, end]`` intervals (as :func:`trace_reduce.union_ns` merges
    them)."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_intervals(ops, window_ns) -> list:
    """The stretches of ``window_ns`` in which none of the device ops
    ``[(name, start_ns, end_ns)]`` of one chip runs."""
    w0, w1 = window_ns
    _, busy = trace_reduce.union_ns(
        (max(s, w0), min(e, w1)) for _, s, e in ops if e > w0 and s < w1)
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    return [[a, b] for a, b in zip(edges[0::2], edges[1::2]) if b > a]
