"""Named host spans inside the program, on the profiler's clock.

``with span("replay.bind"): ...`` marks a stretch of host work.  While
tracing is on (:func:`start` … :func:`stop`) a span is a
``jax.profiler.TraceAnnotation``: it lands on the host plane of the same
trace as the device's operations, so an idle stretch of the chip can be
named by the host work under it.  While tracing is off a span is one
shared null context: a flag test, nothing allocated.

The switch is process-wide because the profiler it feeds is.  Spans go
around calls into a layer, never inside a per-column or per-rank loop, so
that a replay opens a few hundred of them.

Names in use (the layer each belongs to, PERF.md §3):

* replay front + bind: ``replay.prepare``, ``replay.bind``,
  ``replay.degrade``, ``replay.results``;
* host transport: ``replay.run``, ``transport.level``,
  ``transport.waits``, ``transport.collective``,
  ``transport.link_consts``;
* scan engine: ``scan.maxplus``, ``scan.running_max``, ``scan.call``,
  ``scan.fetch``;
* expert-parallel decode emission (``serve/sim.py``): ``serve.ep_route``,
  ``serve.ep_emit``.
"""

from __future__ import annotations

import contextlib
import functools

import jax

_NULL = contextlib.nullcontext()
_on = False
_names: set = set()


def span(name: str):
    """A context that records ``name`` as a host span while tracing is
    on, and does nothing otherwise."""
    if not _on:
        return _NULL
    _names.add(name)
    return jax.profiler.TraceAnnotation(name)


def traced(name: str):
    """Decorator: the whole call is one :func:`span` named ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def start() -> None:
    """Turn spans on (call once the profiler runs); forgets the names
    that an earlier traced stretch opened."""
    global _on
    _names.clear()
    _on = True


def stop() -> None:
    """Turn spans off (call before the profiler stops)."""
    global _on
    _on = False


def names() -> frozenset:
    """Every span name opened since the last :func:`start`."""
    return frozenset(_names)
