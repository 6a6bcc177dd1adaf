"""Pluggable scan-engine seam for the compiled executors (DESIGN.md §2.5).

The two kernels every compiled replay spends its time in — the segmented
max-plus scan and the segmented running maximum of
:mod:`repro.core.exanet.sim` — are pure array programs over a
``(k, *batch)`` layout with data-independent combine masks.  That makes
them retargetable: this module defines the engine interface the
:class:`~repro.core.exanet.exec_compiled.VecTransport` kernels call
through, with two implementations:

* :class:`NumpyScanEngine` (``engine="numpy"``, the default) — delegates
  to the in-place masked-ufunc scans in ``sim.py``.  No dependencies
  beyond NumPy; the reference for the ≤1e-9 agreement tests.
* :class:`JaxScanEngine` (``engine="jax"``) — the same Hillis-Steele
  passes as ``jax.jit``-compiled kernels over the ``(k, columns)``
  layout, on whatever device jax picks (the TPU when one is attached).
  Kernels run under the *scoped* ``jax.enable_x64(True)`` context — the
  compiled executor is held to ≤1e-9 agreement with the interpreter,
  which float32 cannot meet — without flipping the process-global x64
  flag (other jax users in the same process, e.g. the Layer-B models,
  keep their own precision defaults).  An instance counts its kernel
  dispatches and records the devices its outputs lived on, so a caller
  that passes its own instance can see what one replay sent to the
  device.

Engines are stateless beyond caches and counters, so one instance serves
every compiled program; executors resolve a per-call ``engine=`` argument
through :func:`resolve_engine` (``None`` → numpy).  The combine masks
arrive as the precomputed ``takes`` lists of
:func:`~repro.core.exanet.sim.scan_take_masks` — shift offsets are
static per stage (they key the jitted kernel cache), masks are traced
operands.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.exanet.sim import (segmented_maxplus_scan,
                                   segmented_running_max)
from repro.runtime.spans import span


class NumpyScanEngine:
    """The default engine: sim.py's in-place masked-ufunc scans."""

    name = "numpy"

    def maxplus_scan(self, D, T, takes):
        """Segmented max-plus scan; may clobber ``D``/``T`` (callers pass
        freshly-built per-stage arrays)."""
        return segmented_maxplus_scan(D, T, None, 0, takes=takes,
                                      copy=False)

    def running_max(self, v, takes):
        return segmented_running_max(v, takes)


@functools.lru_cache(maxsize=None)
def _maxplus_kernel(shifts: tuple):
    """Jitted max-plus kernel over ``(k, columns)`` for one static shift
    sequence.  One Hillis-Steele pass composes ``(D1,T1) then (D2,T2)``
    into ``(D1+D2, max(T1+D2, T2))`` where the ``(k - s, 1)`` take mask
    allows.  The kernel works on the 2-D layout directly: ``jax.vmap`` of
    the per-column form over a single column is miscompiled by the CPU
    backend of jax 0.9.0.  The function's name is the compiled program's
    (``jit_maxplus_scan``), which a profiler trace shows per launch."""

    def maxplus_scan(D, T, masks):
        for s, m in zip(shifts, masks):
            T = T.at[s:].set(jnp.where(
                m, jnp.maximum(T[:-s] + D[s:], T[s:]), T[s:]))
            D = D.at[s:].set(jnp.where(m, D[:-s] + D[s:], D[s:]))
        return D, T

    return jax.jit(maxplus_scan)


@functools.lru_cache(maxsize=None)
def _running_max_kernel(shifts: tuple):
    """Jitted segmented running maximum (``jit_running_max``)."""

    def running_max(v, masks):
        for s, m in zip(shifts, masks):
            v = v.at[s:].set(jnp.where(m, jnp.maximum(v[:-s], v[s:]),
                                       v[s:]))
        return v

    return jax.jit(running_max)


class JaxScanEngine:
    """``jax.jit`` lane of the same scan kernels.

    Jitted kernels are cached per shift sequence (the static part of a
    stage's ``takes``); the ``(k - s, 1)`` mask operands are cached per
    ``takes`` list identity — the cache holds a reference to the list
    itself, so a recycled ``id()`` can never alias a dead stage.  Inputs
    and outputs are NumPy arrays: conversion happens at this boundary
    only, and the surrounding gather/scatter bookkeeping stays NumPy
    either way.

    ``dispatches`` counts kernel calls per ``(kernel, shifts, (k,
    columns))`` — each key is one compiled program, so a cold process
    pays one compile per key — and ``devices`` collects the devices the
    kernels' outputs lived on (read once per key: one compiled program
    always runs where it first ran).  ``bytes_in`` sums the ``nbytes`` of
    every operand a kernel call receives, masks included, and
    ``bytes_out`` those of every output fetched back: what crosses the
    host-device link.

    Each call is a span ``scan.maxplus`` or ``scan.running_max``
    (:mod:`repro.runtime.spans`) holding ``scan.call`` (the operands'
    transfer and the launch) and ``scan.fetch`` (waiting for the device
    and copying the outputs back).
    """

    name = "jax"

    def __init__(self):
        self._takes_cache: dict = {}
        self.dispatches: collections.Counter = collections.Counter()
        self.devices: set = set()
        self.bytes_in = 0
        self.bytes_out = 0

    def _prep(self, takes):
        key = id(takes)
        ent = self._takes_cache.get(key)
        if ent is None or ent[0] is not takes:
            shifts = tuple(int(s) for s, _ in takes)
            masks = tuple(np.ascontiguousarray(m) for _, m in takes)
            ent = self._takes_cache[key] = (takes, shifts, masks)
        return ent[1], ent[2]

    def _record(self, kernel: str, shifts: tuple, out):
        key = (kernel, shifts, out.shape)
        if key not in self.dispatches:
            self.devices.update(out.devices())
        self.dispatches[key] += 1

    def maxplus_scan(self, D, T, takes):
        with span("scan.maxplus"):
            shifts, masks = self._prep(takes)
            shape = T.shape
            if D.shape != shape:
                D = np.broadcast_to(D, shape)
            if T.ndim != 2:
                D = np.ascontiguousarray(D).reshape(shape[0], -1)
                T = np.ascontiguousarray(T).reshape(shape[0], -1)
            self.bytes_in += D.nbytes + T.nbytes + _nbytes(masks)
            # scoped x64: the ≤1e-9 contract needs float64, but the flag
            # must not leak to other jax users in the process (the x64
            # state keys the jit cache, so scoping is sound)
            with jax.enable_x64(True):
                kernel = _maxplus_kernel(shifts)
                with span("scan.call"):
                    Dj, Tj = kernel(D, T, masks)
                self._record("maxplus", shifts, Tj)
                with span("scan.fetch"):
                    D, T = np.asarray(Dj), np.asarray(Tj)
            self.bytes_out += D.nbytes + T.nbytes
            return D.reshape(shape), T.reshape(shape)

    def running_max(self, v, takes):
        with span("scan.running_max"):
            shifts, masks = self._prep(takes)
            shape = v.shape
            if v.ndim != 2:
                v = np.ascontiguousarray(v).reshape(shape[0], -1)
            self.bytes_in += v.nbytes + _nbytes(masks)
            with jax.enable_x64(True):
                kernel = _running_max_kernel(shifts)
                with span("scan.call"):
                    out = kernel(v, masks)
                self._record("running_max", shifts, out)
                with span("scan.fetch"):
                    v = np.asarray(out)
            self.bytes_out += v.nbytes
            return v.reshape(shape)


def _nbytes(arrays) -> int:
    return sum(a.nbytes for a in arrays)


#: the default engine instance (module-level: every compiled program
#: shares it, and ``resolve_engine(None)`` is an attribute read)
NUMPY = NumpyScanEngine()

_engines: dict = {"numpy": NUMPY, "jax": JaxScanEngine()}


def get_scan_engine(name: str = "numpy"):
    """The shared engine instance for ``name``; ``ValueError`` for
    unknown names."""
    eng = _engines.get(name)
    if eng is None:
        raise ValueError(f"unknown scan engine {name!r}; "
                         f"options: {sorted(_engines)}")
    return eng


def resolve_engine(engine):
    """Normalize a per-call ``engine=`` argument: ``None`` → the numpy
    default, a name → the shared instance, an engine object → itself."""
    if engine is None:
        return NUMPY
    if isinstance(engine, str):
        return get_scan_engine(engine)
    if hasattr(engine, "maxplus_scan") and hasattr(engine, "running_max"):
        return engine
    raise ValueError(f"not a scan engine: {engine!r} (pass 'numpy', "
                     f"'jax', or an object with maxplus_scan/running_max)")
