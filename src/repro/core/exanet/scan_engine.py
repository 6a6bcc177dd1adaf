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
operands.  The per-stage kernels keep their masks on the host and send
them with every call.

The jax engine also runs a whole rendez-vous level of a compiled
program as one dispatch, :meth:`JaxScanEngine.rdv_serial`: each send
takes its rows (R5 → DMA source → link hops → DMA destination) one after
another, send after send, one scan step per send.  The level's row table
is put on the device once per level object and stays there, and each
call sends only the issue times, stream durations and the free times of
the rows the level touches (DESIGN.md §2.5).
"""

from __future__ import annotations

import collections
import functools
import weakref

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


@functools.lru_cache(maxsize=None)
def _rdv_serial_kernel(r5_occ: float, rdma_startup: float):
    """Jitted serial rendez-vous level (``jit_rdv_serial``): each send
    takes its rows (R5, DMA source, link hops, DMA destination) one after
    another, send after send in level order, as the interpreter's engine
    does; one ``lax.scan`` step per send.  Sends may share rows across
    stages in any order, so a level is split only by clock dependencies.

    ``x`` stacks the issue times (k rows), the stream durations (k rows)
    and the free times of the level's u rows; ``consts`` holds each
    send's rows among the u (``rows``, (k, S); a stage the send does not
    take reads the spare row u) and which of its stages it takes
    (``valid``).  The output stacks each send's stream end and the rows'
    new free times."""

    def rdv_serial(x, consts):
        rows, valid = consts["rows"], consts["valid"]
        k, n_stages = rows.shape
        t, stream = x[:k], x[k:2 * k]
        free = jnp.concatenate([x[2 * k:], jnp.zeros_like(x[:1])])

        def send(free, ev):
            r, ok, te, de = ev
            F = free[r]
            start = jnp.maximum(te, F[0])             # R5, then start-up
            new = [start + r5_occ]
            cur = end = start + rdma_startup
            for i in range(1, n_stages):              # DMA src, hops, dst
                start = jnp.maximum(cur, F[i])
                new.append(jnp.where(ok[i], start + de, F[i]))
                cur = jnp.where(ok[i], start, cur)
                end = jnp.where(ok[i], start + de, end)
            return free.at[r].set(jnp.stack(new)), end

        free, ends = jax.lax.scan(send, free, (rows, valid, t, stream))
        return jnp.concatenate([ends, free[:-1]])

    return jax.jit(rdv_serial)


def _serial_consts(lv):
    """The rows a serial level touches and its per-send row table."""
    table = lv.serial
    valid = table >= 0
    u_rows = np.unique(table[valid])
    rows = np.where(valid, np.searchsorted(u_rows, np.where(valid, table, 0)),
                    len(u_rows)).astype(np.int32)
    return u_rows, {"rows": rows, "valid": valid}


class JaxScanEngine:
    """``jax.jit`` lane of the same scan kernels.

    Jitted kernels are cached per shift sequence (the static part of a
    stage's ``takes``).  A per-stage kernel's ``(k - s, 1)`` mask
    operands are kept on the host, cached per ``takes`` list identity,
    and sent to the device with every call; the cache holds a reference
    to the list itself, so a recycled ``id()`` can never alias a dead
    stage.  A fused level's row table (:meth:`rdv_serial`) lives on the
    device instead, held weakly per level object: it goes when the level
    goes, so programs compiled per call leave nothing behind.  Inputs
    and outputs are NumPy arrays: conversion happens at this boundary
    only.

    ``dispatches`` counts kernel calls per ``(kernel, static signature,
    output shape)`` — each key is one compiled program, so a cold
    process pays one compile per key — and ``devices`` collects the
    devices the kernels' outputs lived on (read once per key: one
    compiled program always runs where it first ran).  ``bytes_in`` sums
    the ``nbytes`` of every operand a kernel call sends, masks included,
    and of a level's device constants when they are put there;
    ``bytes_out`` sums those of every output fetched back: what crosses
    the host-device link.  ``levels_fused`` and ``levels_staged`` count
    the rendez-vous levels the transport ran as one :meth:`rdv_serial`
    dispatch and as a chain of per-stage kernels.  ``rdv_level_bytes``
    sums, per :meth:`rdv_serial` call, its operand, its resident row
    table and its result: what any implementation of the level has to
    read and write once.

    Each call is a span ``scan.maxplus`` (a fused level is a max-plus
    program too) or ``scan.running_max`` (:mod:`repro.runtime.spans`)
    holding ``scan.call`` (the operands' transfer and the launch) and
    ``scan.fetch`` (waiting for the device and copying the outputs
    back).  A fused level looks up its constants, stacks its operand
    and writes the free times back outside its span: that host work is
    the transport's, as the staged chain's gathers and scatters are.
    """

    name = "jax"

    def __init__(self):
        self._takes_cache: dict = {}
        self._level_cache = weakref.WeakKeyDictionary()
        self.dispatches: collections.Counter = collections.Counter()
        self.devices: set = set()
        self.bytes_in = 0
        self.bytes_out = 0
        self.levels_fused = 0
        self.levels_staged = 0
        self.rdv_level_bytes = 0

    @property
    def fuses_levels(self) -> bool:
        """Whether :meth:`rdv_serial` may stand in for the per-stage
        kernels: only while they are this class's own.  A subclass or a
        patch that replaces :meth:`maxplus_scan` or :meth:`running_max`
        (a float32 control, a planted fault) keeps the staged chain, so
        the replacement runs."""
        cls = type(self)
        return (cls.maxplus_scan is _OWN_STAGE_KERNELS[0]
                and cls.running_max is _OWN_STAGE_KERNELS[1])

    def _prep(self, takes):
        key = id(takes)
        ent = self._takes_cache.get(key)
        if ent is None or ent[0] is not takes:
            shifts = tuple(int(s) for s, _ in takes)
            masks = tuple(np.ascontiguousarray(m) for _, m in takes)
            ent = self._takes_cache[key] = (takes, shifts, masks)
        return ent[1], ent[2]

    def _record(self, kernel: str, static: tuple, out):
        key = (kernel, static, out.shape)
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

    def _serial(self, lv):
        """The rows serial level ``lv`` touches, its row table on the
        device (put there on first use) and the table's bytes."""
        ent = self._level_cache.get(lv)
        if ent is None:
            u_rows, consts = _serial_consts(lv)
            nbytes = _nbytes(consts.values())
            self.bytes_in += nbytes
            ent = self._level_cache[lv] = (u_rows, jax.device_put(consts),
                                           nbytes)
        return ent

    def rdv_serial(self, state, lv, t, stream, r5_occ: float,
                   rdma_startup: float):
        """A serial level (``lv.serial``) as one dispatch: each send
        takes its rows one after another, send after send in level
        order.  ``t`` is each send's R5 issue time (issue plus
        handshake) and ``stream`` its stream duration, both ``(k,
        *batch)``.  Advances ``state.free`` over the level's rows and
        returns the time each send's stream ends (its completion less the
        hop latency)."""
        u_rows, consts, c_bytes = self._serial(lv)
        shape = t.shape
        k, u = shape[0], len(u_rows)
        x = np.concatenate([
            t.reshape(k, -1),
            np.broadcast_to(stream, shape).reshape(k, -1),
            state.free[u_rows].reshape(u, -1)])
        self.bytes_in += x.nbytes
        with span("scan.maxplus"), jax.enable_x64(True):
            kernel = _rdv_serial_kernel(float(r5_occ), float(rdma_startup))
            with span("scan.call"):
                out = kernel(x, consts)
            self._record("rdv_serial", consts["rows"].shape + (u,), out)
            with span("scan.fetch"):
                out = np.asarray(out)
        self.bytes_out += out.nbytes
        self.rdv_level_bytes += x.nbytes + c_bytes + out.nbytes
        state.free[u_rows] = out[k:].reshape((u,) + shape[1:])
        self.levels_fused += 1
        return out[:k].reshape(shape)


#: the per-stage kernels a fused level stands in for (``fuses_levels``)
_OWN_STAGE_KERNELS = (JaxScanEngine.maxplus_scan, JaxScanEngine.running_max)


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
