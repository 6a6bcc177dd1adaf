"""Monte-Carlo link-fault sweeps of one simulated deployment.

Each sweep prices ``columns`` fresh fault sets as one batched replay on the
jax scan lane: ``batch_fault_axes`` folds the sets into scenario axes and
``ExanetMachine.cost_program_scenarios`` replays them, with the results on
the host when it returns.  Sweeps run back to back, one at a time (closed
loop).  The sets are drawn from the seed by the benchmark
(``exanet_ref.sample_faults``) during set-up, into a pool of
``pool_blocks`` blocks that the sweeps take in turn; every seed draws the
same number of sets of the same shape, so every seed does the same work.

``correct`` compares, once the window has closed, ``check_columns``
columns drawn from the seed out of every sweep of the window with the
plain reference (``exanet_ref.iteration``, which imports nothing of the
program) on the same fault set: the largest relative gap over the latency
and every rank's clock, against the configuration's limit (set from the
program's and the control's readings; ``PERF.md``).
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchlib import exanet_ref

#: the driver's control: the scan kernels without 64-bit mode (float32)
CONTROL = "float32_scan"


def _float32_engine():
    """The program's jax scan lane with its x64 scope switched off: the
    nearest precision below the float64 the configuration states."""
    from repro.core.exanet import scan_engine as se

    class Float32ScanEngine(se.JaxScanEngine):
        def maxplus_scan(self, D, T, takes):
            shifts, masks = self._prep(takes)
            shape = T.shape
            D = np.broadcast_to(D, shape).reshape(shape[0], -1)
            T = np.asarray(T).reshape(shape[0], -1)
            Dj, Tj = se._maxplus_kernel(shifts)(
                D.astype(np.float32), T.astype(np.float32), masks)
            self._record("maxplus", shifts, Tj)
            return (np.asarray(Dj, np.float64).reshape(shape),
                    np.asarray(Tj, np.float64).reshape(shape))

        def running_max(self, v, takes):
            shifts, masks = self._prep(takes)
            shape = v.shape
            out = se._running_max_kernel(shifts)(
                np.asarray(v).reshape(shape[0], -1).astype(np.float32),
                masks)
            self._record("running_max", shifts, out)
            return np.asarray(out, np.float64).reshape(shape)

    return Float32ScanEngine()


def rel_gap(got, latency: float, clocks) -> float:
    """Largest relative gap over latency and per-rank clocks of one
    ``ProgramResult`` against the reference's."""
    a = np.array([got.latency_us, *got.clocks])
    b = np.array([latency, *clocks])
    if a.shape != b.shape:
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


class Driver:
    def __init__(self, cell, seed: int, *, variant=None, peaks=None,
                 log=print):
        if variant not in (None, CONTROL):
            raise ValueError(f"unknown variant {variant!r}")
        self.cfg, self.mix = cell.config, cell.traffic
        self.seed, self.variant, self.log = seed, variant, log
        self.samples: dict = {}
        self.attempted = self.failed = 0
        self.sweeps: list = []      # (block index, results) per sweep

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.core.exanet.apps import ALL_APPS
        from repro.core.exanet.faults import FaultSpec
        from repro.core.exanet.mpi import ExanetMPI
        from repro.core.exanet.scan_engine import JaxScanEngine
        from repro.core.machine import ExanetMachine
        cfg, mix = self.cfg, self.mix
        # the prototype with one rank per A53 core, as the paper ran HPCG
        # (the machine's default places one rank per MPSoC)
        self.machine = ExanetMachine(mpi=ExanetMPI())
        self.prog = ALL_APPS[cfg["app"]]().emit_iteration(cfg["mode"],
                                                          cfg["ranks"])
        self.ref_machine = exanet_ref.Machine(cfg["machine"])
        n = mix["columns"]
        draws = exanet_ref.sample_faults(
            np.random.default_rng(self.seed), self.ref_machine,
            cfg["faults"], n * mix["pool_blocks"])
        self.draws = [draws[b * n:(b + 1) * n]
                      for b in range(mix["pool_blocks"])]
        self.pool = [[FaultSpec(slow_links=d["slow"],
                                link_extra_latency_us=d["extra_us"],
                                lossy_links=d["lossy"]) for d in block]
                     for block in self.draws]
        self.engine = (_float32_engine() if self.variant == CONTROL
                       else JaxScanEngine())
        # warm-up: one sweep compiles (or loads) every kernel the replay
        # uses; the kernels' shapes depend only on the program and the
        # column count, so every block then runs compiled code
        self._sweep(0, None)
        self.sweeps.clear()

    def counters(self) -> dict:
        return {"sweeps": len(self.sweeps),
                "scan_dispatches": sum(self.engine.dispatches.values())}

    def _sweep(self, block: int, tracer):
        from repro.core.exanet.faults import batch_fault_axes
        span = tracer.span if tracer is not None else \
            (lambda _name: contextlib.nullcontext())
        with span("batch_fault_axes"):
            axes = batch_fault_axes(self.pool[block], self.prog)
        with span("cost_program_scenarios"):
            res = self.machine.cost_program_scenarios(
                self.prog, **axes, engine=self.engine)
        self.sweeps.append((block, res))

    # ------------------------------------------------------------ window
    def window(self, seconds: float, tracer) -> dict:
        t0 = time.perf_counter()
        end = t0 + seconds
        n = 0
        while True:
            self._sweep(n % len(self.pool), tracer)
            n += 1
            now = time.perf_counter()
            if now >= end:
                break
        elapsed = now - t0
        cols = n * self.mix["columns"]
        self.attempted = cols
        self.failed = sum(self.mix["columns"] - len(r)
                          for _, r in self.sweeps)
        self.log(f"replay: {n} sweeps of {self.mix['columns']} columns in "
                 f"{elapsed} s")
        return {"replay_columns_per_s": (cols - self.failed) / elapsed}

    def release(self):
        pass

    # ------------------------------------------------------------- check
    def check(self) -> dict:
        n = self.mix["columns"]
        total = len(self.sweeps) * n
        rng = np.random.default_rng((self.seed, 1))
        picks = rng.choice(total, size=min(self.mix["check_columns"], total),
                           replace=False)
        it = {**self.cfg["iteration"], "ranks": self.cfg["ranks"]}
        gap = 0.0
        for p in picks:
            s, j = divmod(int(p), n)
            b, res = self.sweeps[s]
            lat, clocks = exanet_ref.iteration(self.ref_machine, it,
                                               self.draws[b][j])
            gap = max(gap, rel_gap(res[j], lat, clocks))
        self.log(f"check: {len(picks)} of {total} columns against the "
                 f"plain reference")
        return {"rel_gap_vs_reference": {
            "value": gap, "limit": self.cfg["limits"]["rel_gap_vs_reference"]}}
