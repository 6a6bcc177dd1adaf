"""Monte-Carlo link-fault sweeps of one expert-parallel decode step.

The step is emitted once during set-up by the program's own emitter
(``repro.serve.sim.EPDecodeSim``) from router logits drawn from the
configuration's ``routing_seed``, so every run replays the same program;
``--seed`` draws only the fault sets.  Each sweep prices ``columns`` fault
sets as one batched replay on the jax scan lane
(``batch_fault_axes`` -> ``ExanetMachine.cost_program_scenarios``), with
the results on the host when it returns.  Sweeps run back to back, one at
a time (closed loop), taking the ``pool_blocks`` blocks of sets drawn
during set-up in turn.

``correct`` compares, once the window has closed, ``check_columns``
columns drawn from the seed out of the window's sweeps with the plain
reference (``benchlib/ep_ref.py``, which imports nothing of the program)
on the same logits and fault set: the largest relative gap over the
latency and every rank's clock, against the configuration's limit.
"""

from __future__ import annotations

import contextlib
import gc
import resource
import statistics
import time

import numpy as np

from benchlib import ep_ref, exanet_ref
from benchlib.kinds.replay_sweep import CONTROL, rel_gap


def _float32_engine():
    """The program's jax scan lane with its serial levels, which carry
    all of this step's traffic, computed in float32: the nearest
    precision below the float64 the configuration states."""
    from repro.core.exanet import scan_engine as se

    class Float32SerialEngine(se.JaxScanEngine):
        def rdv_serial(self, state, lv, t, stream, r5_occ, rdma_startup):
            u_rows, consts, _ = self._serial(lv)
            shape = t.shape
            k, u = shape[0], len(u_rows)
            x = np.concatenate([
                t.reshape(k, -1),
                np.broadcast_to(stream, shape).reshape(k, -1),
                state.free[u_rows].reshape(u, -1)]).astype(np.float32)
            out = se._rdv_serial_kernel(float(r5_occ), float(rdma_startup))(
                x, consts)
            self._record("rdv_serial", consts["rows"].shape + (u,), out)
            out = np.asarray(out, np.float64)
            state.free[u_rows] = out[k:].reshape((u,) + shape[1:])
            return out[:k].reshape(shape)

    return Float32SerialEngine()


def logits(cfg: dict) -> np.ndarray:
    """Router logits ``(moe layers, tokens, experts)`` from the
    configuration's ``routing_seed``: N(0, ``sigma``) per token and
    expert, plus a per-expert popularity term N(0, ``popularity_sigma``)
    per layer (topic skew)."""
    lg = cfg["logits"]
    dep = cfg["deployment"]
    shape = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
             dep["ranks"] * dep["tokens_per_rank"], cfg["n_routed_experts"])
    rng = np.random.default_rng(cfg["routing_seed"])
    return rng.normal(0.0, lg["sigma"], shape) + rng.normal(
        0.0, lg["popularity_sigma"], (shape[0], 1, shape[2]))


def emitter(cfg: dict):
    """The program's emitter for the configuration's deployment: the
    repository's architecture (``arch``) with the file's routing values
    (the same at the published size; a rehearsal routes over fewer
    experts)."""
    import dataclasses

    from repro.configs import get
    from repro.serve.sim import EPDecodeSim, EPDecodeSpec
    dep = cfg["deployment"]
    arch = get(cfg["arch"])
    arch = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"]))
    spec = EPDecodeSpec(
        arch=cfg["arch"], nranks=dep["ranks"],
        tokens_per_rank=dep["tokens_per_rank"], context=dep["context"],
        n_dense_layers=cfg["first_k_dense_replace"],
        n_moe_layers=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        dtype_bytes=dep["weight_bytes"],
        core_rate_flops_per_us=dep["core_flops_per_us"],
        mem_bw_bytes_per_us=dep["core_bytes_per_us"],
        cores_per_rank=dep["cores_per_rank"])
    return EPDecodeSim(spec, arch)


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
        from repro.core.exanet.faults import FaultSpec
        from repro.core.exanet.mpi import ExanetMPI
        from repro.core.exanet.scan_engine import JaxScanEngine
        from repro.core.machine import ExanetMachine
        cfg, mix = self.cfg, self.mix
        t0 = time.perf_counter()
        sim = emitter(cfg)
        self.logits = logits(cfg)
        self.prog = sim.emit_step(self.logits)
        self.log(f"emit: {time.perf_counter() - t0} s, {self.prog.counts()}")
        self.machine = ExanetMachine(mpi=ExanetMPI(
            ranks_per_mpsoc=cfg["deployment"]["ranks_per_mpsoc"]))
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
        # warm-up: one sweep probes the step, lowers its levels and
        # compiles (or loads) every kernel; the kernels' shapes depend only
        # on the program and the column count
        t0 = time.perf_counter()
        self._sweep(0, None)
        self.log(f"warm-up sweep: {time.perf_counter() - t0} s")
        self.sweeps.clear()

    def counters(self) -> dict:
        return {"sweeps": len(self.sweeps),
                "scan_dispatches": sum(self.engine.dispatches.values()),
                "rdv_level_bytes": getattr(self.engine, "rdv_level_bytes",
                                           0)}

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
        ru0, cpu0 = resource.getrusage(resource.RUSAGE_SELF), \
            time.process_time()
        full0 = gc.get_stats()[2]["collections"]
        t0 = time.perf_counter()
        end = t0 + seconds
        sweep_s = []
        while True:
            s0 = time.perf_counter()
            self._sweep(len(sweep_s) % len(self.pool), tracer)
            now = time.perf_counter()
            sweep_s.append(now - s0)
            if now >= end:
                break
        elapsed = now - t0
        n = len(sweep_s)
        cols = n * self.mix["columns"]
        self.attempted = cols
        self.failed = sum(self.mix["columns"] - len(r)
                          for _, r in self.sweeps)
        # what a slow run spends its time on: the host's own work (process
        # CPU time), being put off its cores (involuntary context switches)
        # or waiting (voluntary ones, the device's among them)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        q = statistics.quantiles(sweep_s, n=4) if n > 1 else sweep_s * 3
        self.log(f"replay: {n} sweeps of {self.mix['columns']} columns in "
                 f"{elapsed} s; sweep s min {min(sweep_s)} quartiles {q} "
                 f"max {max(sweep_s)}; process CPU "
                 f"{time.process_time() - cpu0} s; context switches "
                 f"{ru.ru_nivcsw - ru0.ru_nivcsw} involuntary, "
                 f"{ru.ru_nvcsw - ru0.ru_nvcsw} voluntary; full "
                 f"collections {gc.get_stats()[2]['collections'] - full0}")
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
        step = ep_ref.Step(self.cfg, self.logits)
        ops = step.ops()
        order = ep_ref.firing_order(self.ref_machine, step, ops)
        gap = 0.0
        for p in picks:
            s, j = divmod(int(p), n)
            b, res = self.sweeps[s]
            lat, clocks = ep_ref.step_latency(self.ref_machine, step, ops,
                                              order, self.draws[b][j])
            gap = max(gap, rel_gap(res[j], lat, clocks))
        self.log(f"check: {len(picks)} of {total} columns against the "
                 f"plain reference")
        return {"rel_gap_vs_reference": {
            "value": gap, "limit": self.cfg["limits"]["rel_gap_vs_reference"]}}
