"""Benchmark: overlap-aware train-step co-simulation
(``BENCH_train.json``, ROADMAP item 5; DESIGN.md §2.9).

Per (model, rank count, scaling mode) the train co-sim emits one data-
parallel training step — roofline compute slices interleaved with
bucketed gradient allreduces — and *executes* it on the ExaNeSt machine
at sim fidelity (and through the TPU machine's analytic walk of the
same emission), so backward/sync overlap is an emergent quantity, not a
closed form.  Reported: weak-scaling (fixed per-rank batch) and
strong-scaling (fixed global batch) step-time curves with blocking vs
overlapped sync, global token throughput, and the critical-path lower
bound each overlapped step is checked against.

The ``speedup`` section measures the candidate-population fast path: a
64-member family of split-perturbed sync candidates costed as batch
columns of ONE compiled replay (per-site payload scale + per-compute-
slot scale) against the naive lane — one emit + ``run_program`` per
candidate, identical payloads, lane agreement <=1e-9 asserted.  The
``planner`` section records ``CollectivePlanner.plan_train_sync``
hillclimbs against the analytic ``CommPolicy`` baseline; the full sweep
asserts at least one decision flips with margin.

Per-rank GFLOP/s is set per model to land compute and gradient wire
time in the same decade — the regime where sync scheduling moves step
time; see MODELS.

Run: PYTHONPATH=src python benchmarks/train_sweep.py [--smoke]
         [--engine numpy|jax]

``--smoke`` (the CI lane) runs 16 ranks with the same 1e-9 agreement
guards and the emergent-overlap bound check, and per the BENCH schema
rules (DESIGN.md §6) omits the acceptance keys
(``scenario_speedup_at_512``, ``planner_flip``) so a smoke artifact can
never masquerade as the full sweep.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core.machine import ExanetMachine, TpuMachine  # noqa: E402
from repro.core.planner import CollectivePlanner  # noqa: E402
from repro.train.cosim import (SyncCandidate, TrainSim,  # noqa: E402
                               TrainStepSpec)

AGREEMENT_RTOL = 1e-9
RANKS = (512, 1024)
PREDICT_RANKS = (2048, 4096)
GLOBAL_BATCH = 4096           #: sequences, strong-scaling numerator
#: (arch, per-rank GFLOP/s, seq_len).  The GFLOP/s knob places each
#: model's backward compute and gradient wire time in the same decade:
#: the 100m config on bare A53+NEON nodes, the 123b dense model on
#: accelerator-equipped nodes, DeepSeek-V3 back on modest nodes (its
#: sparse active-param compute meets a full-param gradient sync).
MODELS = (("exanest-lm-100m", 50.0, 2048),
          ("mistral-large-123b", 1000.0, 2048),
          ("deepseek-v3-671b", 50.0, 2048))


def cand_dict(c: SyncCandidate) -> dict:
    return {"n_buckets": c.n_buckets, "algo": c.algo,
            "overlap_depth": c.overlap_depth,
            "split": list(c.split) if c.split else None}


def make_sim(machine, arch: str, gflops: float, seq: int, nranks: int,
             bpr: int) -> TrainSim:
    return TrainSim(TrainStepSpec(arch=arch, nranks=nranks, seq_len=seq,
                                  batch_per_rank=bpr, rank_gflops=gflops),
                    machine)


def scaling_row(sim: TrainSim, mode: str, over: SyncCandidate, *,
                engine: str, check: int, bounds: bool = True) -> dict:
    """One (arch, nranks, mode) point: blocking vs overlapped step time
    on the sim machine plus the same pair through the TPU analytic walk,
    with the emergent-overlap bound check."""
    spec = sim.spec
    block = dataclasses.replace(over, overlap_depth=0)
    t0 = time.perf_counter()
    bl, ov = sim.cost_candidates([block, over], engine=engine, check=check,
                                 rtol=AGREEMENT_RTOL)
    wall = time.perf_counter() - t0
    lb = sim.lower_bound_us(over) if bounds else None
    # bound check: overlapped in [critical path, blocking].  Equality
    # with blocking is legitimate in comm-saturated regimes (the engine
    # already pipelines compute into comm slack without handles); strict
    # gain is asserted separately over the sweep (see main).
    emergent = bool(bounds and lb * (1 - AGREEMENT_RTOL) <= ov <= bl)
    tokens = spec.nranks * spec.batch_per_rank * spec.seq_len
    row = {
        "arch": spec.arch, "nranks": spec.nranks, "mode": mode,
        "machine": "exanet-sim", "engine": engine,
        "batch_per_rank": spec.batch_per_rank, "seq_len": spec.seq_len,
        "rank_gflops": spec.rank_gflops,
        "candidate": cand_dict(over),
        "blocking_step_us": float(bl), "overlapped_step_us": float(ov),
        "overlap_gain": round(float((bl - ov) / bl), 4),
        "lower_bound_us": float(lb) if lb is not None else None,
        "overlap_emergent": emergent,
        "tokens_per_sec_global": round(tokens / (float(ov) / 1e6), 1),
        "wall_s": round(wall, 3),
    }
    print(f"{spec.arch:20s} N={spec.nranks:5d} {mode:6s} "
          f"bpr={spec.batch_per_rank:2d}  "
          f"block={bl/1e6:9.2f}s over={ov/1e6:9.2f}s "
          f"gain={row['overlap_gain']:6.1%} "
          f"tok/s={row['tokens_per_sec_global']:12.1f}  [{wall:5.1f}s]")
    return row


def analytic_row(sim: TrainSim, mode: str, over: SyncCandidate) -> dict:
    """The same emission through the TPU machine's analytic hooks —
    overlap still emerges because analytic costing runs on the shared
    nonblocking-collective scheduler."""
    spec = sim.spec
    block = dataclasses.replace(over, overlap_depth=0)
    tpu = TpuMachine()
    bl = sim.step_time_analytic(block, tpu)
    ov = sim.step_time_analytic(over, tpu)
    return {"arch": spec.arch, "nranks": spec.nranks, "mode": mode,
            "machine": "tpu-analytic",
            "batch_per_rank": spec.batch_per_rank,
            "candidate": cand_dict(over),
            "blocking_step_us": float(bl), "overlapped_step_us": float(ov),
            "overlap_gain": round(float((bl - ov) / bl), 4)}


def speedup_row(sim: TrainSim, *, n_candidates: int, n_single: int,
                engine: str, check: int, seed: int = 7) -> dict:
    """Batched-vs-per-candidate lane comparison on identical candidates:
    one family of split-perturbed members costed as columns of one
    compiled replay vs one emit+run_program per member."""
    base = SyncCandidate(8, sim.feasible_algos()[0], 1)
    rng = np.random.default_rng(seed)
    fam = [base]
    while len(fam) < n_candidates:
        m = sim.mutate(dataclasses.replace(base), rng)
        if m.family() == base.family() and m not in fam:
            fam.append(m)
    sim.cost_candidates([base], engine=engine)       # warm schedule caches
    t0 = time.perf_counter()
    us = sim.cost_candidates(fam, engine=engine, check=check,
                             rtol=AGREEMENT_RTOL)
    batched_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    singles = np.array([sim.step_time_single(c, engine=engine)
                        for c in fam[:n_single]])
    single_wall = time.perf_counter() - t0
    lane_rel = float(np.max(np.abs(us[:n_single] - singles) / singles))
    assert lane_rel <= AGREEMENT_RTOL, \
        f"batched lane deviates from per-candidate lane: {lane_rel:.2e}"
    batched_rate = len(fam) / batched_wall
    single_rate = n_single / single_wall
    speedup = batched_rate / single_rate
    print(f"speedup @N={sim.spec.nranks}: batched {batched_rate:7.1f} "
          f"cand/s ({len(fam)} columns) vs per-candidate "
          f"{single_rate:6.2f} cand/s ({n_single} runs) -> "
          f"{speedup:.1f}x  (lane agree {lane_rel:.1e})")
    return {
        "arch": sim.spec.arch, "nranks": sim.spec.nranks, "engine": engine,
        "family": {"n_buckets": base.n_buckets, "algo": base.algo,
                   "overlap_depth": base.overlap_depth},
        "batched": {"candidates": len(fam),
                    "wall_s": round(batched_wall, 4),
                    "cand_per_sec": round(batched_rate, 2),
                    "interp_checked_columns": check},
        "per_candidate": {"candidates": n_single,
                          "wall_s": round(single_wall, 4),
                          "cand_per_sec": round(single_rate, 3)},
        "scenario_speedup": round(speedup, 1),
        "lane_agreement_rel": lane_rel,
    }


def planner_row(sim: TrainSim, *, engine: str, check: int,
                generations: int = 2, survivors: int = 4,
                children: int = 4) -> dict:
    t0 = time.perf_counter()
    plan = CollectivePlanner(sim.machine).plan_train_sync(
        sim, generations=generations, survivors=survivors,
        children=children, engine=engine, check=check)
    wall = time.perf_counter() - t0
    print(f"planner @{plan.arch} N={plan.nranks}: "
          f"{cand_dict(plan.baseline)} ({plan.baseline_step_us/1e6:.2f}s) "
          f"-> {cand_dict(plan.chosen)} ({plan.step_us/1e6:.2f}s)  "
          f"flip={plan.flipped} {plan.flip_kinds} "
          f"margin={plan.margin:.1%} [{plan.evaluated} evals, {wall:.1f}s]")
    return {"arch": plan.arch, "nranks": plan.nranks,
            "baseline": cand_dict(plan.baseline),
            "baseline_step_us": plan.baseline_step_us,
            "chosen": cand_dict(plan.chosen), "step_us": plan.step_us,
            "flipped": plan.flipped, "flip_kinds": list(plan.flip_kinds),
            "margin": round(plan.margin, 4), "evaluated": plan.evaluated,
            "wall_s": round(wall, 2)}


def main(out_path: str = "BENCH_train.json", smoke: bool = False,
         engine: str = "numpy") -> None:
    machine = ExanetMachine()
    out: dict = {"engine": engine, "agreement_rtol": AGREEMENT_RTOL,
                 "results": [], "speedup": [], "planner": []}
    if smoke:
        out["smoke"] = True
        out["ranks"] = [16]
        sim = make_sim(machine, "exanest-lm-100m", 50.0, 256, 16, 1)
        over = SyncCandidate(4, sim.feasible_algos()[0], 2)
        row = scaling_row(sim, "weak", over, engine=engine, check=2)
        assert row["overlap_emergent"] and row["overlap_gain"] > 0, \
            "smoke: overlapped step must sit in [lower bound, blocking)"
        out["results"].append(row)
        out["results"].append(analytic_row(sim, "weak", over))
        out["speedup"].append(speedup_row(sim, n_candidates=8, n_single=3,
                                          engine=engine, check=2))
        out["planner"].append(planner_row(
            sim, engine=engine, check=1, generations=1, survivors=2,
            children=2))
    else:
        out["ranks"] = list(RANKS)
        out["prediction_ranks"] = list(PREDICT_RANKS)
        out["models"] = [m[0] for m in MODELS]
        out["global_batch_strong"] = GLOBAL_BATCH
        for arch, gflops, seq in MODELS:
            for n in RANKS:
                for mode, bpr in (("weak", 1),
                                  ("strong", max(1, GLOBAL_BATCH // n))):
                    sim = make_sim(machine, arch, gflops, seq, n, bpr)
                    over = SyncCandidate(8, sim.feasible_algos()[0], 2)
                    out["results"].append(scaling_row(
                        sim, mode, over, engine=engine, check=1))
                    if mode == "weak":
                        out["results"].append(analytic_row(sim, mode, over))
            sim512 = make_sim(machine, arch, gflops, seq, 512, 1)
            out["planner"].append(planner_row(sim512, engine=engine,
                                              check=1))
        # the fast-path headline on the repo's own config
        arch0, gflops0, seq0 = MODELS[0]
        sim512 = make_sim(machine, arch0, gflops0, seq0, 512, 1)
        out["speedup"].append(speedup_row(sim512, n_candidates=64,
                                          n_single=6, engine=engine,
                                          check=2))
        # predicted tiers: carry the 512-rank overlapped plan upward
        # (weak scaling, the repo's own config)
        for n in PREDICT_RANKS:
            sim = make_sim(machine, MODELS[0][0], MODELS[0][1],
                           MODELS[0][2], n, 1)
            over = SyncCandidate(8, sim.feasible_algos()[0], 2)
            row = scaling_row(sim, "weak", over, engine=engine, check=1)
            row["prediction"] = True
            out["results"].append(row)
        # acceptance keys: full sweeps only (see module docstring)
        out["scenario_speedup_at_512"] = min(
            s["scenario_speedup"] for s in out["speedup"])
        assert out["scenario_speedup_at_512"] >= 10.0, \
            "batched candidate lane must be >=10x per-candidate at 512"
        flips = [p for p in out["planner"] if p["flipped"]]
        assert flips, "no planner decision flipped vs the analytic baseline"
        out["planner_flip"] = {"count": len(flips),
                               "max_margin": max(p["margin"]
                                                 for p in flips)}
        sim_rows = [r for r in out["results"]
                    if r["machine"] == "exanet-sim"]
        assert all(r["overlap_emergent"] for r in sim_rows), \
            "an overlapped step left [lower bound, blocking]"
        out["overlap_emergent_all_rows"] = True
        gained = [r for r in sim_rows if r["overlap_gain"] > 0.01]
        assert gained, "no row shows strict overlap gain"
        out["rows_with_overlap_gain"] = len(gained)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {out_path}")
    if not smoke:
        print(f"scenario_speedup @512: {out['scenario_speedup_at_512']}x; "
              f"planner flips: {out['planner_flip']['count']} "
              f"(max margin {out['planner_flip']['max_margin']:.1%})")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="numpy", choices=("numpy", "jax"),
                    help="scan backend of the batched compiled lane")
    args = ap.parse_args()
    main(smoke=args.smoke, engine=args.engine)
