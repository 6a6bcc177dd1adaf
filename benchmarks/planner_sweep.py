"""Micro-benchmark: CollectivePlanner quality and overhead.

Two sections, written to ``BENCH_planner.json``:

* **tpu_grad_sync** — a mixed message-size workload (trace-like: many tiny
  norm/bias buckets, few huge weight buckets) planned on the TpuMachine
  over a (intra x inter) mesh.  Reports total predicted cost of the chosen
  plans vs the always-flat baseline (the headline: planned >= 2x cheaper),
  plus plan-cache hit rate and plans/sec (the planner must be cheap enough
  to run at trace time).
* **exanet_fig19** — the sw/accel crossover on the ExanetMachine at full
  event-simulation fidelity: per vector size, the planner's choice and the
  cost-derived crossover size (the paper's Fig. 19 reproduced from cost
  alone, no hand-coded 4 KB threshold).
* **exanet_plan_many** — sim-fidelity cold planning over a message-size
  grid: ``plan_many`` (one compiled round program per candidate schedule
  serves the whole grid, PR 3) vs scalar ``plan`` per size, plus the warm
  (plan-cache) rate.

Run: PYTHONPATH=src python benchmarks/planner_sweep.py [--smoke]
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.comm import CommPolicy                      # noqa: E402
from repro.core.exanet.mpi import ExanetMPI                 # noqa: E402
from repro.core.machine import ExanetMachine                # noqa: E402
from repro.core.planner import CollectivePlanner            # noqa: E402

#: (bucket bytes, count) — lognormal-ish gradient-bucket mix: most messages
#: are tiny fused scalars/norms, most *bytes* are in a few huge buckets
WORKLOAD = (
    (256, 400), (1 << 10, 300), (8 << 10, 150), (64 << 10, 80),
    (512 << 10, 40), (4 << 20, 20), (32 << 20, 8), (128 << 20, 2),
)
MESH = (16, 4)  # (intra=ICI, inter=cross-pod DCN) axis sizes


def tpu_grad_sync_section(repeats: int) -> dict:
    policy = CommPolicy()
    planner = policy.planner
    msgs = [size for size, cnt in WORKLOAD for _ in range(cnt)]
    random.Random(0).shuffle(msgs)

    # cold planning cost: what a jit trace over fresh bucket sizes pays
    # (every query a cache miss, measured on a fresh planner)
    cold_sizes = sorted({s for s, _ in WORKLOAD} |
                        {s + 128 for s, _ in WORKLOAD})
    cold_planner = CommPolicy().planner
    t0 = time.perf_counter()
    for s in cold_sizes:
        cold_planner.plan("grad_sync", s, MESH, allow_lossy=True)
    cold_pps = len(cold_sizes) / (time.perf_counter() - t0)

    planned = flat = 0.0
    chosen: dict[str, int] = {}
    t0 = time.perf_counter()
    for _ in range(repeats):
        for size in msgs:
            # explicit lossy opt-in: the sweep benchmarks the planner's
            # full candidate set, including the int8 cross-pod sync
            plan = planner.plan("grad_sync", size, MESH, allow_lossy=True)
            planned += plan.cost_s
            flat += plan.cost_of("flat")
            chosen[plan.schedule] = chosen.get(plan.schedule, 0) + 1
    wall = time.perf_counter() - t0
    n_plans = len(msgs) * repeats
    planned /= repeats
    flat /= repeats
    return {
        "mesh": {"intra": MESH[0], "inter": MESH[1]},
        "workload": [{"bytes": s, "count": c} for s, c in WORKLOAD],
        "planned_cost_s": planned,
        "always_flat_cost_s": flat,
        "cost_reduction_x": round(flat / planned, 2),
        "chosen": chosen,
        "cold_plans_per_sec": round(cold_pps, 1),
        "warm_plans_per_sec": round(n_plans / wall, 1),
        "plan_cache": planner.cache_info(),
    }


def exanet_fig19_section(nranks_list: tuple[int, ...]) -> dict:
    out = {}
    for nranks in nranks_list:
        mpi = ExanetMPI(ranks_per_mpsoc=1)
        planner = CollectivePlanner(ExanetMachine(mpi=mpi), fidelity="sim")
        sizes = [256 << i for i in range(9)]  # 256 B .. 64 KB
        rows = []
        crossover = None
        for size in sizes:
            plan = planner.plan("allreduce", size, (nranks,))
            accel = plan.cost_of("accel")
            best_sw = min(c for k, c in plan.costs if k != "accel")
            if crossover is None and plan.schedule != "accel":
                crossover = size
            rows.append({"bytes": size, "choice": plan.schedule,
                         "accel_us": round(accel * 1e6, 2),
                         "best_sw_us": round(best_sw * 1e6, 2)})
        out[str(nranks)] = {"rows": rows,
                            "crossover_bytes_cost_derived": crossover}
    return out


def exanet_plan_many_section(nranks: int, n_sizes: int) -> dict:
    """Cold sim-fidelity planning over a size grid, batched vs scalar."""
    sizes = [1 << i for i in range(n_sizes)]
    batched = CollectivePlanner(ExanetMachine(), fidelity="sim")
    t0 = time.perf_counter()
    plans = batched.plan_many("allreduce", sizes, (nranks,))
    t_batch = time.perf_counter() - t0
    scalar = CollectivePlanner(ExanetMachine(), fidelity="sim")
    t0 = time.perf_counter()
    for s in sizes:
        scalar.plan("allreduce", s, (nranks,))
    t_scalar = time.perf_counter() - t0
    t0 = time.perf_counter()
    batched.plan_many("allreduce", sizes, (nranks,))
    t_warm = time.perf_counter() - t0
    return {
        "nranks": nranks, "grid_sizes": len(sizes),
        "cold_batched_plans_per_sec": round(len(sizes) / t_batch, 1),
        "cold_scalar_plans_per_sec": round(len(sizes) / t_scalar, 1),
        "warm_batched_plans_per_sec": round(len(sizes) / t_warm, 1),
        "cold_speedup_x": round(t_scalar / t_batch, 2),
        "chosen": {str(s): p.schedule for s, p in zip(sizes, plans)},
    }


def main(out_path: str = "BENCH_planner.json", smoke: bool = False) -> None:
    repeats = 2 if smoke else 5
    nranks = (16, 64) if smoke else (16, 64, 128)
    out = {"tpu_grad_sync": tpu_grad_sync_section(repeats),
           "exanet_fig19": exanet_fig19_section(nranks),
           "exanet_plan_many": exanet_plan_many_section(
               16 if smoke else 64, 12 if smoke else 21)}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    g = out["tpu_grad_sync"]
    print(f"planned vs always-flat: {g['cost_reduction_x']:.2f}x cheaper "
          f"({g['planned_cost_s']*1e3:.2f} ms vs "
          f"{g['always_flat_cost_s']*1e3:.2f} ms per step), "
          f"chosen={g['chosen']}")
    print(f"planner overhead: {g['cold_plans_per_sec']:.0f} cold / "
          f"{g['warm_plans_per_sec']:.0f} warm plans/s, "
          f"cache hit rate {g['plan_cache']['hit_rate']:.3f}")
    for n, sec in out["exanet_fig19"].items():
        print(f"exanet N={n}: cost-derived sw/accel crossover at "
              f"{sec['crossover_bytes_cost_derived']} B")
    pm = out["exanet_plan_many"]
    print(f"plan_many N={pm['nranks']} over {pm['grid_sizes']} sizes: "
          f"{pm['cold_batched_plans_per_sec']:.0f} cold-batched vs "
          f"{pm['cold_scalar_plans_per_sec']:.0f} cold-scalar plans/s "
          f"({pm['cold_speedup_x']:.2f}x), "
          f"{pm['warm_batched_plans_per_sec']:.0f} warm")
    print(f"wrote {out_path}")
    assert g["cost_reduction_x"] >= 2.0, "planner must beat always-flat 2x"


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main(smoke="--smoke" in sys.argv[1:])
