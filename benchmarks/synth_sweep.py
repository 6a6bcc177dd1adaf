"""Collective-schedule synthesis sweep over the OSU (nbytes x nranks)
grid -> BENCH_synth.json (schema: DESIGN.md §6).

Per cell, :func:`repro.core.synth.search.search_cell` runs the full
skeleton search (Split / Pipeline / Hierarchical / Dissemination
combinators of the round algebra) with batched compiled fitness, and
each row reports the best hand-written menu cost vs the synthesized
winner, the search throughput (candidates/s), and both synthesis gates
(semantic contribution check + interpreter agreement <=1e-9).

Two acceptance checks ride on top (ISSUE 8):

* **win cells** — the synthesized schedule must beat the best
  hand-written menu schedule (accelerator included) on >= 3 grid cells;
* **Fig. 19 crossover** — the search family contains no accelerator,
  yet bisecting accel-vs-synthesized cost with the planner's
  :func:`~repro.core.planner.crossover_bytes` must re-derive the
  paper's sw/accel crossover: accel wins below, synthesized software
  wins above.  The menu-derived crossover (what the planner would
  compute from hand-written schedules alone) is reported next to it.

``--write-cache default`` regenerates the committed winner-cache
artifact ``src/repro/core/synth/winners.json`` the planner loads as its
``synthesized`` candidate source; only winners that beat the software
menu are cached.

Run:
  PYTHONPATH=src python benchmarks/synth_sweep.py [--smoke] [--engine jax]
      [--pop 24] [--gens 6] [--write-cache default]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.machine import ExanetMachine  # noqa: E402
from repro.core.planner import crossover_bytes  # noqa: E402
from repro.core.synth.search import (WinnerCache, registered,  # noqa: E402
                                     search_cell)

#: OSU-style grid: latency-bound, crossover-adjacent, bandwidth-bound
GRID_NRANKS = (16, 64, 256)
GRID_NBYTES = (64, 4096, 65536, 262144)
SMOKE_NRANKS = (64,)
SMOKE_NBYTES = (4096, 65536)


def derive_crossover(machine: ExanetMachine, nranks: int,
                     winners: list, *, hi: int = 1 << 22) -> dict:
    """Re-derive the Fig. 19 sw/accel crossover by bisection.

    ``winners`` are the synthesized schedules found at this rank count —
    none of them saw the accelerator during search.  The same
    :func:`crossover_bytes` the planner uses for its eager threshold
    bisects accel cost against (a) the synthesized family and (b) the
    hand-written software menu, so the two crossovers are directly
    comparable."""
    from repro.core.synth.search import _menu_costs

    def accel(n: int) -> float:
        from repro.core.exanet.schedules import HierarchicalAccelAllreduce
        return machine.cost_s(HierarchicalAccelAllreduce(), nranks, n,
                              fidelity="sim")

    def best_synth(n: int) -> float:
        return min(machine.cost_s(w, nranks, n, fidelity="sim")
                   for w in winners)

    def best_sw_menu(n: int) -> float:
        sw, _ = _menu_costs(machine, nranks, n, "sim")
        return sw[0][1]

    x_synth = crossover_bytes(accel, best_synth, hi=hi)
    x_menu = crossover_bytes(accel, best_sw_menu, hi=hi)

    # spot-check the Fig. 19 shape on both sides of the derived point
    below, above = max(1, x_synth // 4), min(hi, x_synth * 4)
    fig19 = {
        "probe_below": below, "accel_s_below": accel(below),
        "synth_s_below": best_synth(below),
        "probe_above": above, "accel_s_above": accel(above),
        "synth_s_above": best_synth(above),
    }
    fig19["ok"] = (fig19["accel_s_below"] < fig19["synth_s_below"]
                   and fig19["synth_s_above"] < fig19["accel_s_above"])
    return {
        "nranks": nranks,
        "accel_vs_synth_bytes": x_synth,
        "accel_vs_menu_bytes": x_menu,
        "ratio_synth_vs_menu": x_synth / x_menu if x_menu else None,
        "method": "repro.core.planner.crossover_bytes bisection, sim "
                  "fidelity; accel absent from the search family",
        "fig19": fig19,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="2-cell grid, tiny population, for CI")
    ap.add_argument("--engine", default="numpy", choices=("numpy", "jax"),
                    help="scan backend of the batched fitness replays")
    ap.add_argument("--pop", type=int, default=24)
    ap.add_argument("--gens", type=int, default=6)
    ap.add_argument("--refine", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_synth.json")
    ap.add_argument("--write-cache", default=None, metavar="PATH",
                    help="persist menu-beating winners ('default' = the "
                         "committed src/repro/core/synth/winners.json)")
    args = ap.parse_args()
    if args.smoke:
        nranks_grid, nbytes_grid = SMOKE_NRANKS, SMOKE_NBYTES
        pop, gens, refine = 8, 3, 1
    else:
        nranks_grid, nbytes_grid = GRID_NRANKS, GRID_NBYTES
        pop, gens, refine = args.pop, args.gens, args.refine

    cache = None
    if args.write_cache is not None:
        path = (WinnerCache.DEFAULT_PATH if args.write_cache == "default"
                else args.write_cache)
        cache = WinnerCache(path=path)

    machine = ExanetMachine()
    rows = []
    winners_by_nranks: dict[int, list] = {}
    t0 = time.perf_counter()
    i = 0
    for nranks in nranks_grid:
        for nbytes in nbytes_grid:
            res = search_cell(machine, nbytes, nranks, pop=pop, gens=gens,
                              refine=refine, seed=args.seed + i,
                              engine=args.engine)
            i += 1
            rows.append(res.to_row())
            winners_by_nranks.setdefault(nranks, []).append(
                registered(res.winner_name))
            if cache is not None and res.winner_s < res.best_sw_menu_s:
                cache.put(machine.name, res.op, nranks, nbytes,
                          res.placement, spec=res.winner_spec,
                          cost_s=res.winner_s,
                          best_menu_s=res.best_sw_menu_s,
                          menu_name=res.best_sw_menu)
            beats = "WIN " if res.winner_s < res.best_menu_s else "    "
            print(f"{beats}N={nranks:4d} nbytes={nbytes:7d}  "
                  f"synth={res.winner_s:.3e}s  menu={res.best_menu_s:.3e}s"
                  f" ({res.best_menu})  x{res.best_menu_s / res.winner_s:.3f}"
                  f"  {res.candidates_per_s:.0f} cand/s  "
                  f"agree {res.agreement_rel:.1e}")

    win_cells = [r for r in rows if r["winner_s"] < r["best_menu_s"]]
    sw_win_cells = [r for r in rows if r["winner_s"] < r["best_sw_menu_s"]]

    # Fig. 19 crossover at the grid's center rank count
    x_nranks = 64 if 64 in winners_by_nranks else nranks_grid[0]
    crossover = derive_crossover(machine, x_nranks,
                                 winners_by_nranks[x_nranks],
                                 hi=(1 << 18 if args.smoke else 1 << 22))

    out = {
        "smoke": args.smoke, "engine": args.engine, "fidelity": "sim",
        "machine": machine.name, "population": pop, "generations": gens,
        "refine": refine,
        "grid": {"nranks": list(nranks_grid), "nbytes": list(nbytes_grid)},
        "results": rows,
        "n_cells": len(rows),
        "n_win_cells": len(win_cells),
        "n_sw_win_cells": len(sw_win_cells),
        "all_semantic_ok": all(r["semantic_ok"] for r in rows),
        "max_agreement_rel": max(r["agreement_rel"] for r in rows),
        "crossover": crossover,
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    if cache is not None and len(cache):
        out["cache_path"] = cache.save()
        print(f"wrote {len(cache)} winners -> {out['cache_path']}")
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"win cells {len(win_cells)}/{len(rows)} (vs full menu), "
          f"{len(sw_win_cells)} vs sw menu; crossover synth="
          f"{crossover['accel_vs_synth_bytes']}B menu="
          f"{crossover['accel_vs_menu_bytes']}B fig19_ok="
          f"{crossover['fig19']['ok']}")
    print(f"wrote {args.out} ({out['wall_s']}s)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
