"""Benchmark: trace-driven application simulation (Table 3 on the engine).

One artifact (``BENCH_apps.json``) with one row per (app, mode, rank
count):

* **predicted-vs-paper efficiency** — the Program-IR apps model
  (``apps.py``: per-rank halo/compute/allreduce programs executed on the
  discrete-event engine, congestion emergent) against the paper's Table 3
  anchors where they exist (2 and 512 ranks; 512 is the calibration
  point, 2 a prediction);
* **compiled vs interpreted throughput** — simulated app-iterations per
  wall second on both executors of ``run_program`` (the interpreted heap
  scheduler vs the vectorized level programs of
  ``core/exanet/program_compiled.py``), with a ≤1e-9 agreement guard:
  every timed row first checks the two backends return the same latency
  and per-rank clocks;
* **paper-scale weak-scaling predictions** — 1024/2048/4096-rank rows
  (scaled-torus tiers) that only the compiled backend makes practical to
  sweep; the interpreter is timed only through 512 ranks (at 1024 it
  still runs once, for the agreement guard);
* **beta vs retired alpha** — the per-(app, mode) MPI-stack residual
  ``beta`` that replaced the old closed-form fudge factor;
* **Monte-Carlo scenario rows** (PR 6) — N perturbed scenarios of one
  iteration (per-scenario compute skew x byte jitter) executed as ONE
  array program (``run_program_scenarios`` -> ``bind_arrays``: no N
  Program objects, no N scheduler probes) vs the per-binding lane
  (``rebind_program`` + ``run_program_many``), fresh random draws every
  timed repetition so neither lane hits a warm bind cache; batched and
  per-binding results are cross-checked to <=1e-9 and ``batch_speedup``
  is recorded per row (schema: DESIGN.md §6).

Run: PYTHONPATH=src python benchmarks/apps_sweep.py [--smoke]
         [--min-runs N] [--engine numpy|jax]

Timing windows have a ``--min-runs`` floor (default 5): a 0.2 s budget
fits only ~2 interpreted runs at 512 ranks, and single-sample throughput
rows are noise.  Every row records its ``wall_s``.

``--smoke`` (the CI benchmark step) drops the 64/512-rank and prediction
rows and shortens timed windows, but still runs the compiled backend and
its agreement guard end to end; per the BENCH schema rules (DESIGN.md
§6), smoke artifacts omit the acceptance keys
(``table3_max_abs_error_pts_512``, ``prediction_max_abs_error_pts_2``,
``iters_per_sec_at_512``, ``compiled_speedup_at_512``) so a smoke run can
never masquerade as the full sweep.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.exanet.apps import ALL_APPS, PAPER_TABLE3  # noqa: E402

RANKS = (2, 8, 64, 512)
SMOKE_RANKS = (2, 8)
#: weak-scaling predictions beyond the prototype (scaled-torus tiers);
#: compiled-backend only — interpreting a 4096-rank iteration takes tens
#: of seconds, sweeping it is impractical
PREDICT_RANKS = (1024, 2048, 4096)
MODES = ("weak", "strong")
AGREEMENT_RTOL = 1e-9


def _iterations_per_sec(model, mode: str, n: int, min_wall_s: float,
                        min_runs: int, backend: str,
                        engine: str = "numpy") -> tuple:
    """Simulated app-iterations per wall second (cold costs excluded: the
    first run builds routes/paths — and, for the compiled backend, the
    lowered artifact — then we time steady-state runs)."""
    prog = model.emit_iteration(mode, n)
    mpi = model.mpi_for(n)
    mpi.run_program(prog, backend=backend, engine=engine)  # warm / compile
    runs, wall = 0, 0.0
    t0 = time.perf_counter()
    while wall < min_wall_s or runs < min_runs:
        mpi.run_program(prog, backend=backend, engine=engine)
        runs += 1
        wall = time.perf_counter() - t0
    return runs / wall, runs, wall


def _agreement_rel(model, mode: str, n: int) -> float:
    """Max relative deviation (latency + per-rank clocks) between the
    compiled and interpreted executors on one iteration."""
    prog = model.emit_iteration(mode, n)
    mpi = model.mpi_for(n)
    a = mpi.run_program(prog, backend="interp")
    b = mpi.run_program(prog, backend="compiled")
    rel = abs(b.latency_us - a.latency_us) / max(abs(a.latency_us), 1e-12)
    for x, y in zip(a.clocks, b.clocks):
        rel = max(rel, abs(y - x) / max(abs(x), 1e-12))
    assert (a.n_sends, a.n_collectives) == (b.n_sends, b.n_collectives)
    return rel


def _row(model, app: str, mode: str, n: int, ev: dict, sim) -> dict:
    paper = PAPER_TABLE3[app][mode].get(n)
    eff_pct = round(100 * ev["efficiency"], 1)
    return {
        "app": app, "mode": mode, "nranks": n,
        "efficiency_pct": eff_pct,
        "paper_pct": paper,
        "error_pts": (round(eff_pct - paper, 1)
                      if paper is not None else None),
        "calibrated": ev["calibrated"],
        "comm_fraction": round(ev["comm_fraction"], 4),
        "t_iter_us": round(ev["t_iter_us"], 1),
        "sim_comm_us": round(sim.comm_us, 2),
        "n_sends": sim.n_sends,
        "beta": round(ev["beta"], 4),
        "alpha_retired": round(ev["alpha_retired"], 3),
    }


def sweep(ranks: tuple[int, ...], min_wall_s: float,
          min_runs: int, engine: str = "numpy") -> list[dict]:
    rows = []
    for app, factory in ALL_APPS.items():
        model = factory()
        for mode in MODES:
            for n in ranks:
                ev = model._eval(mode, n)
                sim = model._simulate(mode, n)
                rel = _agreement_rel(model, mode, n)
                assert rel <= AGREEMENT_RTOL, \
                    f"{app}/{mode}@{n}: compiled deviates {rel:.2e}"
                ips_i, runs_i, wall_i = _iterations_per_sec(
                    model, mode, n, min_wall_s, min_runs, "interp")
                ips_c, runs_c, wall_c = _iterations_per_sec(
                    model, mode, n, min_wall_s, min_runs, "compiled",
                    engine)
                row = _row(model, app, mode, n, ev, sim)
                row.update({
                    "engine": engine,
                    "agreement_rel": rel,
                    "interp": {"sim_iterations_per_sec": round(ips_i, 1),
                               "timed_runs": runs_i,
                               "wall_s": round(wall_i, 4)},
                    "compiled": {"sim_iterations_per_sec": round(ips_c, 1),
                                 "timed_runs": runs_c,
                                 "wall_s": round(wall_c, 4)},
                    "speedup_compiled": round(ips_c / ips_i, 2),
                })
                rows.append(row)
                anchor = (f" paper={row['paper_pct']}"
                          f" err={row['error_pts']:+.1f}"
                          if row["paper_pct"] else "")
                print(f"{app:7s} {mode:6s} N={n:4d}  "
                      f"eff={row['efficiency_pct']:5.1f}%{anchor}  "
                      f"interp {ips_i:7.1f} it/s  compiled {ips_c:7.1f} "
                      f"it/s  ({row['speedup_compiled']:.1f}x, "
                      f"agree {rel:.1e})")
    return rows


def predict_rows(min_wall_s: float, min_runs: int,
                 engine: str = "numpy") -> list[dict]:
    """Weak-scaling predictions at 1024-4096 ranks: compiled-only timing
    (one interpreted run at 1024 keeps the agreement guard honest at the
    first beyond-prototype tier)."""
    rows = []
    for app, factory in ALL_APPS.items():
        model = factory()
        for n in PREDICT_RANKS:
            ev = model._eval("weak", n)
            sim = model._simulate("weak", n)
            rel = None
            if n == PREDICT_RANKS[0]:
                rel = _agreement_rel(model, "weak", n)
                assert rel <= AGREEMENT_RTOL, \
                    f"{app}/weak@{n}: compiled deviates {rel:.2e}"
            ips_c, runs_c, wall_c = _iterations_per_sec(
                model, "weak", n, min_wall_s, min_runs, "compiled",
                engine)
            row = _row(model, app, "weak", n, ev, sim)
            row.update({
                "prediction": True,
                "engine": engine,
                "agreement_rel": rel,
                "compiled": {"sim_iterations_per_sec": round(ips_c, 1),
                             "timed_runs": runs_c,
                             "wall_s": round(wall_c, 4)},
            })
            rows.append(row)
            print(f"{app:7s} weak   N={n:4d}  "
                  f"eff={row['efficiency_pct']:5.1f}% (prediction)  "
                  f"compiled {ips_c:7.1f} it/s"
                  + (f"  (agree {rel:.1e})" if rel is not None else ""))
    return rows


def scenario_rows(ranks, n_scenarios: int, min_wall_s: float,
                  min_runs: int, engine: str = "numpy") -> list[dict]:
    """Monte-Carlo scenario sweeps (PR 6): N perturbed copies of one
    weak-scaling iteration — per-scenario compute skew (0.9-1.1x) and
    point-to-point byte jitter (0.8-1.2x) — as ONE array program
    (``run_program_scenarios``) vs the per-binding lane
    (``rebind_program`` + ``run_program_many``, which probes each
    distinct payload).  Every timed repetition draws fresh scales, so
    neither lane reuses a warm bind; the first draw cross-checks batched
    against per-binding results to <=1e-9 (and the batched lane against
    the interpreter via ``check=``)."""
    import numpy as np

    from repro.core.exanet.program_compiled import (extract_data,
                                                    rebind_program)
    rows = []
    for app, factory in ALL_APPS.items():
        model = factory()
        for n in ranks:
            prog = model.emit_iteration("weak", n)
            mpi = model.mpi_for(n)
            mpi.run_program(prog, backend="compiled")  # warm artifact
            comp, post, _ = extract_data(prog)
            base_c = np.array(comp, dtype=np.float64)
            base_p = np.array(post, dtype=np.float64)
            rng = np.random.default_rng(n)

            def draw():
                return (rng.uniform(0.9, 1.1, n_scenarios),
                        rng.uniform(0.8, 1.2, n_scenarios))

            def per_binding(cs, bs):
                progs = [rebind_program(prog,
                                        compute_us=base_c * c,
                                        post_nbytes=np.rint(base_p * b))
                         for c, b in zip(cs, bs)]
                return mpi.run_program_many(progs, backend="compiled",
                                            engine=engine)

            # agreement: batched vs per-binding on one draw, plus the
            # interpreter cross-check built into run_program_scenarios
            cs, bs = draw()
            got = mpi.run_program_scenarios(
                prog, compute_scale=cs, byte_scale=bs, engine=engine,
                check=3, rtol=AGREEMENT_RTOL)
            ref = per_binding(cs, bs)
            rel = max(abs(g.latency_us - r.latency_us)
                      / max(abs(r.latency_us), 1e-12)
                      for g, r in zip(got, ref))
            assert rel <= AGREEMENT_RTOL, \
                f"{app}@{n}: scenario batch deviates {rel:.2e}"

            lanes = {}
            for lane, fn in (("batched", lambda c, b:
                              mpi.run_program_scenarios(
                                  prog, compute_scale=c, byte_scale=b,
                                  engine=engine)),
                             ("per_binding", per_binding)):
                runs, wall = 0, 0.0
                t0 = time.perf_counter()
                while wall < min_wall_s or runs < min_runs:
                    c, b = draw()
                    fn(c, b)
                    runs += 1
                    wall = time.perf_counter() - t0
                lanes[lane] = {
                    "scenarios_per_sec": round(n_scenarios * runs / wall,
                                               1),
                    "timed_runs": runs, "wall_s": round(wall, 4)}
            row = {"app": app, "mode": "weak", "nranks": n,
                   "engine": engine, "n_scenarios": n_scenarios,
                   "agreement_rel": rel, **lanes,
                   "batch_speedup": round(
                       lanes["batched"]["scenarios_per_sec"]
                       / lanes["per_binding"]["scenarios_per_sec"], 2)}
            rows.append(row)
            print(f"{app:7s} scen   N={n:4d}  x{n_scenarios}  "
                  f"batched {lanes['batched']['scenarios_per_sec']:8.1f} "
                  f"scen/s  per-binding "
                  f"{lanes['per_binding']['scenarios_per_sec']:8.1f}  "
                  f"({row['batch_speedup']:.1f}x, agree {rel:.1e})")
    return rows


def main(out_path: str = "BENCH_apps.json", smoke: bool = False,
         min_runs: int = 5, engine: str = "numpy") -> None:
    ranks = SMOKE_RANKS if smoke else RANKS
    min_wall = 0.05 if smoke else 0.2
    rows = sweep(ranks, min_wall, min_runs, engine)
    preds = [] if smoke else predict_rows(min_wall, min_runs, engine)
    scen = scenario_rows((max(ranks),), 8 if smoke else 32,
                         min_wall, 2 if smoke else min(min_runs, 3),
                         engine)
    out: dict = {"ranks": list(ranks),
                 "prediction_ranks": [] if smoke else list(PREDICT_RANKS),
                 "min_runs": min_runs,
                 "engine": engine,
                 "agreement_rtol": AGREEMENT_RTOL,
                 "results": rows, "predictions": preds,
                 "scenario_results": scen}
    betas = {f"{r['app']}/{r['mode']}": {"beta": r["beta"],
                                         "alpha_retired": r["alpha_retired"]}
             for r in rows if r["nranks"] == max(ranks)}
    out["beta_vs_alpha_retired"] = betas
    if not smoke:
        # acceptance keys: full sweeps only (see module docstring)
        err512 = [abs(r["error_pts"]) for r in rows
                  if r["nranks"] == 512 and r["error_pts"] is not None]
        err2 = [abs(r["error_pts"]) for r in rows
                if r["nranks"] == 2 and r["error_pts"] is not None]
        at512 = [r for r in rows if r["nranks"] == 512]
        ips512 = [r["compiled"]["sim_iterations_per_sec"] for r in at512]
        spd512 = [r["speedup_compiled"] for r in at512]
        out["table3_max_abs_error_pts_512"] = max(err512)
        out["prediction_max_abs_error_pts_2"] = max(err2)
        out["iters_per_sec_at_512"] = {"min": min(ips512),
                                       "max": max(ips512)}
        out["interp_iters_per_sec_at_512"] = {
            "min": min(r["interp"]["sim_iterations_per_sec"]
                       for r in at512),
            "max": max(r["interp"]["sim_iterations_per_sec"]
                       for r in at512)}
        out["compiled_speedup_at_512"] = {"min": min(spd512),
                                          "max": max(spd512)}
        out["compiled_max_ranks"] = max(
            (r["nranks"] for r in preds), default=None)
        sb = [r["batch_speedup"] for r in scen]
        out["scenario_batch_speedup_at_512"] = {"min": min(sb),
                                                "max": max(sb)}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {out_path}")
    worst = max((r for r in rows), key=lambda r: r["beta"])
    print(f"largest residual: {worst['app']}/{worst['mode']} "
          f"beta={worst['beta']:.3f} vs retired alpha="
          f"{worst['alpha_retired']:.2f}")
    if not smoke:
        print(f"Table 3 max |error|: {out['table3_max_abs_error_pts_512']}"
              f" pts at 512 (calibrated), "
              f"{out['prediction_max_abs_error_pts_2']} pts at 2 "
              f"(predicted); compiled {out['iters_per_sec_at_512']['min']:.0f}"
              f"-{out['iters_per_sec_at_512']['max']:.0f} sim-iters/s @512 "
              f"({out['compiled_speedup_at_512']['min']:.1f}-"
              f"{out['compiled_speedup_at_512']['max']:.1f}x interp), "
              f"predictions to {out['compiled_max_ranks']} ranks")
        assert out["table3_max_abs_error_pts_512"] <= 0.5, \
            "512-rank cells are calibrated and must match Table 3"
        assert out["prediction_max_abs_error_pts_2"] <= 7.0, \
            "2-rank predictions must stay in the DESIGN.md §7 band"
        assert out["compiled_speedup_at_512"]["min"] >= 8.0, \
            "compiled run_program must be >=8x the interpreter at 512"
        assert out["scenario_batch_speedup_at_512"]["min"] >= 5.0, \
            "batched scenario sweep must be >=5x the per-binding lane " \
            "at 512 ranks"
    # the IR's whole point: the residual must not exceed the retired fudge
    for k, v in betas.items():
        assert v["beta"] <= v["alpha_retired"] + 1e-9, \
            f"{k}: beta {v['beta']} exceeds retired alpha {v['alpha_retired']}"


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--min-runs", type=int, default=5,
                    help="floor on timed runs per throughput row")
    ap.add_argument("--engine", default="numpy",
                    choices=("numpy", "jax"),
                    help="scan backend of the compiled lanes")
    args = ap.parse_args()
    main(smoke=args.smoke, min_runs=args.min_runs, engine=args.engine)
