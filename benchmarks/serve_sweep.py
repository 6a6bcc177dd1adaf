"""Benchmark: simulated LM serving under open-loop traffic
(``BENCH_serve.json``, ROADMAP item 1).

Whole load sweeps run through the batched compiled substrate: every step
state a continuous-batching server can occupy — (decoding slots,
prefilling slots, KV bucket) x Monte-Carlo draw, with per-column compute
skew, per-column collective payloads (``site_scale``) and per-rank
arrival jitter (the ``t0`` axis) — binds as one column of ONE
``run_program_scenarios`` call per rank count, and the open-loop replay
(Poisson and bursty-trace arrivals, continuous batching over slots) then
walks every load point as table lookups.  Reported per (arch, nranks,
load point): per-request latency CDFs and p50/p99/p99.9, TTFT and
queueing quantiles, goodput — with the goodput-vs-load knee per rank
count (largest offered load still served at >= 95% of the offered rate;
past it the open-loop queue diverges).

The ``speedup`` section measures the fast path against the naive lane —
one ``rebind_program`` + ``run_program`` per simulated step, the exact
same column payloads — on identical truncated workloads; the two lanes'
per-request latencies must agree to <=1e-9 (they share every line of
queueing logic, so lane agreement is executor agreement), and every step
table is built with sampled interpreter cross-checks (``check=``,
<=1e-9) on top.

Run: PYTHONPATH=src python benchmarks/serve_sweep.py [--smoke]
         [--engine numpy|jax] [--arch <id>]

``--smoke`` (the CI lane) runs a tiny Poisson sweep at 16 ranks — small
enough that the pairwise alltoall KV exchange is active — with the same
agreement guards, and per the BENCH schema rules (DESIGN.md §6) omits
the acceptance keys (``scenario_speedup``, ``knees``-derived capacity
claims) so a smoke artifact can never masquerade as the full sweep.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.serve import traffic  # noqa: E402
from repro.serve.sim import ServeSim, ServeSimSpec  # noqa: E402

AGREEMENT_RTOL = 1e-9
#: full-sweep rank counts (512 = the prototype's cores; beyond = scaled
#: torus tiers) and the reduced grid for the biggest tiers
RANKS = (512, 1024)
PREDICT_RANKS = (2048, 4096)
LOAD_FRACS = (0.3, 0.5, 0.7, 0.85, 1.0, 1.2)
PREDICT_LOAD_FRACS = (0.5, 0.85, 1.2)
KNEE_FRAC = 0.95


def capacity_estimate_rps(sim: ServeSim, tab, prompt_mean: int,
                          out_mean: int, n: int = 0) -> float:
    """Measured saturation throughput that anchors the load grid: replay
    a backlog (every request arrives at t=0) through the step table and
    take n/makespan.  Accurate by construction for any compute/comm
    balance point (an analytic slots/step estimate misprices the
    prefill-heavy steps badly on compute-bound configs)."""
    sp = sim.spec
    n = n or 8 * sp.slots
    wl = traffic.trace_workload(np.zeros(n),
                                np.full(n, prompt_mean, dtype=np.int64),
                                np.full(n, out_mean, dtype=np.int64))
    res = traffic.replay(wl, slots=sp.slots, prefill_chunk=sp.prefill_chunk,
                         window=sp.window, kv_bucket=sp.kv_bucket,
                         step_time=tab.lookup)
    return n / float(res.done_us.max()) * 1e6


def run_load_point(sim: ServeSim, tab, offered_rps: float, n_requests: int,
                   reps: int, prompt_mean: int, out_mean: int,
                   seed: int, arrivals: str = "poisson") -> dict:
    sp = sim.spec
    lat, ttft, queue = [], [], []
    steps = 0
    sim_us = 0.0
    tokens = 0
    t0 = time.perf_counter()
    for rep in range(reps):
        if arrivals == "poisson":
            wl = traffic.poisson_workload(offered_rps, n_requests,
                                          seed + rep,
                                          prompt_tokens=prompt_mean,
                                          out_tokens=out_mean)
        else:  # bursty trace: groups of slots-size bursts, same mean rate
            burst = max(2, sp.slots)
            n_bursts = int(np.ceil(n_requests / burst))
            times = np.repeat(np.arange(n_bursts)
                              * (burst / offered_rps * 1e6),
                              burst)[:n_requests]
            rng = np.random.default_rng(seed + rep)
            wl = traffic.trace_workload(
                times, rng.integers(max(1, prompt_mean // 2),
                                    prompt_mean * 3 // 2 + 1, n_requests),
                rng.integers(max(1, out_mean // 2),
                             out_mean * 3 // 2 + 1, n_requests))
        res = traffic.replay(wl, slots=sp.slots,
                             prefill_chunk=sp.prefill_chunk,
                             window=sp.window, kv_bucket=sp.kv_bucket,
                             step_time=tab.lookup)
        lat.append(res.latency_us)
        ttft.append(res.ttft_us)
        queue.append(res.queue_us)
        steps += res.n_steps
        tokens += res.tokens_out
        span = res.done_us.max() - res.arrive_us.min()
        sim_us += span
    lat = np.concatenate(lat)
    n_total = lat.size
    goodput_rps = n_total / max(sim_us, 1e-30) * 1e6
    return {
        "arrivals": arrivals,
        "offered_rps": round(offered_rps, 3),
        "n_requests": n_total, "mc_reps": reps,
        "latency_us": traffic.quantiles(lat),
        "ttft_us": traffic.quantiles(np.concatenate(ttft)),
        "queue_mean_us": float(np.mean(np.concatenate(queue))),
        "goodput_rps": round(goodput_rps, 3),
        "goodput_tok_s": round(tokens / max(sim_us, 1e-30) * 1e6, 1),
        "steps": steps,
        "latency_cdf": traffic.cdf_points(lat, 32),
        "wall_s": round(time.perf_counter() - t0, 4),
    }


def sweep_rank(arch: str, nranks: int, *, load_fracs, n_requests: int,
               reps: int, mc: int, check: int, engine: str,
               prompt_mean: int = 256, out_mean: int = 24,
               slots: int = 8, smoke: bool = False) -> dict:
    spec = ServeSimSpec(arch=arch, nranks=nranks, slots=slots,
                        window=1024 if not smoke else 256,
                        prefill_chunk=256 if not smoke else 64,
                        kv_buckets=4 if not smoke else 2)
    sim = ServeSim(spec)
    t0 = time.perf_counter()
    tab = sim.build_table(mc=mc, rng=nranks, engine=engine, check=check,
                          rtol=AGREEMENT_RTOL)
    table_wall = time.perf_counter() - t0
    cap = capacity_estimate_rps(sim, tab, prompt_mean, out_mean)
    rows = []
    for f in load_fracs:
        row = run_load_point(sim, tab, f * cap, n_requests, reps,
                             prompt_mean, out_mean, seed=nranks * 1000)
        row.update({"arch": arch, "nranks": nranks, "load_frac": f,
                    "engine": engine})
        rows.append(row)
        q = row["latency_us"]
        print(f"{arch:16s} N={nranks:5d} load={f:4.2f} "
              f"({row['offered_rps']:8.2f} rps)  "
              f"p50={q['p50']/1e3:9.1f}ms p99={q['p99']/1e3:9.1f}ms "
              f"p99.9={q['p999']/1e3:9.1f}ms  "
              f"goodput={row['goodput_rps']:8.2f} rps")
    # one bursty-trace point at the middle load (trace-arrival lane)
    mid = load_fracs[len(load_fracs) // 2]
    trow = run_load_point(sim, tab, mid * cap, n_requests, max(1, reps - 1),
                          prompt_mean, out_mean, seed=nranks * 1000 + 77,
                          arrivals="trace_bursty")
    trow.update({"arch": arch, "nranks": nranks, "load_frac": mid,
                 "engine": engine})
    rows.append(trow)
    knee = traffic.knee_point(
        [r["offered_rps"] for r in rows if r["arrivals"] == "poisson"],
        [r["goodput_rps"] for r in rows if r["arrivals"] == "poisson"],
        KNEE_FRAC)
    print(f"{arch:16s} N={nranks:5d} knee={knee} rps "
          f"(capacity est {cap:.2f} rps, table {table_wall:.2f}s, "
          f"{len(tab.states)}x{mc} columns)")
    return {"rows": rows, "knee_offered_rps": knee,
            "capacity_est_rps": round(cap, 3),
            "table": {"n_states": len(tab.states), "mc": mc,
                      "n_columns": len(tab.states) * mc,
                      "wall_s": round(table_wall, 4),
                      "interp_checked_columns": check},
            "_sim": sim, "_tab": tab,
            "_workload": (prompt_mean, out_mean)}


def speedup_row(swept: dict, *, engine: str, per_step_steps: int,
                offered_frac: float = 0.85) -> dict:
    """Batched-vs-per-step lane comparison on identical workloads.

    Batched rate counts the table build + every replayed step of the
    full load sweep; the per-step lane replays a truncated workload with
    one rebind + run_program per step (the same column payloads — lane
    agreement <=1e-9 is asserted on the per-request latencies)."""
    sim: ServeSim = swept["_sim"]
    tab = swept["_tab"]
    prompt_mean, out_mean = swept["_workload"]
    sp = sim.spec
    total_steps = sum(r["steps"] for r in swept["rows"])
    total_wall = (swept["table"]["wall_s"]
                  + sum(r["wall_s"] for r in swept["rows"]))
    batched_rate = total_steps / total_wall

    # truncated workload sized to ~per_step_steps steps
    slot_steps = int(np.ceil(prompt_mean / sp.prefill_chunk)) + out_mean
    n_req = max(2, int(per_step_steps * sp.slots / slot_steps))
    cap = swept["capacity_est_rps"]
    wl = traffic.poisson_workload(offered_frac * cap, n_req, 12345,
                                  prompt_tokens=prompt_mean,
                                  out_tokens=out_mean)
    kw = dict(slots=sp.slots, prefill_chunk=sp.prefill_chunk,
              window=sp.window, kv_bucket=sp.kv_bucket)
    ref = traffic.replay(wl, step_time=tab.lookup, **kw)

    calls = [0]

    def per_step(nd, npf, kvb, i):
        calls[0] += 1
        return sim.step_time_single(tab, (nd, npf, kvb), i % tab.mc,
                                    backend="auto", engine=engine)

    t0 = time.perf_counter()
    naive = traffic.replay(wl, step_time=per_step, **kw)
    per_step_wall = time.perf_counter() - t0
    per_step_rate = calls[0] / per_step_wall

    lane_rel = float(np.max(np.abs(naive.done_us - ref.done_us)
                            / np.maximum(np.abs(ref.done_us), 1e-12)))
    assert lane_rel <= AGREEMENT_RTOL, \
        f"per-step lane deviates from the batched table: {lane_rel:.2e}"
    speedup = batched_rate / per_step_rate
    print(f"speedup @N={sp.nranks}: batched {batched_rate:9.1f} steps/s "
          f"(table+replay, {total_steps} steps) vs per-step "
          f"{per_step_rate:7.2f} steps/s ({calls[0]} steps) -> "
          f"{speedup:.1f}x  (lane agree {lane_rel:.1e})")
    return {
        "nranks": sp.nranks, "arch": sp.arch, "engine": engine,
        "batched": {"steps": total_steps,
                    "wall_s": round(total_wall, 4),
                    "steps_per_sec": round(batched_rate, 1),
                    "includes_table_build": True},
        "per_step": {"steps": calls[0],
                     "wall_s": round(per_step_wall, 4),
                     "steps_per_sec": round(per_step_rate, 2)},
        "scenario_speedup": round(speedup, 1),
        "lane_agreement_rel": lane_rel,
    }


def strip_private(swept: dict) -> dict:
    return {k: v for k, v in swept.items() if not k.startswith("_")}


#: nominal host execution rates for the calibration row's roofline
#: prediction — the *ratio* is the anchor, not an absolute claim
HOST_RATE_GFLOPS = 10.0
HOST_BW_GBPS = 10.0


def calibration_row(arch: str = "exanest-lm-100m", *, requests: int = 4,
                    slots: int = 2, window: int = 64, max_new: int = 4,
                    prompt_len: int = 5) -> dict:
    """Measured-vs-predicted anchor (DESIGN.md §6): run the REAL
    slot-based engine (the ``launch/serve.py`` path — jax forward passes,
    wall clock) on a reduced config, take measured wall time per engine
    step, and fold it back onto :func:`repro.roofline.analysis.
    lm_serve_step_cost` via ``serve_step_calibration``.  The recorded
    ``measured_over_predicted`` ratio is the single constant that maps
    the closed form onto this host.  Skipped (with reason) where jax or
    a model backend is unavailable — the simulated sweep above never
    depends on it."""
    try:
        import jax
        from repro.config import reduced
        from repro.configs import get
        from repro.models import build_model
        from repro.roofline.analysis import serve_step_calibration
        from repro.serve.engine import ServeEngine
    except ImportError as e:                        # pragma: no cover
        return {"arch": arch, "skipped": f"import: {e}"}
    cfg = reduced(get(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=slots, window=window)
    rng = np.random.default_rng(0)
    rids = [eng.submit(list(rng.integers(0, cfg.vocab_size,
                                         size=prompt_len)),
                       max_new_tokens=max_new) for _ in range(requests)]
    eng.step()                     # compile outside the measured window
    t0 = time.perf_counter()
    steps = 1 + eng.run_until_idle(max_steps=2000)
    dt = time.perf_counter() - t0
    stats = eng.request_steps()
    # mean occupied decode slots over the run: request-steps / engine steps
    busy = sum(d - s for s, d in stats.values())
    n_decode = max(1.0, busy / max(steps, 1))
    cal = serve_step_calibration(
        cfg, measured_step_us=dt / max(steps, 1) * 1e6,
        n_decode=n_decode, decode_kv=prompt_len + max_new / 2,
        rate_flops_per_us=HOST_RATE_GFLOPS * 1e3,
        bw_bytes_per_us=HOST_BW_GBPS * 1e3)
    cal.update({"arch": arch, "reduced": True, "engine_steps": steps,
                "requests": len(rids), "wall_s": round(dt, 4),
                "mean_decode_slots": round(n_decode, 3),
                "host_rate_gflops": HOST_RATE_GFLOPS,
                "host_bw_gbps": HOST_BW_GBPS})
    print(f"calibration {arch} (reduced): measured "
          f"{cal['measured_step_us']:.0f} us/step vs predicted "
          f"{cal['predicted_step_us']:.0f} us/step -> "
          f"ratio {cal['measured_over_predicted']:.2f} "
          f"({steps} steps, {dt:.2f}s)")
    return cal


def main(out_path: str = "BENCH_serve.json", smoke: bool = False,
         engine: str = "numpy", arch: str = "deepseek-7b") -> None:
    out: dict = {"engine": engine, "agreement_rtol": AGREEMENT_RTOL,
                 "knee_criterion":
                     f"largest offered load with goodput >= "
                     f"{KNEE_FRAC} * offered (open loop)",
                 "results": [], "knees": {}, "tables": {}}
    if smoke:
        out["smoke"] = True
        out["ranks"] = [16]
        sw = sweep_rank(arch, 16, load_fracs=(0.5, 1.0), n_requests=48,
                        reps=1, mc=2, check=3, engine=engine, slots=4,
                        prompt_mean=64, out_mean=8, smoke=True)
        out["results"] += sw["rows"]
        out["knees"]["16"] = {"knee_offered_rps": sw["knee_offered_rps"],
                              "capacity_est_rps": sw["capacity_est_rps"]}
        out["tables"]["16"] = sw["table"]
        out["speedup"] = [speedup_row(sw, engine=engine,
                                      per_step_steps=10)]
        out["calibration"] = calibration_row()
    else:
        out["ranks"] = list(RANKS)
        out["prediction_ranks"] = list(PREDICT_RANKS)
        out["arch"] = arch
        speedups = []
        for n in RANKS:
            sw = sweep_rank(arch, n, load_fracs=LOAD_FRACS,
                            n_requests=320, reps=3, mc=3, check=4,
                            engine=engine)
            out["results"] += sw["rows"]
            out["knees"][str(n)] = {
                "knee_offered_rps": sw["knee_offered_rps"],
                "capacity_est_rps": sw["capacity_est_rps"]}
            out["tables"][str(n)] = sw["table"]
            if n == 512:
                speedups.append(speedup_row(sw, engine=engine,
                                            per_step_steps=24))
        # the repo's own config at the prototype's 512 cores (second
        # lane: a model small enough that communication dominates)
        sw = sweep_rank("exanest-lm-100m", 512,
                        load_fracs=(0.5, 0.85, 1.2), n_requests=240,
                        reps=2, mc=3, check=4, engine=engine)
        out["results"] += sw["rows"]
        out["knees"]["exanest-lm-100m/512"] = {
            "knee_offered_rps": sw["knee_offered_rps"],
            "capacity_est_rps": sw["capacity_est_rps"]}
        for n in PREDICT_RANKS:
            sw = sweep_rank(arch, n, load_fracs=PREDICT_LOAD_FRACS,
                            n_requests=160, reps=2, mc=2, check=1,
                            engine=engine)
            for r in sw["rows"]:
                r["prediction"] = True
            out["results"] += sw["rows"]
            out["knees"][str(n)] = {
                "knee_offered_rps": sw["knee_offered_rps"],
                "capacity_est_rps": sw["capacity_est_rps"],
                "prediction": True}
            out["tables"][str(n)] = sw["table"]
        out["speedup"] = speedups
        out["calibration"] = calibration_row()
        # acceptance keys: full sweeps only (see module docstring)
        out["scenario_speedup_at_512"] = min(
            s["scenario_speedup"] for s in speedups)
        assert out["scenario_speedup_at_512"] >= 10.0, \
            "batched serving sweep must be >=10x the per-step lane at 512"
        for n in RANKS:
            rows = [r for r in out["results"]
                    if r["nranks"] == n and r["arch"] == arch
                    and r["arrivals"] == "poisson"]
            assert len(rows) == len(LOAD_FRACS), f"missing rows at {n}"
            assert all(np.isfinite(r["latency_us"]["p999"])
                       for r in rows), f"non-finite tail at {n}"
            assert out["knees"][str(n)]["knee_offered_rps"] is not None, \
                f"no knee found at {n}: widen the load grid"
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {out_path}")
    if not smoke:
        print(f"scenario_speedup @512: {out['scenario_speedup_at_512']}x; "
              f"knees: " + ", ".join(
                  f"{k}={v['knee_offered_rps']}"
                  for k, v in out["knees"].items()))


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="numpy", choices=("numpy", "jax"),
                    help="scan backend of the batched compiled lane")
    ap.add_argument("--arch", default="deepseek-7b",
                    help="serving config for the full sweep")
    args = ap.parse_args()
    main(smoke=args.smoke, engine=args.engine, arch=args.arch)
