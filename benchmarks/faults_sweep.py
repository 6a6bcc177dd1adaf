"""Benchmark: fault & congestion scenario engine (DESIGN.md §2.10).

One artifact (``BENCH_faults.json``) with five blocks:

* **degradation classes** — paper-style weak-scaling efficiency for
  HPCG/LAMMPS/miniFE under one sampled fault of each class: a dead link
  (structural: routes change, the app runs on a *degraded machine*
  variant keyed by the fault signature), a hot link, a lossy link
  (§4.5.3 block-replay cost ``1/(1-p)``), extra per-link latency, and a
  slow "hot" rank.  Non-structural classes ride the batched scenario
  axes (``link_scale`` / ``link_latency_us`` / ``compute_scale``); every
  row carries a ≤1e-9 degraded compiled-vs-interpreted agreement guard.
* **Monte-Carlo fault sweep** — N sampled link-degradation sets
  (hot + lossy + retimer latency) × one app iteration, costed as ONE
  ``run_program_scenarios`` replay (``batch_fault_axes``: column ``j``
  carries fault set ``j``) vs the per-fault-set lane (one statically
  degraded ``ExanetMPI`` twin per set — fresh topology, routes and
  compiled artifact each time, which is what batching amortizes);
  fresh fault draws every timed repetition, first draw cross-checked
  lane-vs-lane and against the interpreter to ≤1e-9.
* **interference curves** — a halo-exchange app co-located with a
  background tenant on *shared* QFDBs (``interleave_qfdb``: both
  tenants' cross-board traffic funnels through each board's single
  network MPSoC), neighbour load swept as ``byte_scale`` columns on the
  background posts only; app efficiency vs neighbour load is emergent
  link contention, not a fitted model.
* **straggler replanning** — train-step time under a slow rank with and
  without replanning: the healthy winner of ``plan_train_sync`` costed
  on the straggler machine vs a fresh plan searched *against* it
  (``TrainSim(rank_compute_scale=...)``), reporting the recovered
  margin; the simulated step-time series is fed through
  ``StragglerMonitor`` to show the ``on_straggle`` hook firing.
* **§5.3 graceful-degradation floor** — the IP-overlay-vs-native ladder
  (native wire 6.42, overlay 4.7, baseline 1.3 Gb/s on the paper's
  5-hop path) as the floor degraded native transport is measured
  against: the fraction of sampled fault sets whose bottleneck still
  beats the overlay.

Run: PYTHONPATH=src python benchmarks/faults_sweep.py [--smoke]
         [--min-runs N] [--engine numpy|jax]

``--smoke`` (the CI benchmark step) shrinks rank counts and fault-set
counts but still runs every block end to end, including the degraded
agreement guards; per the BENCH schema rules (DESIGN.md §6), smoke
artifacts omit the acceptance keys (``mc_batch_speedup_at_512``,
``degraded_agreement_max``, ``interference_min_efficiency``,
``straggler_recovered_margin``) so a smoke run can never masquerade as
the full sweep.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core.exanet.apps import ALL_APPS  # noqa: E402
from repro.core.exanet.faults import (FaultSpec, UnroutableError,  # noqa: E402
                                      all_link_keys, batch_fault_axes,
                                      sample_fault_spec)
from repro.core.exanet.interference import (background_stream,  # noqa: E402
                                            interleave_qfdb, merge_tenants,
                                            neighbor_load_byte_scale)
from repro.core.exanet.ip_overlay import overlay_vs_native_gap  # noqa: E402
from repro.core.machine import ExanetMachine  # noqa: E402

RANKS = (8, 64, 512)
SMOKE_RANKS = (8,)
AGREEMENT_RTOL = 1e-9
#: one sampled fault per class per (app, rank count); structural classes
#: select a degraded machine, the rest ride the batched axes
CLASSES = ("dead_link", "hot_link", "lossy_link", "extra_latency",
           "slow_rank")
LOADS = (0.0, 0.5, 1.0, 2.0, 4.0)


def _occupied_links(mpi, nranks: int) -> list:
    """Links with both endpoints inside the rank-hosting MPSoC prefix
    (the machine places 1 rank/MPSoC, QFDB-major), so a sampled fault
    can actually sit on a route the program uses."""
    used = mpi.rank_core(nranks - 1) // mpi.p.cores_per_mpsoc + 1
    keys = [k for k in all_link_keys(mpi.topo)
            if k[1] < used and k[2] < used]
    return keys or all_link_keys(mpi.topo)[:1]


def _class_spec(cls: str, rng, mpi, nranks: int) -> FaultSpec:
    links = _occupied_links(mpi, nranks)
    pick = lambda k: [links[i] for i in  # noqa: E731
                      rng.choice(len(links), size=min(k, len(links)),
                                 replace=False)]
    if cls == "dead_link":
        return FaultSpec(dead_links=pick(1))
    if cls == "hot_link":
        return FaultSpec(slow_links={k: float(rng.uniform(2.0, 8.0))
                                     for k in pick(2)})
    if cls == "lossy_link":
        return FaultSpec(lossy_links={k: float(rng.uniform(0.05, 0.3))
                                      for k in pick(2)})
    if cls == "extra_latency":
        return FaultSpec(link_extra_latency_us={k: 10.0 for k in pick(2)})
    if cls == "slow_rank":
        return FaultSpec(slow_ranks={int(rng.integers(nranks)):
                                     float(rng.uniform(2.0, 6.0))})
    raise ValueError(cls)


def _deg_agreement(mpi, prog) -> float:
    """Max relative deviation (latency + per-rank clocks) between the
    executors on one *degraded* machine."""
    a = mpi.run_program(prog, backend="interp")
    b = mpi.run_program(prog, backend="compiled")
    rel = abs(b.latency_us - a.latency_us) / max(abs(a.latency_us), 1e-12)
    for x, y in zip(a.clocks, b.clocks):
        rel = max(rel, abs(y - x) / max(abs(x), 1e-12))
    return rel


def class_rows(machine, ranks, engine: str, guards: list) -> list[dict]:
    """Weak-scaling efficiency per (app, degradation class, rank count).
    Degraded efficiency = healthy efficiency x t_healthy / t_degraded —
    the paper's Table-3 metric with the iteration slowed by the fault."""
    rows = []
    for app, factory in ALL_APPS.items():
        model = factory()
        for n in ranks:
            prog = model.emit_iteration("weak", n)
            mpi = machine._mpi_for(n)
            eff_h = model._eval("weak", n)["efficiency"]
            t_h = mpi.run_program(prog, backend="compiled",
                                  engine=engine).latency_us
            for cls in CLASSES:
                rng = np.random.default_rng(
                    abs(hash((app, cls, n))) % (1 << 32))
                for attempt in range(20):
                    spec = _class_spec(cls, rng, mpi, n)
                    try:
                        if spec.degrades_structure:
                            dmpi = machine.degraded(spec)._mpi_for(n)
                            rel = _deg_agreement(dmpi, prog)
                            t_d = dmpi.run_program(
                                prog, backend="compiled",
                                engine=engine).latency_us
                        else:
                            axes = batch_fault_axes([spec], prog)
                            res = machine.cost_program_scenarios(
                                prog, **axes, engine=engine, check=1,
                                rtol=AGREEMENT_RTOL)
                            t_d = res[0].latency_us
                            rel = 0.0  # check=1 raised if > rtol
                        break
                    except UnroutableError:
                        continue  # this draw cut the network; redraw
                else:
                    raise RuntimeError(f"no routable {cls} draw at {n}")
                assert rel <= AGREEMENT_RTOL, \
                    f"{app}/{cls}@{n}: degraded compiled deviates {rel:.2e}"
                guards.append(rel)
                eff_d = eff_h * t_h / t_d
                row = {"app": app, "mode": "weak", "nranks": n,
                       "class": cls, "fault": spec.signature(),
                       "structural": spec.degrades_structure,
                       "t_healthy_us": round(t_h, 2),
                       "t_degraded_us": round(t_d, 2),
                       "slowdown": round(t_d / t_h, 4),
                       "efficiency_pct": round(100 * eff_h, 1),
                       "degraded_efficiency_pct": round(100 * eff_d, 1),
                       "agreement_rel": rel}
                rows.append(row)
                print(f"{app:7s} {cls:13s} N={n:4d}  "
                      f"eff {row['efficiency_pct']:5.1f}% -> "
                      f"{row['degraded_efficiency_pct']:5.1f}%  "
                      f"(x{row['slowdown']:.2f}, {spec.signature()})")
    return rows


def node_failure_block(machine, engine: str) -> dict:
    """Structural node failure at 8 ranks under *block* placement (rank
    = core: 8 ranks on MPSoCs 0-1, MPSoCs 2-3 rank-free): a dead
    intra-QFDB link forces the crossbar relay, a dead relay MPSoC forces
    the *next* relay, and killing every relay is a diagnosable cut
    (``UnroutableError``) — the reroute ladder of DESIGN.md §2.10 as
    data."""
    from repro.core.exanet.mpi import ExanetMPI
    model = ALL_APPS["hpcg"]()
    prog = model.emit_iteration("weak", 8)
    t_h = ExanetMPI().run_program(prog, backend="compiled",
                                  engine=engine).latency_us
    dead_link = FaultSpec(dead_links=[("intra_qfdb", 0, 1)])
    relay_down = FaultSpec(dead_links=[("intra_qfdb", 0, 1)],
                           dead_mpsocs=[2])
    cut = FaultSpec(dead_links=[("intra_qfdb", 0, 1)], dead_mpsocs=[2, 3])
    hmpi = ExanetMPI()
    p2p_h = hmpi.net.rdv_latency(65536, hmpi.topo.route(0, 4))
    out = {"nranks": 8, "placement": "block", "t_healthy_us": round(t_h, 2),
           "p2p_healthy_us": round(p2p_h, 2),
           "route_healthy": [f"{l.kind}({l.src_mpsoc},{l.dst_mpsoc})"
                             for l in hmpi.topo.route(0, 4).links]}
    for name, spec in (("dead_link", dead_link),
                       ("dead_link_and_relay", relay_down)):
        dmpi = ExanetMPI(faults=spec)
        t = dmpi.run_program(prog, backend="compiled",
                             engine=engine).latency_us
        path = dmpi.topo.route(0, 4)
        out[name] = {"fault": spec.signature(), "t_us": round(t, 2),
                     "slowdown": round(t / t_h, 4),
                     # the app hides the reroute behind compute; the raw
                     # point-to-point latency shows its true cost
                     "p2p_us": round(dmpi.net.rdv_latency(65536, path), 2),
                     "route": [f"{l.kind}({l.src_mpsoc},{l.dst_mpsoc})"
                               for l in path.links],
                     "route_cache": dmpi.topo.route_cache_info()}
    try:
        ExanetMPI(faults=cut).run_program(prog)
        raise AssertionError("cut partition must be unroutable")
    except UnroutableError as e:
        out["cut"] = {"fault": cut.signature(), "diagnosis": str(e)}
    print(f"node_failure: p2p {out['p2p_healthy_us']}us -> "
          f"{out['dead_link']['p2p_us']}us (dead link) -> "
          f"{out['dead_link_and_relay']['p2p_us']}us (+dead relay), "
          f"cut -> UnroutableError")
    return out


def mc_rows(machine, n: int, n_sets: int, n_per: int, min_wall_s: float,
            min_runs: int, engine: str, guards: list) -> dict:
    """Monte-Carlo link-degradation sweep at ``n`` ranks: ``n_sets``
    sampled fault sets as ONE batched replay (``batch_fault_axes``) vs
    one statically degraded ``ExanetMPI`` twin per set.  Fresh draws per
    timed repetition; the lanes are cross-checked on the first draw."""
    from repro.core.exanet.mpi import ExanetMPI
    model = ALL_APPS["hpcg"]()
    prog = model.emit_iteration("weak", n)
    mpi = machine._mpi_for(n)
    mpi.run_program(prog, backend="compiled")  # warm artifact + routes
    rng = np.random.default_rng(n)

    def draw(k: int) -> list[FaultSpec]:
        return [sample_fault_spec(rng, mpi.topo, n_slow_links=2,
                                  n_lossy_links=1, extra_latency_us=5.0)
                for _ in range(k)]

    def batched(specs):
        return machine.cost_program_scenarios(
            prog, **batch_fault_axes(specs, prog), engine=engine)

    def per_fault_set(specs):
        out = []
        for s in specs:
            twin = ExanetMPI(mpi.p, ranks_per_mpsoc=mpi._rpm, faults=s,
                             cache=False)
            out.append(twin.run_program(prog, backend="compiled",
                                        engine=engine))
        return out

    # cross-check: batched columns == statically-degraded twins, plus
    # the interpreter twin check built into run_program_scenarios
    specs0 = draw(n_per)
    got = machine.cost_program_scenarios(
        prog, **batch_fault_axes(specs0, prog), engine=engine,
        check=min(2, n_per), rtol=AGREEMENT_RTOL)
    ref = per_fault_set(specs0)
    rel = max(abs(g.latency_us - r.latency_us)
              / max(abs(r.latency_us), 1e-12)
              for g, r in zip(got, ref))
    assert rel <= AGREEMENT_RTOL, \
        f"mc@{n}: batched fault lane deviates {rel:.2e}"
    guards.append(rel)

    lanes = {}
    for lane, fn, k in (("batched", batched, n_sets),
                        ("per_fault_set", per_fault_set, n_per)):
        runs, wall = 0, 0.0
        t0 = time.perf_counter()
        while wall < min_wall_s or runs < min_runs:
            fn(draw(k))
            runs += 1
            wall = time.perf_counter() - t0
        lanes[lane] = {"fault_sets_per_sec": round(k * runs / wall, 2),
                       "n_fault_sets": k, "timed_runs": runs,
                       "wall_s": round(wall, 4)}
    speedup = (lanes["batched"]["fault_sets_per_sec"]
               / lanes["per_fault_set"]["fault_sets_per_sec"])
    lat = [r.latency_us for r in batched(draw(n_sets))]
    out = {"app": "hpcg", "nranks": n, "n_fault_sets": n_sets,
           "engine": engine, "agreement_rel": rel, **lanes,
           "batch_speedup": round(speedup, 2),
           "latency_us": {"p50": round(float(np.median(lat)), 2),
                          "p95": round(float(np.percentile(lat, 95)), 2),
                          "max": round(float(np.max(lat)), 2)}}
    print(f"mc      N={n:4d}  x{n_sets}  batched "
          f"{lanes['batched']['fault_sets_per_sec']:8.2f} sets/s  "
          f"per-fault-set "
          f"{lanes['per_fault_set']['fault_sets_per_sec']:8.2f}  "
          f"({speedup:.1f}x, agree {rel:.1e})")
    return out


def interference_block(n_app: int, n_bg: int, engine: str,
                       guards: list) -> dict:
    """App efficiency vs neighbour load on shared QFDBs: the whole curve
    is one ``byte_scale`` replay over the merged two-tenant Program.
    Runs under *block* placement (rank = core) — ``interleave_qfdb``
    splits each board's cores between the tenants, so both tenants'
    cross-board traffic funnels through the board's single network
    MPSoC onto shared mezzanine links."""
    from repro.core.exanet.mpi import ExanetMPI
    from repro.core.program import halo3d
    app = halo3d(n_app, 65536, compute_us=50.0)
    bg = background_stream(n_bg, iters=12, nbytes=131072)
    a_ranks, b_ranks = interleave_qfdb(n_app, n_bg)
    mix = merge_tenants(app, bg, a_ranks, b_ranks)
    bs = neighbor_load_byte_scale(mix, LOADS)
    res = ExanetMPI().run_program_scenarios(
        mix.program, byte_scale=bs, engine=engine, check=2,
        rtol=AGREEMENT_RTOL)
    guards.append(0.0)  # check=2 raised if > rtol
    app_us = [mix.app_latency_us(r) for r in res]
    eff = [app_us[0] / t for t in app_us]
    out = {"n_app": n_app, "n_bg": n_bg, "engine": engine,
           "placement": "interleave_qfdb",
           "loads": list(LOADS),
           "app_us": [round(t, 2) for t in app_us],
           "efficiency": [round(e, 4) for e in eff]}
    print("interf  " + "  ".join(f"load {ld:g}: {e:.3f}"
                                 for ld, e in zip(LOADS, eff)))
    assert all(b <= a + 1e-9 for a, b in zip(eff, eff[1:])), \
        f"interference must be monotone in neighbour load: {eff}"
    return out


def straggler_block(machine, nranks: int, smoke: bool,
                    engine: str) -> dict:
    """Train-step time under one hot rank, with and without replanning,
    plus the ``StragglerMonitor.on_straggle`` hook firing on the
    simulated step-time series."""
    from repro.core.planner import CollectivePlanner
    from repro.runtime.fault import StragglerMonitor
    from repro.train.cosim import TrainSim, TrainStepSpec
    spec = TrainStepSpec(nranks=nranks)
    rank, factor = 5 % nranks, 4.0
    rcs = np.ones(nranks)
    rcs[rank] = factor
    healthy = TrainSim(spec, machine)
    slow = TrainSim(spec, machine, rank_compute_scale=rcs)
    planner = CollectivePlanner(machine, fidelity="sim", engine=engine)
    gens = 1 if smoke else 2
    h_plan = planner.plan_train_sync(healthy, generations=gens,
                                     engine=engine, check=1)
    t_noreplan = float(slow.cost_candidates([h_plan.chosen],
                                            engine=engine, check=1)[0])
    s_plan = planner.plan_train_sync(slow, generations=gens,
                                     engine=engine, check=1)
    recovered = (t_noreplan - s_plan.step_us) / t_noreplan

    # the runtime hook: healthy cadence, then the straggler appears
    events: list[dict] = []
    mon = StragglerMonitor(
        deadline_factor=1.5,
        on_straggle=lambda step, dt, deadline: events.append(
            {"step": step, "dt_us": round(dt, 2),
             "deadline_us": round(deadline, 2)}))
    for step, dt in enumerate([h_plan.step_us] * 12 + [t_noreplan] * 4):
        mon.observe(step, dt)

    out = {"nranks": nranks, "straggler_rank": rank,
           "compute_factor": factor, "engine": engine,
           "t_healthy_us": round(h_plan.step_us, 2),
           "t_straggler_no_replan_us": round(t_noreplan, 2),
           "t_straggler_replanned_us": round(s_plan.step_us, 2),
           "recovered_margin": round(recovered, 4),
           "healthy_plan": repr(h_plan.chosen),
           "replanned": repr(s_plan.chosen),
           "plan_flipped": h_plan.chosen != s_plan.chosen,
           "machines": {"healthy": h_plan.machine,
                        "degraded_search_space": s_plan.evaluated},
           "monitor": {"deadline_factor": mon.factor,
                       "flagged_steps": mon.flagged,
                       "on_straggle_events": events}}
    assert s_plan.step_us <= t_noreplan * (1 + 1e-9), \
        "replanning must not lose to the stale plan"
    assert mon.flagged and events, \
        "the straggler steps must trip the monitor hook"
    print(f"straggl N={nranks:4d}  healthy {h_plan.step_us:.0f}us  "
          f"stale plan {t_noreplan:.0f}us  replanned "
          f"{s_plan.step_us:.0f}us  (recovered {100 * recovered:.1f}%, "
          f"{len(events)} on_straggle events)")
    return out


def planner_replan_block(machine, engine: str) -> dict:
    """Collective planning against a structurally degraded machine: the
    winner cache is keyed by the machine *name*, which carries the fault
    signature, so healthy winners never leak onto broken fabrics."""
    from repro.core.planner import CollectivePlanner
    spec = FaultSpec(dead_links=[("mezz", 0, 4)])
    nbytes, p = 262144, 64
    h = CollectivePlanner(machine, fidelity="sim",
                          engine=engine).plan("allreduce", nbytes, p)
    d = CollectivePlanner(machine.degraded(spec), fidelity="sim",
                          engine=engine).plan("allreduce", nbytes, p)
    out = {"op": "allreduce", "nbytes": nbytes, "nranks": p,
           "fault": spec.signature(),
           "healthy": {"schedule": h.schedule, "machine": h.machine,
                       "cost_us": round(h.cost_s * 1e6, 2)},
           "degraded": {"schedule": d.schedule, "machine": d.machine,
                        "cost_us": round(d.cost_s * 1e6, 2)},
           "plan_flipped": h.schedule != d.schedule,
           "degradation_cost": round(d.cost_s / h.cost_s, 4)}
    assert h.machine != d.machine, \
        "degraded machine must carry the fault signature in its name"
    print(f"replan  allreduce {nbytes}B@{p}: {h.schedule} "
          f"{out['healthy']['cost_us']}us -> {d.schedule} "
          f"{out['degraded']['cost_us']}us on {d.machine}")
    return out


def overlay_block(mc_specs: list[FaultSpec]) -> dict:
    """§5.3 ladder + the graceful-degradation floor: which sampled fault
    sets leave native transport still worth more than the IP overlay."""
    gap = overlay_vs_native_gap()
    paper = {"native_wire_gbps": 6.42, "overlay_gbps": 4.7,
             "baseline_gbps": 1.3}
    floors = []
    for s in mc_specs:
        worst = max([s.link_slow(*k) for k in s.degraded_link_keys()],
                    default=1.0)
        floors.append(gap["native_wire_gbps"] / worst)
    above = [f for f in floors if f > gap["overlay_gbps"]]
    out = {**gap, "paper": paper,
           "rel_err": {k: round(abs(gap[k] - v) / v, 4)
                       for k, v in paper.items()},
           "degraded_native_floor_gbps": {
               "min": round(min(floors), 3) if floors else None,
               "p50": round(float(np.median(floors)), 3)
               if floors else None},
           "native_beats_overlay_fraction":
               round(len(above) / len(floors), 4) if floors else None}
    assert gap["baseline_gbps"] < gap["overlay_gbps"] \
        < gap["native_wire_gbps"], "§5.3 ladder ordering"
    print(f"overlay native {gap['native_wire_gbps']:.2f}  overlay "
          f"{gap['overlay_gbps']:.2f}  baseline "
          f"{gap['baseline_gbps']:.2f} Gb/s; degraded native floor "
          f"p50 {out['degraded_native_floor_gbps']['p50']} Gb/s")
    return out


def main(out_path: str = "BENCH_faults.json", smoke: bool = False,
         min_runs: int = 3, engine: str = "numpy") -> None:
    machine = ExanetMachine()
    ranks = SMOKE_RANKS if smoke else RANKS
    min_wall = 0.05 if smoke else 0.2
    guards: list[float] = []
    rows = class_rows(machine, ranks, engine, guards)
    node = node_failure_block(machine, engine)
    n_mc = max(ranks)
    mc = mc_rows(machine, n_mc, 8 if smoke else 32, 2 if smoke else 3,
                 min_wall, 1 if smoke else min(min_runs, 2), engine,
                 guards)
    # tenants must span QFDBs (>=16 ranks each at 8 cores/tenant/board)
    # for their cross-board traffic to meet on the mezzanine links
    interf = interference_block(16 if smoke else 32,
                                16 if smoke else 32, engine, guards)
    strag = straggler_block(machine, 16 if smoke else 64, smoke, engine)
    replan = planner_replan_block(machine, engine)
    rng = np.random.default_rng(7)
    topo = machine._mpi_for(n_mc).topo
    overlay = overlay_block([sample_fault_spec(
        rng, topo, n_slow_links=2, n_lossy_links=1)
        for _ in range(4 if smoke else 32)])
    out: dict = {"ranks": list(ranks), "engine": engine,
                 "min_runs": min_runs,
                 "agreement_rtol": AGREEMENT_RTOL,
                 "classes": list(CLASSES),
                 "class_results": rows,
                 "node_failure": node,
                 "monte_carlo": mc,
                 "interference": interf,
                 "straggler_replanning": strag,
                 "planner_replanning": replan,
                 "ip_overlay_floor": overlay}
    if not smoke:
        # acceptance keys: full sweeps only (see module docstring)
        out["mc_batch_speedup_at_512"] = mc["batch_speedup"]
        out["degraded_agreement_max"] = max(guards)
        out["interference_min_efficiency"] = min(interf["efficiency"])
        out["straggler_recovered_margin"] = strag["recovered_margin"]
        assert mc["nranks"] == 512
        assert out["mc_batch_speedup_at_512"] >= 10.0, \
            "batched fault sweep must be >=10x the per-fault-set lane " \
            "at 512 ranks"
        assert out["degraded_agreement_max"] <= AGREEMENT_RTOL
        assert out["interference_min_efficiency"] < 0.9, \
            "the neighbour-load sweep must show real contention"
        assert out["straggler_recovered_margin"] >= 0.0
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"\nwrote {out_path}")
    if not smoke:
        print(f"batched fault sweep {mc['batch_speedup']:.1f}x @512; "
              f"worst degraded agreement {out['degraded_agreement_max']:.1e}; "
              f"interference floor "
              f"{out['interference_min_efficiency']:.3f}; straggler "
              f"margin {100 * strag['recovered_margin']:.1f}%")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--min-runs", type=int, default=3,
                    help="floor on timed runs per throughput row")
    ap.add_argument("--engine", default="numpy",
                    choices=("numpy", "jax"),
                    help="scan backend of the compiled/batched lanes")
    args = ap.parse_args()
    main(smoke=args.smoke, min_runs=args.min_runs, engine=args.engine)
