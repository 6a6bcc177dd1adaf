"""The expert-parallel decode cell (``replay-dsv3ep128-mc64``) on the CPU:
its names resolve, its configuration is the published architecture, the
program agrees with the plain reference, and the control and the planted
faults come out not correct.  Rehearsals run at a small size (16 ranks,
32 experts in 4 groups, top-4 within 2 groups, 4 tokens per rank, 1 dense
+ 2 MoE layers)."""

import time
import types

import numpy as np
import pytest

from benchlib import ep_ref, exanet_ref, harness, spec
from benchlib.kinds import ep_replay_sweep

CELL = "replay-dsv3ep128-mc64"


def _small(columns=3, check=3):
    dep = spec.find_cell(CELL).config["deployment"]
    return {"config": {"n_routed_experts": 32, "num_experts_per_tok": 4,
                       "n_group": 4, "topk_group": 2,
                       "num_hidden_layers": 3, "first_k_dense_replace": 1,
                       "deployment": {**dep, "ranks": 16,
                                      "tokens_per_rank": 4}},
            "traffic": {"columns": columns, "pool_blocks": 2,
                        "check_columns": check}}


def _run(*, variant=None, seed=3000000029):
    return harness.run_cell(CELL, seed, 0.5, False, t0=time.perf_counter(),
                            require_chip=False, overrides=_small(),
                            variant=variant, log=lambda _msg: None)


def test_the_cell_its_config_traffic_and_metric_resolve():
    cell = spec.find_cell(CELL)
    assert cell.chips == 1
    assert spec.driver(cell.traffic["kind"]) is ep_replay_sweep
    assert {m["name"] for m in cell.end_to_end} == {"replay_columns_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "scan_dispatches_per_replay", "device_idle_share.replay",
        "rdv_level_hbm_share"}
    assert callable(spec.reader("rdv_level_hbm_share"))
    assert cell.traffic["columns"] == 64 and cell.traffic["pool_blocks"] == 4
    entry = {c["name"]: c for c in spec.load_spec()["configs"]}[
        "dsv3-ep128-decode"]
    assert cell.config["source"] == entry["source"]
    # the published widths and routing are the repository's own V3 config
    from repro.configs import get
    arch, c = get(cell.config["arch"]), cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_rope_head_dim"],
            c["intermediate_size"], c["moe_intermediate_size"]) == (
        arch.d_model, arch.n_heads, arch.mla.q_lora_rank,
        arch.mla.kv_lora_rank, arch.mla.qk_rope_head_dim, arch.d_ff,
        arch.moe.d_expert)
    assert (c["n_routed_experts"], c["num_experts_per_tok"], c["n_group"],
            c["topk_group"], c["routed_scaling_factor"]) == (
        arch.moe.n_experts, arch.moe.top_k, arch.moe.n_group,
        arch.moe.topk_group, arch.moe.routed_scaling_factor)
    assert c["scoring_func"] == "sigmoid" and not arch.moe.router_softmax
    sim = ep_replay_sweep.emitter(c)
    dep = c["deployment"]
    assert (sim.dispatch_bytes, sim.combine_bytes) == (
        dep["dispatch_bytes_per_token"], dep["combine_bytes_per_token"])


def test_rdv_level_hbm_share_reads_bytes_over_device_time():
    read = spec.reader("rdv_level_hbm_share")
    trace = types.SimpleNamespace(programs={"jit_rdv_serial": [8, 0.5]})
    run = types.SimpleNamespace(trace=trace, peaks={"hbm_bytes_per_s": 8e11},
                                counters={"rdv_level_bytes": 4e9})
    assert read(run) == pytest.approx(1.0)
    run.trace = types.SimpleNamespace(programs={})
    assert read(run) is None


def test_the_reference_routes_as_the_program_does():
    c = {**spec.find_cell(CELL).config, **_small()["config"]}
    lg = ep_replay_sweep.logits(c)
    sim = ep_replay_sweep.emitter(c)
    ids = sim.route(lg)
    for layer, want in enumerate(ids):
        got = ep_ref.route(lg[layer], c)
        assert [set(r) for r in got.tolist()] == \
            [set(r) for r in want.tolist()]


def _faults_on_the_step_s_links(machine, c, n):
    """``n`` fault sets of the configuration's kind, each on links that
    the rehearsal's 16 ranks route over (the rack's other links carry
    nothing at this size)."""
    cores = [r * c["machine"]["cores_per_mpsoc"]
             for r in range(c["deployment"]["ranks"])]
    used = sorted({exanet_ref.link_key(*ln) for a in cores for b in cores
                   for ln in machine.route(a, b)[0]})
    f = c["faults"]
    rng = np.random.default_rng(7)
    out = []
    for _ in range(n):
        pick = rng.choice(len(used), f["n_slow_links"] + f["n_lossy_links"],
                          replace=False)
        hot = [used[i] for i in pick[:f["n_slow_links"]]]
        out.append({
            "slow": {k: float(rng.uniform(*f["slow_factor"])) for k in hot},
            "extra_us": {k: float(f["extra_latency_us"]) for k in hot},
            "lossy": {used[i]: float(rng.uniform(*f["loss_prob"]))
                      for i in pick[f["n_slow_links"]:]}})
    return out


def test_the_program_equals_the_reference_on_three_fault_sets():
    c = {**spec.find_cell(CELL).config, **_small()["config"]}
    lg = ep_replay_sweep.logits(c)
    prog = ep_replay_sweep.emitter(c).emit_step(lg)
    machine = exanet_ref.Machine(c["machine"])
    draws = _faults_on_the_step_s_links(machine, c, 3)
    from repro.core.exanet.faults import FaultSpec, batch_fault_axes
    from repro.core.exanet.scan_engine import JaxScanEngine
    from repro.core.machine import ExanetMachine
    specs = [FaultSpec(slow_links=d["slow"],
                       link_extra_latency_us=d["extra_us"],
                       lossy_links=d["lossy"]) for d in draws]
    res = ExanetMachine().cost_program_scenarios(
        prog, **batch_fault_axes(specs, prog), engine=JaxScanEngine())
    step = ep_ref.Step(c, lg)
    ops = step.ops()
    order = ep_ref.firing_order(machine, step, ops)
    assert len(order) == sum(2 * len(p) for p in step.pairs)
    healthy = ep_ref.step_latency(machine, step, ops, order, {})
    moved = 0
    for r, d in zip(res, draws):
        lat, clocks = ep_ref.step_latency(machine, step, ops, order, d)
        assert lat >= healthy[0]
        moved += clocks != healthy[1]
        gap = ep_replay_sweep.rel_gap(r, lat, clocks)
        assert gap <= c["limits"]["rel_gap_vs_reference"], gap
    assert moved == len(draws)      # every set reaches the step


def test_the_cell_rehearsal_is_correct_and_reports_its_metrics():
    out = _run()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"replay_columns_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0


def _drop_one_message(monkeypatch):
    from repro.serve.sim import EPDecodeSim
    inner = EPDecodeSim.layer_traffic

    def dropped(self, ids):
        tokens, load = inner(self, ids)
        s, d = np.argwhere(tokens)[0]
        tokens[s, d] = 0
        return tokens, load
    monkeypatch.setattr(EPDecodeSim, "layer_traffic", dropped)


def _combine_at_dispatch_size(monkeypatch):
    inner = ep_replay_sweep.emitter

    def emitter(cfg):
        sim = inner(cfg)
        sim.combine_bytes = sim.dispatch_bytes
        return sim
    monkeypatch.setattr(ep_replay_sweep, "emitter", emitter)


@pytest.mark.parametrize("fault", ["dropped_message",
                                   "combine_at_dispatch_size",
                                   "float32_control"])
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    variant = None
    if fault == "dropped_message":
        _drop_one_message(monkeypatch)
    elif fault == "combine_at_dispatch_size":
        _combine_at_dispatch_size(monkeypatch)
    else:
        variant = ep_replay_sweep.CONTROL
    out = _run(variant=variant)
    assert not out["correct"], out["checks"]


def test_emitting_without_the_emitter_fails_at_once(monkeypatch):
    """What a checkout without the expert-parallel emitter does: set-up
    raises before any replay."""
    import repro.serve.sim as sim_mod
    monkeypatch.delattr(sim_mod, "EPDecodeSim")
    t0 = time.perf_counter()
    with pytest.raises(ImportError):
        _run()
    assert time.perf_counter() - t0 < 30.0


def test_an_unchanged_column_reads_the_healthy_step():
    """A fault set that degrades nothing gives the reference's healthy
    step, and a slower link never makes the step faster."""
    c = {**spec.find_cell(CELL).config, **_small()["config"]}
    step = ep_ref.Step(c, ep_replay_sweep.logits(c))
    machine = exanet_ref.Machine(c["machine"])
    ops = step.ops()
    order = ep_ref.firing_order(machine, step, ops)
    lat0, _ = ep_ref.step_latency(machine, step, ops, order, {})
    key = machine.links()[0]
    lat1, _ = ep_ref.step_latency(machine, step, ops, order,
                                  {"slow": {key: 8.0}, "extra_us": {},
                                   "lossy": {}})
    assert lat1 >= lat0 > 0
