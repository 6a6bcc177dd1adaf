"""Expert-parallel decode of a node-limited MoE model (DeepSeek-V3): the
shared router, the emitted step's traffic and compute, and the step's
compiled replay against the interpreter, at a small size on the CPU
(16 ranks, 32 experts in 4 groups, top-4 within 2 groups, 4 tokens per
rank, 1 dense + 2 MoE layers)."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MoEConfig, reduced
from repro.configs import get
from repro.core.exanet import scan_engine as se
from repro.core.exanet.mpi import ExanetMPI
from repro.core.program import Compute, Isend, Wait
from repro.models import moe as moe_lib
from repro.serve.sim import EPDecodeSim, EPDecodeSpec

SMALL = MoEConfig(n_experts=32, top_k=4, d_expert=2048, n_shared_experts=1,
                  d_shared=2048, router_softmax=False, n_group=4,
                  topk_group=2, routed_scaling_factor=2.5)
SPEC = EPDecodeSpec(nranks=16, tokens_per_rank=4, n_dense_layers=1,
                    n_moe_layers=2)


def _sim():
    return EPDecodeSim(SPEC, dataclasses.replace(get("deepseek-v3-671b"),
                                                 moe=SMALL))


def _logits(seed=0):
    rng = np.random.default_rng(seed)
    n = SPEC.nranks * SPEC.tokens_per_rank
    return rng.normal(size=(SPEC.n_moe_layers, n, SMALL.n_experts)) + \
        rng.normal(0.0, 0.5, (SPEC.n_moe_layers, 1, SMALL.n_experts))


def _brute_force(logits, m):
    """Every ``topk_group``-subset of groups and every ``top_k``-subset of
    their experts tried: the groups with the largest summed group score,
    then the experts with the largest summed score inside them."""
    s = 1.0 / (1.0 + np.exp(-logits))
    size = len(s) // m.n_group
    gscore = [sum(sorted(s[g * size:(g + 1) * size])[-2:])
              for g in range(m.n_group)]
    groups = max(itertools.combinations(range(m.n_group), m.topk_group),
                 key=lambda gs: sum(gscore[g] for g in gs))
    eligible = [e for g in groups for e in range(g * size, (g + 1) * size)]
    return set(max(itertools.combinations(eligible, m.top_k),
                   key=lambda es: sum(s[e] for e in es)))


def test_route_equals_a_brute_force_selection_within_its_groups():
    logits = _logits(1)[0, :24]
    with jax.enable_x64(True):
        ids = np.asarray(moe_lib.route(jnp.asarray(logits), None, SMALL))
    size = SMALL.n_experts // SMALL.n_group
    for row, lg in zip(ids, logits):
        assert set(row.tolist()) == _brute_force(lg, SMALL)
        assert len(set((row // size).tolist())) <= SMALL.topk_group


def test_reduced_v3_lm_routes_within_its_groups():
    """The LM's MoE layer, through ``apply_moe``, equals a dense reference
    that weighs only each token's in-group experts (normalized sigmoid
    scores times the scaling factor) plus the shared expert."""
    m = dataclasses.replace(SMALL, n_experts=8, top_k=2, d_expert=16,
                            d_shared=16, n_group=4, topk_group=2,
                            capacity_factor=8.0)
    cfg = reduced(get("deepseek-v3-671b"), moe=m, dtype="float32")
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y = np.asarray(moe_lib.apply_moe(p, x, cfg))
        xt = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
        logits = xt @ np.asarray(p["router"], np.float64)
        s = 1.0 / (1.0 + np.exp(-logits))
        act = jax.nn.silu

        def ffn(w, v):
            return np.asarray((act(v @ w["w_gate"]) * (v @ w["w_up"]))
                              @ w["w_out"])
        ref = np.array(ffn(p["shared"], jnp.asarray(xt, jnp.float32)))
        size = m.n_experts // m.n_group
        for t in range(len(xt)):
            chosen = sorted(_brute_force(logits[t], m))
            assert len({e // size for e in chosen}) <= m.topk_group
            w = s[t, chosen] / s[t, chosen].sum() * m.routed_scaling_factor
            for e, we in zip(chosen, w):
                ref[t] += we * ffn({k: p[k][e] for k in
                                    ("w_gate", "w_up", "w_out")},
                                   jnp.asarray(xt[t:t + 1], jnp.float32))[0]
    np.testing.assert_allclose(y.reshape(ref.shape), ref, rtol=2e-4,
                               atol=2e-5)


def test_mla_serve_cost_reads_the_latent_cache():
    from repro.roofline.analysis import lm_serve_step_cost
    cfg = get("deepseek-v3-671b")
    c = lm_serve_step_cost(cfg, n_decode=1, decode_kv=0.0, dtype_bytes=1)
    assert c["kv_bytes_per_token"] == cfg.n_layers * (512 + 64)


def test_the_dispatch_carries_each_token_once_per_other_destination():
    sim = _sim()
    logits = _logits()
    prog = sim.emit_step(logits)
    ids = sim.route(logits)
    per = sim.experts_per_rank
    T = SPEC.tokens_per_rank
    db, cb = sim.dispatch_bytes, sim.combine_bytes
    assert (db, cb) == (7168 + 56 * 4, 2 * 7168)
    for layer, lid in enumerate(ids):
        pairs = {(t, int(e) // per) for t, row in enumerate(lid)
                 for e in row if int(e) // per != t // T}
        sent = {(r, op.dst): op.nbytes for r, ops in enumerate(prog.rank_ops)
                for op in ops if isinstance(op, Isend) and op.tag == 2 * layer}
        assert sum(sent.values()) == len(pairs) * db
        for (s, d), nb in sent.items():
            assert nb == db * sum(1 for t, dd in pairs
                                  if t // T == s and dd == d)
        back = {(op.dst, r): op.nbytes for r, ops in enumerate(prog.rank_ops)
                for op in ops
                if isinstance(op, Isend) and op.tag == 2 * layer + 1}
        assert back == {k: v // db * cb for k, v in sent.items()}


def test_each_rank_s_expert_compute_follows_the_tokens_it_received():
    sim = _sim()
    logits = _logits()
    prog = sim.emit_step(logits)
    ids = sim.route(logits)
    per = sim.experts_per_rank
    for r, ops in enumerate(prog.rank_ops):
        # per MoE layer: ... dispatch Wait, experts, ... combine Wait
        waits = [i for i, op in enumerate(ops) if isinstance(op, Wait)]
        experts = [ops[i + 1] for i in waits[0::2]]
        assert len(waits) == 2 * SPEC.n_moe_layers
        assert all(isinstance(op, Compute) for op in experts)
        for layer, us in enumerate(op.us for op in experts):
            load = [int((ids[layer] == e).sum())
                    for e in range(r * per, (r + 1) * per)]
            assert us == pytest.approx(sum(sim.expert_us(n) for n in load),
                                       rel=1e-15)
            assert (us > 0) == (sum(load) > 0)
    # a weight sweep for the first tokens, then two operations per weight
    # and token
    assert sim.expert_us(16) > sim.expert_us(1) > sim.expert_us(0) == 0.0


@pytest.mark.parametrize("ranks_per_mpsoc", [1, None])
@pytest.mark.parametrize("lane", ["numpy", "jax_serial"])
def test_compiled_step_equals_the_interpreter(ranks_per_mpsoc, lane):
    """Both placements (one rank per MPSoC, one per core), on the stage-
    major levels (numpy) and on the serial levels (jax)."""
    prog = _sim().emit_step(_logits())
    mpi = ExanetMPI(ranks_per_mpsoc=ranks_per_mpsoc)
    ref = mpi.run_program(prog, backend="interp")
    engine = None
    if lane == "jax_serial":
        engine = se.JaxScanEngine()
    res = mpi.run_program_scenarios(prog, compute_scale=np.ones(2),
                                    engine=engine)
    if engine is not None:
        assert engine.levels_fused == 2 * SPEC.n_moe_layers
        assert engine.levels_staged == 0
        assert {kernel for (kernel, _, _) in engine.dispatches} == {
            "rdv_serial"}
    for r in res:
        got = np.array([r.latency_us, *r.clocks])
        want = np.array([ref.latency_us, *ref.clocks])
        np.testing.assert_allclose(got, want, rtol=1e-9)


def test_serial_levels_count_what_they_read_and_write():
    """``rdv_level_bytes``: per call the stacked operand, the level's
    resident constants and the result."""
    prog = _sim().emit_step(_logits())
    eng = se.JaxScanEngine()
    ExanetMPI().run_program_scenarios(prog, compute_scale=np.ones(3),
                                      engine=eng)
    want = 0
    for lv, (u_rows, dev, _) in eng._level_cache.items():
        k, u = len(lv.sel), len(u_rows)
        consts = sum(a.nbytes for a in jax.tree_util.tree_leaves(dev))
        want += (2 * k + u) * 3 * 8 + consts + (k + u) * 3 * 8
    assert eng.rdv_level_bytes == want > 0


def test_a_replaced_stage_kernel_keeps_the_stage_major_levels():
    """An engine whose max-plus kernel is replaced (a planted fault, a
    precision control) runs the stage-major chain, so the replacement
    runs; the serial levels stand in only for the engine's own
    kernels."""
    calls = []

    class Replaced(se.JaxScanEngine):
        def maxplus_scan(self, D, T, takes):
            calls.append(len(T))
            return super().maxplus_scan(D, T, takes)

    prog = _sim().emit_step(_logits())
    eng = Replaced()
    mpi = ExanetMPI()
    res = mpi.run_program_scenarios(prog, compute_scale=np.ones(2),
                                    engine=eng)
    assert calls and eng.levels_fused == 0 and eng.levels_staged > 0
    assert "rdv_serial" not in {kernel for (kernel, _, _) in eng.dispatches}
    ref = mpi.run_program(prog, backend="interp")
    np.testing.assert_allclose(res[0].clocks, ref.clocks, rtol=1e-9)


def test_the_serial_kernel_carries_its_own_program_name():
    """A trace names the serial level's program ``jit_rdv_serial``."""
    consts = {"rows": np.zeros((3, 4), np.int32),
              "valid": np.ones((3, 4), bool)}
    with jax.enable_x64(True):
        text = se._rdv_serial_kernel(1.4, 2.4).lower(
            np.zeros((8, 2)), consts).as_text()
    assert "@jit_rdv_serial" in text

