"""Fault & congestion scenario engine (DESIGN.md §2.10): FaultSpec
canonicalization, deterministic fault-aware rerouting (no dead element on
any returned path, dimension order preserved, diagnosable cuts), route
cache epochs, degraded compiled==interp agreement, batched degradation
axes vs statically degraded twins, degraded machine variants, tenant
interference, and the straggler-aware train co-sim."""

import numpy as np
import pytest

from repro.core.exanet.faults import (HEALTHY, FaultSpec, UnroutableError,
                                      all_link_keys, batch_fault_axes,
                                      link_key, sample_fault_spec)
from repro.core.exanet.mpi import ExanetMPI
from repro.core.exanet.params import DEFAULT
from repro.core.exanet.topology import Topology
from repro.core.program import Program, cg_iteration, halo3d

RTOL = 1e-9


def _rel(a, b) -> float:
    rel = abs(b.latency_us - a.latency_us) / max(abs(a.latency_us), 1e-12)
    for x, y in zip(a.clocks, b.clocks):
        rel = max(rel, abs(y - x) / max(abs(x), 1e-12))
    return rel


# ---------------------------------------------------------------- FaultSpec
def test_fault_spec_canonicalization():
    a = FaultSpec(dead_links=[("mezz", 4, 0)],
                  slow_links={("intra_qfdb", 2, 1): 3.0})
    b = FaultSpec(dead_links=[("mezz", 0, 4)],
                  slow_links={("intra_qfdb", 1, 2): 3.0})
    assert a == b and hash(a) == hash(b)
    assert a.signature() == b.signature()
    assert a.is_dead_link("mezz", 4, 0) and a.is_dead_link("mezz", 0, 4)
    assert a.link_slow("intra_qfdb", 2, 1) == 3.0
    assert HEALTHY.is_empty and HEALTHY.signature() == "healthy"
    assert a.degrades_structure and not a.is_empty


def test_fault_spec_lossy_replay_cost():
    """§4.5.3: loss probability p costs 1/(1-p) expected transmissions,
    compounding with a hot-link factor on the same link."""
    s = FaultSpec(slow_links={("mezz", 0, 4): 2.0},
                  lossy_links={("mezz", 0, 4): 0.5})
    assert s.link_slow("mezz", 0, 4) == pytest.approx(4.0)
    assert not s.degrades_structure


def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec(slow_links={("mezz", 0, 4): 0.5})
    with pytest.raises(ValueError):
        FaultSpec(lossy_links={("mezz", 0, 4): 1.0})


# ----------------------------------------------------------------- reroute
def test_reroute_avoids_dead_mezz_link_deterministically():
    spec = FaultSpec(dead_links=[("mezz", 0, 4)])
    t1 = Topology(DEFAULT, faults=spec)
    t2 = Topology(DEFAULT, faults=spec)
    # QFDB0 -> QFDB1 normally crosses mezz(0,4)
    p1, p2 = t1.route(0, 16), t2.route(0, 16)
    assert p1.links == p2.links, "reroute must be deterministic"
    for l in p1.links:
        assert not spec.is_dead_link(l.kind, l.src_mpsoc, l.dst_mpsoc)
    healthy = Topology(DEFAULT).route(0, 16)
    assert p1.links != healthy.links


def test_intra_qfdb_relay_ladder():
    """Dead direct link -> lowest-id alive relay; dead relay -> next."""
    dead = FaultSpec(dead_links=[("intra_qfdb", 0, 1)])
    path = Topology(DEFAULT, faults=dead).route(0, 4)
    hops = [(l.src_mpsoc, l.dst_mpsoc) for l in path.links]
    assert hops == [(0, 2), (2, 1)]
    relay_down = FaultSpec(dead_links=[("intra_qfdb", 0, 1)],
                           dead_mpsocs=[2])
    path = Topology(DEFAULT, faults=relay_down).route(0, 4)
    hops = [(l.src_mpsoc, l.dst_mpsoc) for l in path.links]
    assert hops == [(0, 3), (3, 1)]


def test_unroutable_cut_is_diagnosed():
    cut = FaultSpec(dead_links=[("intra_qfdb", 0, 1)], dead_mpsocs=[2, 3])
    topo = Topology(DEFAULT, faults=cut)
    with pytest.raises(UnroutableError):
        topo.route(0, 4)
    # cutting both ring directions out of a torus node partitions it
    ring_cut = FaultSpec(dead_links=[("mezz", 0, 4), ("mezz", 0, 12)])
    topo = Topology(DEFAULT, faults=ring_cut)
    with pytest.raises(UnroutableError) as e:
        topo.route(0, 32)   # QFDB0 -> QFDB2 needs the x ring
    assert str(e.value)


def test_dead_endpoint_is_unroutable():
    spec = FaultSpec(dead_mpsocs=[1])
    topo = Topology(DEFAULT, faults=spec)
    with pytest.raises(UnroutableError, match="dead"):
        topo.route(0, 4)


def _mezz_dim_sequence(topo, path):
    dims = []
    for l in path.links:
        if l.kind != "mezz":
            continue
        a = topo.qfdb_coords(l.src_mpsoc // topo.fpgas_per_qfdb)
        b = topo.qfdb_coords(l.dst_mpsoc // topo.fpgas_per_qfdb)
        dims.append(next(i for i in range(3) if a[i] != b[i]))
    return dims


def test_fuzz_random_fault_sets_at_512_ranks():
    """Random fault sets on the full 512-core prototype: every returned
    route is fault-free, deterministic, and dimension-ordered (X->Y->Z
    never interleaves — the deadlock-freedom invariant of DOR)."""
    for seed in range(6):
        rng = np.random.default_rng(seed)
        spec = sample_fault_spec(rng, Topology(DEFAULT),
                                 n_dead_links=3, n_dead_mpsocs=2,
                                 n_slow_links=2)
        topo = Topology(DEFAULT, faults=spec)
        alive = [c for c in range(256, 512)
                 if not spec.is_dead_mpsoc(c // DEFAULT.cores_per_mpsoc)]
        pairs = rng.choice(len(alive), size=(60, 2))
        cuts = 0
        for i, j in pairs:
            src, dst = alive[i], alive[j]
            if src == dst:
                continue
            try:
                path = topo.route(src, dst)
            except UnroutableError as e:
                assert str(e)
                cuts += 1
                continue
            assert path.links == topo.route(src, dst).links
            for l in path.links:
                assert not spec.is_dead_link(l.kind, l.src_mpsoc,
                                             l.dst_mpsoc), (seed, l)
                assert not spec.is_dead_mpsoc(l.src_mpsoc)
                assert not spec.is_dead_mpsoc(l.dst_mpsoc)
            dims = _mezz_dim_sequence(topo, path)
            assert dims == sorted(dims), \
                f"dimension order violated: {dims} (seed {seed})"
        assert cuts < len(pairs), "every pair cut: degenerate sample"


def test_route_cache_epoch_and_clear():
    topo = Topology(DEFAULT)
    topo.route(0, 16)
    info = topo.route_cache_info()
    assert info["size"] >= 1 and info["fault_epoch"] == 0
    topo.set_faults(FaultSpec(dead_links=[("mezz", 0, 4)]))
    info = topo.route_cache_info()
    assert info["size"] == 0 and info["fault_epoch"] == 1
    path = topo.route(0, 16)
    for l in path.links:
        assert (l.kind, *sorted((l.src_mpsoc, l.dst_mpsoc))) != \
            ("mezz", 0, 4)
    topo.route_cache_clear()
    assert topo.route_cache_info()["size"] == 0


# ------------------------------------------------- executors under faults
def test_degraded_compiled_matches_interp():
    """Static degradation (structural + hot + lossy + latency) must keep
    the two executors within 1e-9 — same PathMetrics, same answers."""
    spec = FaultSpec(dead_links=[("intra_qfdb", 0, 1)],
                     slow_links={("mezz", 0, 4): 3.0},
                     lossy_links={("mezz", 4, 8): 0.2},
                     link_extra_latency_us={("intra_qfdb", 8, 9): 10.0})
    mpi = ExanetMPI(faults=spec)
    prog = cg_iteration(64, 32768, 120.0, coll_algo="recursive_doubling")
    a = mpi.run_program(prog, backend="interp")
    b = mpi.run_program(prog, backend="compiled")
    assert _rel(a, b) <= RTOL
    healthy = ExanetMPI().run_program(prog, backend="compiled")
    assert b.latency_us > healthy.latency_us


def test_batched_link_axes_match_static_twins():
    """N non-structural fault sets as batch columns == N statically
    degraded machines, column by column (and the built-in interpreter
    check lane)."""
    base = ExanetMPI()
    rng = np.random.default_rng(3)
    specs = [sample_fault_spec(rng, base.topo, n_slow_links=2,
                               n_lossy_links=1, extra_latency_us=4.0)
             for _ in range(4)]
    prog = halo3d(32, 65536, compute_us=40.0)
    axes = batch_fault_axes(specs, prog)
    got = base.run_program_scenarios(prog, **axes, check=4, rtol=RTOL)
    for j, s in enumerate(specs):
        twin = ExanetMPI(faults=s, cache=False)
        ref = twin.run_program(prog, backend="compiled")
        assert _rel(ref, got[j]) <= RTOL, (j, s.signature())


def test_batched_link_axes_jax_engine_agrees():
    base = ExanetMPI()
    rng = np.random.default_rng(5)
    specs = [sample_fault_spec(rng, base.topo, n_slow_links=2)
             for _ in range(3)]
    prog = halo3d(16, 32768, compute_us=25.0)
    axes = batch_fault_axes(specs, prog)
    a = base.run_program_scenarios(prog, **axes, engine="numpy")
    b = base.run_program_scenarios(prog, **axes, engine="jax")
    for x, y in zip(a, b):
        assert _rel(x, y) <= RTOL


def _dense_link_consts(deg, lv) -> dict:
    """Every level constant recomputed for every (send, column), the
    formulas written out once more: what the degraded-pair update of
    ``LinkDegrade`` has to reproduce."""
    mask = lv.link_ids >= 0
    idx = np.where(mask, lv.link_ids, 0)
    s = deg.slow[idx]
    exsum = np.where(mask[..., None], deg.extra[idx], 0.0).sum(axis=1)
    rate = np.where(mask[..., None], lv.link_rate[..., None], np.inf)
    has = (lv.n_links > 0)[:, None]
    wire = np.where(mask[..., None], lv.link_wire[..., None] / s, np.inf)
    t_block = deg._block_bits / (wire.min(axis=1) * 1000.0) + deg._gap_us
    bw = deg._block_bits / t_block / 1000.0
    return {"e_const": lv.e_const + exsum,
            "eager_pb": np.where(has, (8.0 / ((rate / s) * 1000.0)).sum(
                axis=1), lv.eager_pb),
            "handshake": lv.handshake + 2.0 * exsum,
            "stream_pb": np.where(has, 8.0 / (bw * 1000.0), lv.stream_pb),
            "hop": lv.hop + exsum}


def test_link_constants_recompute_only_the_degraded_pairs(monkeypatch):
    """The per-column link constants equal the formulas over every (send,
    column) pair, bit for bit, while only the pairs a degraded link
    touches are recomputed."""
    from repro.core.exanet import exec_compiled as ec
    seen = []
    derive = ec.LinkDegrade._derive

    def spy(self, lv, sel, s, ex):
        seen.append((self, lv, s.shape[0]))
        return derive(self, lv, sel, s, ex)

    monkeypatch.setattr(ec.LinkDegrade, "_derive", spy)
    base = ExanetMPI()
    rng = np.random.default_rng(11)
    specs = [sample_fault_spec(rng, base.topo, n_slow_links=2,
                               n_lossy_links=1, extra_latency_us=4.0)
             for _ in range(6)] + [HEALTHY]
    prog = halo3d(64, 65536, compute_us=40.0)
    base.run_program_scenarios(prog, **batch_fault_axes(specs, prog))
    levels = {id(lv): (deg, lv) for deg, lv, _ in seen}
    assert levels
    for deg, lv in levels.values():
        got, want = deg.consts(lv), _dense_link_consts(deg, lv)
        for name in want:
            np.testing.assert_array_equal(got[name], want[name], name)
        # the healthy column keeps the level's own constants
        np.testing.assert_array_equal(got["handshake"][:, -1:],
                                      lv.handshake)
    k, n = next(iter(levels.values()))[1].link_ids.shape[0], len(specs)
    redone = [m for deg, lv, m in seen if m != lv.link_ids.shape[0]]
    assert redone and max(redone) < k * n


def test_a_link_only_sweep_lays_its_payload_out_once():
    """Entry clocks and link faults leave the payload the same in every
    column: it binds once and every column reads it, with the results of
    a sweep that binds the same payload column by column."""
    base = ExanetMPI()
    rng = np.random.default_rng(13)
    specs = [sample_fault_spec(rng, base.topo, n_slow_links=2)
             for _ in range(3)]
    prog = halo3d(16, 32768, compute_us=25.0)
    axes = batch_fault_axes(specs, prog)
    axes.pop("compute_scale", None)
    once = base.run_program_scenarios(prog, **axes)
    by_column = base.run_program_scenarios(prog, **axes,
                                           compute_scale=np.ones(3))
    assert once == by_column
    bound = base.program_artifact(prog).bind_arrays(prog, ncols=3)
    assert bound.B == 3 and bound.post_off.shape[1] == 3
    assert bound.post_off.strides[1] == 0 and bound.seg_total.strides[1] == 0


def test_batch_fault_axes_validation():
    with pytest.raises(ValueError, match="structural"):
        batch_fault_axes([FaultSpec(dead_links=[("mezz", 0, 4)])])
    slow = FaultSpec(slow_ranks={1: 2.0})
    with pytest.raises(ValueError, match="slow_ranks"):
        batch_fault_axes([slow])
    prog = Program((
        tuple([__import__("repro.core.program",
                          fromlist=["Compute"]).Compute(us=1.0)] * 3),
        (),
    ))
    axes = batch_fault_axes([slow, HEALTHY], prog)
    assert axes["compute_scale"].shape == (3, 2)
    assert np.all(axes["compute_scale"][:, 1] == 1.0)


def test_slow_rank_axis_slows_only_that_rank():
    mpi = ExanetMPI()
    prog = halo3d(16, 16384, compute_us=200.0)
    spec = FaultSpec(slow_ranks={3: 4.0})
    axes = batch_fault_axes([HEALTHY, spec], prog)
    res = mpi.run_program_scenarios(prog, **axes, check=2, rtol=RTOL)
    assert res[1].latency_us > res[0].latency_us
    assert res[1].clocks[3] > res[0].clocks[3] * 2.0


# --------------------------------------------------------------- machine
def test_machine_degraded_variants():
    from repro.core.machine import ExanetMachine
    m = ExanetMachine()
    assert m.degraded(HEALTHY) is m and m.degraded(None) is m
    spec = FaultSpec(dead_links=[("mezz", 0, 4)])
    d = m.degraded(spec)
    assert d is m.degraded(spec), "degraded variants are cached"
    assert spec.signature() in d.name and d.name != m.name
    assert d.placement == m.placement
    assert d.mpi.faults == spec
    # scaled tiers inherit the fault spec
    assert d._mpi_for(1024).faults == spec


def test_network_static_degradation_slows_path():
    slow = FaultSpec(slow_links={("intra_qfdb", 0, 1): 3.0},
                     link_extra_latency_us={("intra_qfdb", 0, 1): 5.0})
    h = ExanetMPI()
    d = ExanetMPI(faults=slow, cache=False)
    path_h = h.topo.route(0, 4)
    path_d = d.topo.route(0, 4)
    assert [l.kind for l in path_h.links] == \
        [l.kind for l in path_d.links], "non-structural: same route"
    assert d.net.rdv_latency(65536, path_d) > \
        h.net.rdv_latency(65536, path_h) + 10.0


# ---------------------------------------------------------- interference
def test_interference_is_emergent_and_monotone():
    from repro.core.exanet.interference import (
        background_stream, interleave_qfdb, merge_tenants,
        neighbor_load_byte_scale)
    app = halo3d(16, 65536, compute_us=50.0)
    bg = background_stream(16, iters=8, nbytes=131072)
    a_ranks, b_ranks = interleave_qfdb(16, 16)
    mix = merge_tenants(app, bg, a_ranks, b_ranks)
    assert set(a_ranks).isdisjoint(b_ranks)
    n_posts = sum(1 for ops in mix.program.rank_ops for op in ops
                  if type(op).__name__ in ("Isend", "Irecv"))
    assert mix.bg_post_mask.shape == (n_posts,)
    loads = (0.0, 1.0, 4.0)
    bs = neighbor_load_byte_scale(mix, loads)
    res = ExanetMPI().run_program_scenarios(mix.program, byte_scale=bs,
                                            check=2, rtol=RTOL)
    app_us = [mix.app_latency_us(r) for r in res]
    assert app_us[0] < app_us[1] < app_us[2], \
        f"neighbour load must slow the app: {app_us}"


def test_merge_tenants_rejects_collectives_and_overlap():
    from repro.core.exanet.interference import merge_tenants
    from repro.core.program import ProgramError
    coll = cg_iteration(4, 1024, 1.0)
    p2p = halo3d(4, 1024)
    with pytest.raises(ProgramError, match="Collective"):
        merge_tenants(coll, p2p)
    with pytest.raises(ValueError, match="overlap"):
        merge_tenants(p2p, p2p, app_ranks=(0, 1, 2, 3),
                      bg_ranks=(3, 4, 5, 6))


# ------------------------------------------------------- train co-sim
def test_trainsim_rank_compute_scale():
    from repro.train.cosim import TrainSim, TrainStepSpec
    spec = TrainStepSpec(nranks=4)
    with pytest.raises(ValueError, match="rank_compute_scale"):
        TrainSim(spec, rank_compute_scale=np.ones(3))
    healthy = TrainSim(spec)
    rcs = np.ones(4)
    rcs[2] = 3.0
    slow = TrainSim(spec, rank_compute_scale=rcs)
    cand = healthy.analytic_candidate()
    t_h = float(healthy.cost_candidates([cand])[0])
    t_s = float(slow.cost_candidates([cand])[0])
    assert t_s > t_h * 1.5, (t_h, t_s)
    # all-ones scale is a no-op (stays on the fast path)
    assert TrainSim(spec, rank_compute_scale=np.ones(4)) \
        .rank_compute_scale is None


def test_on_straggle_callback():
    from repro.runtime.fault import StragglerMonitor
    events = []
    mon = StragglerMonitor(deadline_factor=2.0,
                           on_straggle=lambda s, dt, dl:
                           events.append((s, dt, dl)))
    for i in range(10):
        mon.observe(i, 1.0)
    assert not events
    assert mon.observe(10, 5.0)
    (step, dt, deadline), = events
    assert step == 10 and dt == 5.0 and deadline == pytest.approx(2.0)


# ------------------------------------------------------------ ip overlay
def test_overlay_vs_native_gap():
    import math
    from repro.core.exanet import ip_overlay
    assert ip_overlay.math is math  # module-scope import (no local shadow)
    gap = ip_overlay.overlay_vs_native_gap()
    assert gap["baseline_gbps"] < gap["overlay_gbps"] \
        < gap["native_wire_gbps"]
    assert gap["native_wire_gbps"] == pytest.approx(6.42, rel=0.05)
    assert gap["overlay_gbps"] == pytest.approx(4.7, rel=0.1)
    assert gap["baseline_gbps"] == pytest.approx(1.3, rel=0.1)
