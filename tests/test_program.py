"""Program IR execution semantics (DESIGN.md §2.6).

Covers the tentpole contract: nonblocking point-to-point with tag
matching, compute/communication overlap up to the critical path, deadlock
detection, agreement with the closed-form model for isolated transfers,
one-pass collective planning, machine-level program costing, and the
apps-on-programs regression for custom HwParams.
"""

import dataclasses

import pytest

from repro.core.exanet.mpi import ExanetMPI
from repro.core.exanet.params import DEFAULT
from repro.core.machine import ExanetMachine, TpuMachine
from repro.core.planner import CollectivePlanner
from repro.core.program import (Collective, Compute, Irecv, Isend, Program,
                                ProgramDeadlockError, ProgramError, Wait,
                                analytic_program_us, balanced_grid3,
                                bsp_step, cg_iteration, halo3d)


@pytest.fixture(scope="module")
def mpi():
    return ExanetMPI()


@pytest.fixture(scope="module")
def mpi1():  # one rank per MPSoC: pairs cross a real link
    return ExanetMPI(ranks_per_mpsoc=1)


# ----------------------------------------------------------------- builders
def test_halo3d_structure_and_counts(mpi):
    prog = halo3d(8, 1024, 10.0)          # 2x2x2 grid: all 6 faces real
    assert prog.nranks == 8
    c = prog.counts()
    assert c["isend"] == c["irecv"] == 8 * 6
    res = mpi.run_program(prog)
    assert res.n_sends == 8 * 6           # every face matched exactly once
    assert res.latency_us > 10.0
    assert res.compute_us == (10.0,) * 8


def test_balanced_grid3():
    assert sorted(balanced_grid3(8)) == [2, 2, 2]
    assert sorted(balanced_grid3(512)) == [8, 8, 8]
    px, py, pz = balanced_grid3(2)
    assert px * py * pz == 2


def test_two_rank_periodic_grid_needs_tags(mpi):
    # 1x1x2 grid: both z faces go to the same neighbour; only the tag
    # distinguishes them — program must still match cleanly
    prog = halo3d(2, 4096, 0.0)
    res = mpi.run_program(prog)
    assert res.n_sends == 4               # 2 faces x 2 ranks


# ------------------------------------------------------------------ overlap
def test_compute_hides_communication_up_to_critical_path(mpi):
    face, comp = 8192, 400.0
    t_comm = mpi.run_program(halo3d(2, face, 0.0)).latency_us
    t_overlap = mpi.run_program(halo3d(2, face, comp,
                                       overlap=True)).latency_us
    t_serial = mpi.run_program(halo3d(2, face, comp)).latency_us
    # serial = comm then compute; overlapped compute swallows the comm
    assert t_serial == pytest.approx(t_comm + comp, rel=1e-9)
    assert t_overlap < t_serial - 0.9 * min(comp, t_comm)
    assert t_overlap >= comp              # critical path floor


def test_overlap_floor_is_communication_when_compute_small(mpi):
    face = 8192
    t_comm = mpi.run_program(halo3d(2, face, 0.0)).latency_us
    t_overlap = mpi.run_program(halo3d(2, face, 1.0,
                                       overlap=True)).latency_us
    assert t_overlap == pytest.approx(t_comm, rel=0.05)


# ------------------------------------------------------------- tag matching
def test_tags_match_out_of_order_posts(mpi):
    # rank 0 sends tag 0 (100 B) then tag 1 (5000 B); rank 1 posts the
    # receives in *reverse* tag order.  Only tag-based matching pairs the
    # sizes correctly (FIFO-by-arrival would raise a size mismatch).
    prog = Program((
        (Isend(1, 100, tag=0), Isend(1, 5000, tag=1), Wait()),
        (Irecv(0, 5000, tag=1), Irecv(0, 100, tag=0), Wait()),
    ))
    res = mpi.run_program(prog)
    assert res.n_sends == 2


def test_size_mismatch_on_matched_channel_raises(mpi):
    prog = Program((
        (Isend(1, 100, tag=0), Wait()),
        (Irecv(0, 200, tag=0), Wait()),
    ))
    with pytest.raises(ProgramError, match="size mismatch"):
        mpi.run_program(prog)


def test_named_handles_selective_wait(mpi):
    prog = Program((
        (Isend(1, 64, tag=0, handle="a"), Isend(1, 64, tag=1, handle="b"),
         Wait(("a",)), Compute(5.0), Wait(("b",))),
        (Irecv(0, 64, tag=0), Irecv(0, 64, tag=1), Wait()),
    ))
    res = mpi.run_program(prog)
    assert res.n_sends == 2


# ------------------------------------------------------ deadlock detection
def test_deadlock_on_mismatched_tags(mpi):
    prog = Program((
        (Irecv(1, 100, tag=7), Wait()),
        (Isend(0, 100, tag=8), Wait()),
    ))
    with pytest.raises(ProgramDeadlockError, match="unmatched"):
        mpi.run_program(prog)


def test_deadlock_on_missing_collective_participant(mpi):
    prog = Program((
        (Collective("allreduce", 64, "recursive_doubling"),),
        (Compute(1.0),),
    ))
    with pytest.raises(ProgramDeadlockError, match="collective barrier"):
        mpi.run_program(prog)


def test_collective_signature_mismatch_raises(mpi):
    # ranks must reach *matching* collectives in the same order; merging
    # a barrier with an allreduce would silently cost the wrong thing
    prog = Program((
        (Collective("allreduce", 1024, "recursive_doubling"),),
        (Collective("barrier", 0, "dissemination"),),
    ))
    with pytest.raises(ProgramError, match="collective mismatch"):
        mpi.run_program(prog)


def test_unmatched_request_at_exit_raises(mpi):
    prog = Program((
        (Isend(1, 8, tag=0),),   # eager, never received, never waited
        (Compute(1.0),),
    ))
    with pytest.raises(ProgramError, match="unmatched"):
        mpi.run_program(prog)


def test_validate_rejects_out_of_range_peer():
    with pytest.raises(ProgramError, match="outside"):
        Program(((Isend(3, 8),),)).validate()


# ------------------------------------------- closed-form / interp agreement
def test_single_isolated_transfer_matches_closed_form(mpi1):
    """One rendez-vous Isend/Irecv pair with no contention must reproduce
    the closed-form one-way latency (osu_one_way) the retired apps model
    was built from."""
    size = 8000
    prog = Program((
        (Isend(1, size, tag=0), Wait()),
        (Irecv(0, size, tag=0), Wait()),
    ))
    res = mpi1.run_program(prog)
    expected = mpi1.osu_one_way(size, 0, 1)
    assert res.latency_us == pytest.approx(expected, rel=0.02)


def test_single_eager_transfer_matches_closed_form(mpi1):
    size = 16   # <= 32 B: eager transport
    prog = Program((
        (Isend(1, size, tag=0), Wait()),
        (Irecv(0, size, tag=0), Wait()),
    ))
    res = mpi1.run_program(prog)
    expected = mpi1.osu_one_way(size, 0, 1)
    assert res.latency_us == pytest.approx(expected, rel=0.05)


def test_sim_matches_analytic_walker_without_contention(mpi1):
    """The event engine and the alpha-beta walker agree on a one-direction
    transfer chain when there is nothing to contend on."""
    size = 65536
    prog = Program((
        (Compute(10.0), Isend(1, size, tag=0), Wait()),
        (Irecv(0, size, tag=0), Wait(), Compute(10.0)),
    ))
    sim = mpi1.run_program(prog).latency_us
    m = mpi1.net.path_metrics(0, mpi1.rank_core(1))
    alpha = m.handshake_ow_us + DEFAULT.rdma_startup_us + m.hop_latency_us
    bw = m.rdma_bw_gbps * 1000.0 / 8.0   # bytes/us
    ana = analytic_program_us(prog, alpha_us=alpha, bw_bytes_per_us=bw,
                              coll_cost_us=lambda *a: 0.0).latency_us
    assert sim == pytest.approx(ana, rel=0.03)


def test_embedded_collective_matches_standalone(mpi):
    """A program that is just one collective costs the standalone
    run_schedule latency (same engine, zero-occupancy entry)."""
    prog = bsp_step(8, 0.0, "allreduce", 4096,
                    coll_algo="recursive_doubling")
    res = mpi.run_program(prog)
    direct = mpi.allreduce(4096, 8, "recursive_doubling")
    assert res.latency_us == pytest.approx(direct, rel=1e-12)


def test_run_schedule_t0_is_time_shift_invariant(mpi):
    from repro.core.exanet.schedules import RecursiveDoublingAllreduce
    base = mpi.run_schedule(RecursiveDoublingAllreduce(), 1024, 8,
                            backend="interp")
    shifted = mpi.run_schedule(RecursiveDoublingAllreduce(), 1024, 8,
                               backend="interp", t0=[100.0] * 8)
    assert shifted.latency_us == pytest.approx(base.latency_us + 100.0,
                                               rel=1e-12)
    # a skewed fresh start is exact on the compiled backend too; only
    # reset=False (nonzero live occupancy) stays interpreter-only
    compiled = mpi.run_schedule(RecursiveDoublingAllreduce(), 1024, 8,
                                backend="compiled", t0=[100.0] * 8)
    assert compiled.latency_us == pytest.approx(shifted.latency_us,
                                                rel=1e-9)
    with pytest.raises(ValueError, match="compiled"):
        mpi.run_schedule(RecursiveDoublingAllreduce(), 1024, 8,
                         backend="compiled", t0=[0.0] * 8, reset=False)


# ------------------------------------------------- congestion is emergent
def test_concurrent_halo_flows_contend(mpi1):
    """8 ranks exchanging simultaneously must be slower than the isolated
    closed-form sum of one rank's faces — this gap is what the retired
    alpha had to fake."""
    face = 32768
    prog = halo3d(8, face, 0.0)
    sim = mpi1.run_program(prog).latency_us
    isolated = 3 * mpi1.osu_one_way(face, 0, 1)   # 3 face-pairs, overlap
    assert sim > 1.5 * isolated


# -------------------------------------------------------- planner / machine
def test_plan_program_plans_every_auto_site_in_one_pass():
    planner = CollectivePlanner(ExanetMachine(), fidelity="analytic")
    prog = Program(tuple(
        (Collective("allreduce", 256), Compute(1.0),
         Collective("allreduce", 1 << 20),
         Collective("allreduce", 256),          # duplicate site: one plan
         Collective("barrier", 0))              # non-allreduce: no plan
        for _ in range(8)))
    plans = planner.plan_program(prog)
    assert set(plans) == {("allreduce", 256), ("allreduce", 1 << 20)}
    info = planner.cache_info()
    # replanning is pure cache hits
    planner.plan_program(prog)
    assert planner.cache_info()["misses"] == info["misses"]


def test_cost_program_fidelities(mpi):
    machine = ExanetMachine(mpi=mpi)
    prog = cg_iteration(8, 4096, 200.0, coll_algo="recursive_doubling")
    sim = machine.cost_program(prog, fidelity="sim")
    ana = machine.cost_program(prog, fidelity="analytic")
    assert sim > 200e-6 and ana > 200e-6     # both include the compute
    compute_only = bsp_step(8, 300.0)
    assert machine.cost_program(compute_only, fidelity="sim") == \
        pytest.approx(300e-6, rel=1e-9)
    assert machine.cost_program(compute_only, fidelity="analytic") == \
        pytest.approx(300e-6, rel=1e-9)


def test_accel_collective_costs_at_both_fidelities(mpi):
    machine = ExanetMachine(mpi=mpi)
    prog = bsp_step(8, 0.0, "allreduce", 4096, coll_algo="accel")
    sim = machine.cost_program(prog, fidelity="sim")
    ana = machine.cost_program(prog, fidelity="analytic")
    # the §4.7 engine is a closed form on either path: identical numbers
    assert sim == pytest.approx(ana, rel=1e-12)
    with pytest.raises(ValueError, match="accelerator"):
        TpuMachine().cost_program(prog)
    # analytic auto considers the accelerator too (the planner's twin):
    # at 256 B the accel closed form beats every software alpha-beta cost
    from repro.core.exanet.allreduce_accel import accel_cost_us
    auto = machine.cost_program(bsp_step(64, 0.0, "allreduce", 256),
                                fidelity="analytic")
    assert auto <= accel_cost_us(256, 64, machine.params) * 1e-6 + 1e-12


def test_tpu_machine_costs_programs():
    tpu = TpuMachine()
    prog = bsp_step(16, 50.0, "allreduce", 1 << 20)
    cost = tpu.cost_program(prog)
    assert cost > 50e-6
    # auto picks the cheapest feasible schedule: never worse than ring
    from repro.core.exanet.schedules import RingAllreduce
    ring = tpu.cost_s(RingAllreduce(), 16, 1 << 20)
    assert cost <= 50e-6 + ring + 1e-12


def test_grad_sync_program_emission(mpi):
    from repro.parallel.grad_sync import emit_sync_program
    sizes = [4 << 20, 64 << 10, 256]
    prog = emit_sync_program(4, sizes, compute_us_per_bucket=100.0)
    assert prog.nranks == 4
    assert [c.nbytes for c in prog.collectives()] == sizes
    res = mpi.run_program(prog)   # algo="auto": planned per bucket
    assert res.latency_us >= 300.0
    assert res.n_collectives == 3
    with pytest.raises(ValueError, match="buckets"):
        emit_sync_program(4, sizes, compute_us_per_bucket=[1.0])


# ------------------------------------------------------- apps on programs
def test_apps_emit_programs_and_params_matter():
    """Regression for the dropped-params bug: factories must hand their
    HwParams to the model, and a machine with different hardware must
    produce different simulated iterations."""
    from repro.core.exanet.apps import hpcg
    slow = dataclasses.replace(
        DEFAULT, bw_wire_intra_qfdb_gbps=6.5, bw_wire_mezz_gbps=3.2,
        rate_intra_qfdb_gbps=8.0, rate_mezz_gbps=5.0)
    m_def, m_slow = hpcg(), hpcg(slow)
    assert m_def.params is DEFAULT
    assert m_slow.params is slow           # the PR-4 satellite fix
    comm_def = m_def._simulate("weak", 8).comm_us
    comm_slow = m_slow._simulate("weak", 8).comm_us
    assert comm_slow > 1.3 * comm_def      # halved links, slower halos


def test_apps_reuse_one_mpi_instance():
    from repro.core.exanet.apps import minife
    m = minife()
    assert m.mpi is m.mpi                  # built once, not per eval
    m._simulate("weak", 2)
    m._simulate("strong", 2)
    assert m._mpi is m.mpi


def test_app_iteration_programs_have_halo_and_dots():
    from repro.core.exanet.apps import hpcg
    prog = hpcg().emit_iteration("weak", 8)
    c = prog.counts()
    assert c["isend"] == 8 * 6
    assert c["collective"] == 8 * 2        # 2 dot allreduces per rank
    assert all(col.algo == "recursive_doubling"   # MPICH 3.2.1, §5.2.1
               for col in prog.collectives())


def test_what_a_program_derives_is_walked_once():
    """Derived data is kept on the (immutable) program object: computed
    once, ignored by equality and hashing, and not carried by
    ``dataclasses.replace``."""
    from repro.core.exanet.program_compiled import extract_data
    prog = halo3d(8, 4096, compute_us=10.0)
    key = prog.structure_key()
    assert prog.structure_key() is key
    assert extract_data(prog) is extract_data(prog)
    assert prog.collectives() == prog.collectives() == []
    twin = halo3d(8, 4096, compute_us=10.0)
    assert twin == prog and hash(twin) == hash(prog)
    moved = dataclasses.replace(prog)
    assert "_structure_key" not in moved.__dict__
    assert moved.structure_key() == key
