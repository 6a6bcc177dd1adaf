"""A compiled program's rendez-vous level as one jax dispatch
(``JaxScanEngine.rdv_serial``, DESIGN.md §2.5): it agrees with the staged
chain of per-stage kernels, the 512-rank HPCG replay runs as one such
level, and it moves only what its operands and outputs hold; collective
rounds keep the staged chain."""

import gc

import jax
import numpy as np
import pytest

from repro.core.exanet import ExanetMPI
from repro.core.exanet import scan_engine as se
from repro.core.exanet.apps import ALL_APPS
from repro.core.exanet.faults import batch_fault_axes, sample_fault_spec
from repro.core.exanet.schedules import RecursiveDoublingAllreduce

MPI = ExanetMPI()


@pytest.fixture(scope="module")
def hpcg512():
    """One HPCG weak iteration on all 512 cores and 16 sampled link-fault
    columns (two slow links and one lossy link each)."""
    prog = ALL_APPS["hpcg"]().emit_iteration("weak", 512)
    rng = np.random.default_rng(20231017)
    specs = [sample_fault_spec(rng, MPI.topo, n_slow_links=2,
                               n_lossy_links=1, extra_latency_us=5.0)
             for _ in range(16)]
    return prog, batch_fault_axes(specs, prog)


def _hpcg512_replay(hpcg512, eng):
    prog, axes = hpcg512
    res = MPI.run_program_scenarios(prog, **axes, engine=eng)
    return np.array([[r.latency_us, *r.clocks] for r in res])


def _collective(sizes, eng):
    r = MPI.run_schedule_many(RecursiveDoublingAllreduce(), sizes, 16,
                              engine=eng)
    return np.column_stack([r.latency_us, r.clocks])


@pytest.mark.parametrize("case", ["hpcg512_link_faults",
                                  "collective_rdv_uniform",
                                  "collective_mixed_round"])
def test_fused_level_agrees_with_the_staged_chain(case, request,
                                                  staged_jax_engine):
    if case == "hpcg512_link_faults":
        hpcg512 = request.getfixturevalue("hpcg512")

        def run(eng):
            return _hpcg512_replay(hpcg512, eng)
    else:
        # 64 KiB and 1 MiB are both rendez-vous, over all columns and
        # undegraded; a grid that also holds an eager size (8 B) splits
        # each round's columns.  Collective rounds run staged either way
        sizes = (65536, 1 << 20) if case == "collective_rdv_uniform" \
            else (8, 65536)

        def run(eng):
            return _collective(sizes, eng)
    fused, staged = se.JaxScanEngine(), staged_jax_engine
    a, b = run(fused), run(staged)
    assert staged.levels_fused == 0 and staged.levels_staged > 0
    if case == "hpcg512_link_faults":
        assert fused.levels_fused == 1 and fused.levels_staged == 0
    else:
        assert fused.levels_fused == 0
        assert fused.levels_staged == staged.levels_staged
    np.testing.assert_allclose(a, b, rtol=1e-12, atol=0)
    np.testing.assert_allclose(a, run(None), rtol=1e-12, atol=0)


def _counting_running_max(monkeypatch, seen):
    inner = se._running_max_kernel

    def make(shifts):
        kernel = inner(shifts)

        def call(v, masks):
            out = kernel(v, masks)
            seen["in"] += v.nbytes + sum(m.nbytes for m in masks)
            seen["out"] += out.nbytes
            return out
        return call
    monkeypatch.setattr(se, "_running_max_kernel", make)


def test_hpcg512_replay_runs_each_level_as_one_dispatch(hpcg512,
                                                        monkeypatch):
    """One serial rendez-vous level (the iteration's 3,072 halo sends)
    and 18 eager running maxima per sweep; the level sends its stacked
    issue times, stream durations and row free times and gets back stream
    ends and free times, while its row table crossed once, on the first
    sweep."""
    seen = {"in": 0, "out": 0}
    _counting_running_max(monkeypatch, seen)
    eng = se.JaxScanEngine()
    moved = []
    for _ in range(2):
        before = (eng.bytes_in, eng.bytes_out, seen["in"], seen["out"])
        _hpcg512_replay(hpcg512, eng)
        moved.append((eng.bytes_in - before[0] - (seen["in"] - before[2]),
                      eng.bytes_out - before[1] - (seen["out"] - before[3])))
    assert eng.levels_fused == 2 * 1 and eng.levels_staged == 0
    assert sum(eng.dispatches.values()) == 2 * 19
    assert sum(n for (kernel, *_), n in eng.dispatches.items()
               if kernel == "rdv_serial") == 2 * 1
    assert len(eng._level_cache) == 1

    B = 16
    ops_in = ops_out = consts = 0
    for lv, (u_rows, dev, _) in eng._level_cache.items():
        k, u = len(lv.sel), len(u_rows)
        assert k == 3072
        ops_in += (2 * k + u) * B * 8
        ops_out += (k + u) * B * 8
        consts += sum(a.nbytes for a in jax.tree_util.tree_leaves(dev))
    assert consts > 0
    assert moved[1] == (ops_in, ops_out)
    assert moved[0] == (ops_in + consts, ops_out)


def test_a_level_s_device_constants_go_with_the_level():
    """Programs compiled per call (here, those of an ExanetMPI that is
    dropped) leave no level constants on the device behind."""
    eng, mpi = se.JaxScanEngine(), ExanetMPI()
    mpi.run_program_scenarios(ALL_APPS["hpcg"]().emit_iteration("weak", 16),
                              compute_scale=np.ones(2), engine=eng)
    assert eng.levels_fused > 0 and len(eng._level_cache) > 0
    del mpi
    gc.collect()
    assert len(eng._level_cache) == 0
