"""Batch-binding axis + scan-engine seam (DESIGN.md §2.5, PR 6).

Three contracts:

* **Engines** — ``engine="numpy"`` and ``engine="jax"`` are
  interchangeable scan backends; resolution errors are clear, and the
  jax lane scopes float64 to its own kernels (the process-global x64
  flag never flips).
* **Batched == per-binding** to ≤1e-9 for both engines: message-size
  grids and arrival-offset ``t0`` columns through
  ``run_schedule_many``, fuzzed Program batches (mixed structures,
  compute skew, tag permutations, eager/rendez-vous payloads) through
  ``run_program_many``/``bind_batch``, and array-bound Monte-Carlo
  scenario columns through ``run_program_scenarios``/``bind_arrays``.
  The hypothesis twin lives in ``test_property.py``.
* **Auto gate** — ``backend="auto"`` never picks a losing executor:
  below the rank floor programs stay interpreted (the BENCH_apps 0.87x
  nranks=2 regression), at scale the consolidated gate compiles.
"""

import random

import numpy as np
import pytest

from repro.core.exanet import ExanetMPI
from repro.core.exanet import scan_engine as se
from repro.core.exanet.program_compiled import (extract_data,
                                                rebind_program)
from repro.core.exanet.schedules import (RabenseifnerAllreduce,
                                         RecursiveDoublingAllreduce)
from test_program_compiled import BYTES, _assert_equal, _fuzz_program

MPI = ExanetMPI()


@pytest.fixture(params=["numpy", "jax"])
def engine(request):
    return request.param


# ----------------------------------------------------- engine resolution
def test_unknown_engine_name_lists_options():
    with pytest.raises(ValueError, match=r"unknown scan engine 'torch'"):
        se.get_scan_engine("torch")
    with pytest.raises(ValueError, match=r"\['jax', 'numpy'\]"):
        se.get_scan_engine("cupy")


def test_resolve_engine_normalization():
    assert se.resolve_engine(None) is se.NUMPY
    assert se.resolve_engine("numpy") is se.NUMPY
    assert se.resolve_engine(se.NUMPY) is se.NUMPY  # object passthrough
    with pytest.raises(ValueError, match="not a scan engine"):
        se.resolve_engine(3)


def test_jax_engine_scopes_x64_to_its_kernels():
    """A jax-lane replay computes in float64 on the device yet leaves
    the process-global x64 flag off, so co-resident jax code (the LM
    stack) keeps float32 defaults."""
    import jax
    import jax.numpy as jnp
    eng = se.JaxScanEngine()
    r = MPI.run_schedule_many(RecursiveDoublingAllreduce(), (4096, 65536),
                              16, engine=eng)
    assert r.latency_us.dtype == np.float64
    assert sum(eng.dispatches.values()) > 0
    assert eng.devices and all(d.platform == jax.default_backend()
                               for d in eng.devices)
    assert jax.config.jax_enable_x64 is False
    assert jnp.zeros(3).dtype == jnp.float32
    assert jnp.asarray(1.5).dtype == jnp.float32


# ------------------------------------------------- batched schedule runs
@pytest.mark.parametrize("sched_cls", [RecursiveDoublingAllreduce,
                                       RabenseifnerAllreduce])
def test_size_grid_batched_equals_per_size_loop(engine, sched_cls):
    """One batched replay over the OSU size grid == per-size interpreter
    runs, for both engines."""
    n = 16
    batch = MPI.run_schedule_many(sched_cls(), BYTES, n, engine=engine)
    for b, size in enumerate(BYTES):
        ref = MPI.run_schedule(sched_cls(), size, n, backend="interp")
        assert batch.latency_us[b] == pytest.approx(ref.latency_us,
                                                    rel=1e-9), size
        np.testing.assert_allclose(batch.clocks[b], ref.clocks,
                                   rtol=1e-9, atol=1e-12)


def test_arrival_offset_columns_match_interp(engine):
    """t0 turns the batch axis into a Monte-Carlo arrival-offset
    scenario axis: each column == an interpreted skewed fresh start."""
    n, size, B = 16, 4096, 5
    rng = np.random.default_rng(7)
    t0 = rng.uniform(0.0, 5.0, size=(n, B))
    sched = RecursiveDoublingAllreduce()
    batch = MPI.run_schedule_many(sched, (size,) * B, n, t0=t0,
                                  engine=engine)
    for b in range(B):
        ref = MPI.run_schedule(sched, size, n, backend="interp",
                               t0=list(t0[:, b]))
        assert batch.latency_us[b] == pytest.approx(ref.latency_us,
                                                    rel=1e-9), b
        np.testing.assert_allclose(batch.clocks[b], ref.clocks,
                                   rtol=1e-9, atol=1e-12)


def test_run_schedule_t0_exact_on_compiled_backend():
    """A skewed *fresh* start (t0 with reset=True) is now exact on the
    compiled backend too — only reset=False stays interpreter-only."""
    n, size = 8, 65536
    t0 = [0.0, 3.25, 1.5, 0.75, 2.0, 0.0, 4.125, 0.5]
    sched = RabenseifnerAllreduce()
    a = MPI.run_schedule(sched, size, n, backend="interp", t0=t0)
    b = MPI.run_schedule(sched, size, n, backend="compiled", t0=t0)
    assert b.latency_us == pytest.approx(a.latency_us, rel=1e-9)
    for x, y in zip(a.clocks, b.clocks):
        assert y == pytest.approx(x, rel=1e-9, abs=1e-12)
    with pytest.raises(ValueError, match="nonzero occupancy"):
        MPI.run_schedule(sched, size, n, backend="compiled", t0=t0,
                         reset=False)


# -------------------------------------------------- batched program runs
@pytest.mark.parametrize("seed", range(4))
def test_program_batch_equals_per_binding_loop(engine, seed):
    """run_program_many batches mixed-structure fuzz programs (tag
    permutations, eager/rdv payloads, compute skew, embedded
    collectives) through bind_batch; every column == its own
    interpreted run."""
    rng = random.Random(9000 + seed)
    progs = []
    for _ in range(2):  # two base structures -> exercises grouping
        base = _fuzz_program(rng, rng.choice([4, 8, 16]))
        comp, post, _ = extract_data(base)
        progs.append(base)
        for _ in range(2):  # payload variants share the base's artifact
            f = rng.choice([0.0, 0.5, 1.0, 7.3, 130.0])
            g = rng.uniform(0.25, 4.0)
            progs.append(rebind_program(
                base,
                compute_us=[c * g for c in comp],
                post_nbytes=[int(round(x * f)) for x in post]))
    rng.shuffle(progs)
    got = MPI.run_program_many(progs, backend="compiled", engine=engine)
    for i, p in enumerate(progs):
        ref = MPI.run_program(p, backend="interp")
        _assert_equal(ref, got[i], ("batch", seed, i))


def test_scenario_sweep_matches_rebound_interp(engine):
    """bind_arrays scenario columns (per-scenario compute skew + byte
    jitter) == rebind_program + interpreter, column by column.  Uses a
    wave-structured builder — scenario binding requires the scheduling
    order to be payload-invariant (the fuzz programs are not, and the
    check= guard rejects them; see test below)."""
    from repro.core.program import cg_iteration
    prog = cg_iteration(8, 70000, 30.0)
    comp, post, _ = extract_data(prog)
    base_comp = np.array(comp, dtype=np.float64)
    base_post = np.array(post, dtype=np.float64)
    N = 6
    nrng = np.random.default_rng(11)
    cs = nrng.uniform(0.5, 2.0, size=N)
    bs = nrng.uniform(0.25, 3.0, size=N)
    res = MPI.run_program_scenarios(prog, compute_scale=cs, byte_scale=bs,
                                    engine=engine)
    assert len(res) == N
    for b in range(N):
        pb = rebind_program(prog, compute_us=base_comp * cs[b],
                            post_nbytes=np.rint(base_post * bs[b]))
        ref = MPI.run_program(pb, backend="interp")
        _assert_equal(ref, res[b], ("scenario", b))


def test_scenario_per_rank_skew_passes_internal_check(engine):
    """(nranks, N) compute_scale routes per-rank skew through the
    artifact's compute->rank map; check=N cross-checks every column
    against the interpreter and raises on >1e-9 disagreement."""
    from repro.core.program import halo3d
    prog = halo3d(8, 4096, 40.0, overlap=True)
    N = 4
    nrng = np.random.default_rng(5)
    cs = nrng.uniform(0.5, 2.0, size=(8, N))
    res = MPI.run_program_scenarios(prog, compute_scale=cs, engine=engine,
                                    check=N)
    assert len(res) == N


def test_scenario_check_rejects_payload_dependent_order():
    """The check= guard catches builders whose heap firing order shifts
    with the payload (fuzz programs): it must raise, pointing at
    run_program_many, instead of silently returning wrong latencies."""
    from repro.core.exanet.exec_compiled import ProgramStructureError
    nrng = np.random.default_rng(11)
    for seed in range(20):
        prog = _fuzz_program(random.Random(4242 + seed), 8)
        try:
            MPI.run_program_scenarios(
                prog, compute_scale=nrng.uniform(0.5, 2.0, size=6),
                byte_scale=nrng.uniform(0.25, 3.0, size=6), check=6)
        except ProgramStructureError as e:
            assert "run_program_many" in str(e)
            return
    pytest.skip("no payload-dependent fuzz program in 20 seeds")


def test_scenario_argument_validation():
    prog = _fuzz_program(random.Random(1), 4)
    with pytest.raises(ValueError, match="at least one of compute_scale"):
        MPI.run_program_scenarios(prog)
    with pytest.raises(ValueError, match="disagrees on N"):
        MPI.run_program_scenarios(prog, compute_scale=np.ones(3),
                                  byte_scale=np.ones(4))
    with pytest.raises(ValueError, match=r"\(N,\), \(nranks, N\) or "
                                         r"\(n_computes, N\)"):
        MPI.run_program_scenarios(prog, compute_scale=np.ones((3, 2)))


# ------------------------------------------------------------- auto gate
def test_auto_rank_floor_keeps_small_programs_interpreted():
    """The BENCH_apps nranks=2 regression (speedup_compiled = 0.87x):
    below the rank floor backend="auto" must interpret — no compiled
    artifact is built, and results equal the interpreter exactly."""
    m = ExanetMPI()
    prog = _fuzz_program(random.Random(3), 2)
    assert not m._program_auto_compiles(prog, {})
    a = m.run_program(prog, backend="auto")
    assert prog.structure_key() not in getattr(m, "_app_program_cache", {})
    ref = m.run_program(prog, backend="interp")
    _assert_equal(ref, a, "auto-floor-single")
    outs = m.run_program_many([prog, prog], backend="auto")
    assert prog.structure_key() not in getattr(m, "_app_program_cache", {})
    for r in outs:
        _assert_equal(ref, r, "auto-floor-many")


def test_auto_compiles_above_floor(monkeypatch):
    """At/above the floor the consolidated gate compiles (tracing off,
    splices profitable) and agrees with the interpreter — the positive
    side of the regression, floor lowered so the test stays fast."""
    monkeypatch.setattr(ExanetMPI, "PROGRAM_COMPILED_AUTO_MIN_RANKS", 2)
    m = ExanetMPI()
    from repro.core.program import halo3d
    prog = halo3d(8, 4096, 12.5)
    assert m._program_auto_compiles(prog, {})
    a = m.run_program(prog, backend="auto")
    assert prog.structure_key() in m._app_program_cache
    ref = m.run_program(prog, backend="interp")
    _assert_equal(ref, a, "auto-above-floor")
