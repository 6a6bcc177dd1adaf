"""The plain reference of the replay cells: the machine's links and routes,
single transfers and the fault draws by hand, and one whole iteration
against the program's interpreter as a second witness (CPU only)."""

import json

import numpy as np
import pytest

from benchlib import exanet_ref as ref
from benchlib import spec

CFG = json.loads((spec.BENCH / "configs" / "hpcg-weak-512.json").read_text())
M = CFG["machine"]
MACH = ref.Machine(M)
SEED = 3000000041


def test_the_prototype_has_512_cores_and_272_links():
    assert MACH.n_cores == 512
    links = MACH.links()
    # six crossbar pairs in each of 32 QFDBs; torus rings of 4 (X), 4 (Y)
    # and 2 (Z, one link per pair): 32 + 32 + 16
    assert sum(k == ref.INTRA for k, _, _ in links) == 6 * 32
    assert sum(k == ref.MEZZ for k, _, _ in links) == 32 + 32 + 16
    assert len(set(links)) == len(links) == 272


@pytest.mark.parametrize("src,dst,links,routers", [
    (0, 1, (), 0),                                       # same MPSoC
    (0, 4, ((ref.INTRA, 0, 1),), 0),                     # same QFDB
    (0, 16, ((ref.MEZZ, 0, 4),), 2),                     # X neighbour
    (4, 24, ((ref.INTRA, 1, 0), (ref.MEZZ, 0, 4),
             (ref.INTRA, 4, 6)), 2),                     # via net MPSoCs
    (0, 48, ((ref.MEZZ, 0, 12),), 2),                    # X ring, -1 way
    (0, 32, ((ref.MEZZ, 0, 4), (ref.MEZZ, 4, 8)), 3),    # tie goes +1
    (0, 256, ((ref.MEZZ, 0, 64),), 2),                   # Z ring of 2
])
def test_routes_are_dimension_ordered_the_short_way(src, dst, links,
                                                    routers):
    assert MACH.route(src, dst) == (links, routers)


def _one_transfer(faults, n_transfers=1):
    costs = ref._Costs(MACH, faults)
    return [costs.rendezvous(0, 16, 138444, 0.0, M["sw_oneway_base_us"])
            for _ in range(n_transfers)]


def test_a_rendezvous_transfer_by_hand():
    hop = 2 * M["router_latency_us"] + M["link_latency_us"]
    start = 2 * (M["sw_oneway_base_us"] + hop) + M["rdma_startup_us"]
    block = 16384 * 8
    bw = block / (block / (6.42 * 1000) + M["rdma_block_gap_us"]) / 1000
    stream = 138444 * 8 / (bw * 1000)
    first, second = _one_transfer({}, 2)
    assert first == pytest.approx(start + stream + hop, rel=1e-15)
    # the second waits for the R5 core, then queues behind the first on
    # the source DMA wire
    assert second == pytest.approx(first + stream, rel=1e-15)


def test_a_slow_lossy_link_divides_its_bandwidth_and_adds_latency():
    key = (ref.MEZZ, 0, 4)
    healthy, = _one_transfer({})
    slow, = _one_transfer({"slow": {key: 2.0}, "lossy": {key: 0.5},
                           "extra_us": {key: 5.0}})
    hop = 2 * M["router_latency_us"] + M["link_latency_us"]
    block = 16384 * 8
    bw = block / (block / (6.42 / 4 * 1000) + M["rdma_block_gap_us"]) / 1000
    expect = 2 * (M["sw_oneway_base_us"] + hop + 5) + M["rdma_startup_us"] \
        + 138444 * 8 / (bw * 1000) + hop + 5
    assert slow == pytest.approx(expect, rel=1e-15)
    assert slow > healthy


def test_fault_draws_are_a_function_of_the_seed():
    f = CFG["faults"]
    a = ref.sample_faults(np.random.default_rng(SEED), MACH, f, 64)
    b = ref.sample_faults(np.random.default_rng(SEED), MACH, f, 64)
    c = ref.sample_faults(np.random.default_rng(SEED + 1), MACH, f, 64)
    assert a == b and a != c
    links = set(MACH.links())
    for d in a:
        picked = list(d["slow"]) + list(d["lossy"])
        assert len(set(picked)) == len(picked) == 3
        assert set(picked) <= links and set(d["extra_us"]) == set(d["slow"])
        assert all(2.0 <= v <= 8.0 for v in d["slow"].values())
        assert all(0.02 <= v <= 0.3 for v in d["lossy"].values())


def test_the_iteration_matches_the_programs_interpreter():
    """The second witness: the repository's interpreter on a machine
    degraded by each fault set agrees to rounding."""
    from repro.core.exanet.apps import ALL_APPS
    from repro.core.exanet.faults import FaultSpec
    from repro.core.exanet.mpi import ExanetMPI
    prog = ALL_APPS["hpcg"]().emit_iteration("weak", 512)
    draws = ref.sample_faults(np.random.default_rng(SEED), MACH,
                              CFG["faults"], 2)
    # and one on a link that the halo exchange of rank 0 uses
    draws.append({"slow": {(ref.MEZZ, 0, 4): 3.0}, "lossy": {},
                  "extra_us": {(ref.MEZZ, 0, 4): 5.0}})
    it = {**CFG["iteration"], "ranks": 512}
    healthy, _ = ref.iteration(MACH, it, {})
    for d in draws:
        lat, clocks = ref.iteration(MACH, it, d)
        res = ExanetMPI(faults=FaultSpec(
            slow_links=d["slow"], lossy_links=d["lossy"],
            link_extra_latency_us=d["extra_us"])).run_program(
                prog, backend="interp")
        got = np.array([res.latency_us, *res.clocks])
        want = np.array([lat, *clocks])
        assert np.max(np.abs(got - want) / want) < 1e-14
    assert lat > healthy
