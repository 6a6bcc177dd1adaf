"""The program's host spans (repro.runtime.spans) and the scan engine's
counters at the host-device boundary."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.core.exanet import ExanetMPI
from repro.core.exanet import scan_engine as se
from repro.core.exanet.apps import ALL_APPS
from repro.runtime import spans

#: every span a degraded scenario replay opens
REPLAY_SPANS = {"replay.prepare", "replay.bind", "replay.degrade",
                "replay.run", "replay.results", "transport.level",
                "transport.waits", "transport.collective",
                "transport.link_consts", "scan.maxplus", "scan.running_max",
                "scan.call", "scan.fetch"}


def _replay(eng):
    """Three degraded scenario columns of an HPCG iteration on 16 ranks:
    fused rendez-vous levels, the running-max kernel, waits and spliced
    allreduces."""
    prog = ALL_APPS["hpcg"]().emit_iteration("weak", 16)
    return ExanetMPI().run_program_scenarios(
        prog, compute_scale=np.array([1.0, 1.1, 1.3]),
        link_scale=np.array([1.0, 2.0, 1.5]), engine=eng)


@pytest.fixture
def spans_on():
    spans.start()
    try:
        yield
    finally:
        spans.stop()


def test_off_is_one_shared_null_context_and_records_nothing():
    spans.start()
    spans.stop()
    a, b = spans.span("replay.bind"), spans.span("scan.call")
    assert a is b
    with a as got:
        assert got is None
    assert spans.names() == frozenset()


def test_on_nested_spans_are_collected(spans_on):
    @spans.traced("outer")
    def work(x):
        with spans.span("inner"):
            return x + 1

    assert work.__name__ == "work"
    assert work(1) == 2
    assert isinstance(spans.span("inner"), jax.profiler.TraceAnnotation)
    assert spans.names() == {"outer", "inner"}
    spans.start()                   # a new traced stretch starts afresh
    assert spans.names() == frozenset()


def test_spans_land_nested_on_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    spans.start()
    try:
        with spans.span("outer"):
            with spans.span("inner"):
                np.ones(1000).sum()
    finally:
        spans.stop()
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("outer", "inner"):
                    found[e.name] = (line.name, e.start_ns, e.end_ns)
    (l_out, s_out, e_out), (l_in, s_in, e_in) = (found["outer"],
                                                 found["inner"])
    assert l_out == l_in
    assert s_out <= s_in < e_in <= e_out


def test_a_replay_opens_its_spans_only_while_on(spans_on):
    eng = se.JaxScanEngine()
    on = _replay(eng)
    assert spans.names() == REPLAY_SPANS
    spans.start()
    spans.stop()
    off = _replay(eng)
    assert spans.names() == frozenset()
    assert on == off


def _counting(factory, seen, kind):
    """Wrap a jitted-kernel factory so that each call adds the ``nbytes``
    of its operands and outputs to ``seen``.  Host (NumPy) operands cross
    with every call; operands already on the device (a fused level's
    constants) crossed once, when they were put there."""
    def make(*static):
        kernel = factory(*static)

        def call(*args):
            for a in jax.tree_util.tree_leaves(args):
                if isinstance(a, np.ndarray):
                    seen["in"] += a.nbytes
                elif id(a) not in seen["on_device"]:
                    seen["on_device"].add(id(a))
                    seen["in"] += a.nbytes
            out = kernel(*args)
            outs = out if isinstance(out, tuple) else (out,)
            seen["out"] += sum(o.nbytes for o in outs)
            seen[kind] += 1
            return out
        return call
    return make


def test_jax_engine_counts_the_bytes_each_kernel_call_moves(
        monkeypatch, staged_jax_engine):
    """Both the fused levels and the staged chain's per-stage kernels."""
    seen = {}
    for kind in ("maxplus", "running_max", "rdv_serial"):
        name = "_maxplus_kernel" if kind == "maxplus" else f"_{kind}_kernel"
        monkeypatch.setattr(se, name,
                            _counting(getattr(se, name), seen, kind))
    for eng, kernels in ((se.JaxScanEngine(), ("rdv_serial", "running_max")),
                         (staged_jax_engine, ("maxplus", "running_max"))):
        seen.update({"in": 0, "out": 0, "maxplus": 0, "running_max": 0,
                     "rdv_serial": 0, "on_device": set()})
        _replay(eng)
        assert {k for k in ("maxplus", "running_max", "rdv_serial")
                if seen[k]} == set(kernels)
        assert sum(eng.dispatches.values()) == \
            seen["maxplus"] + seen["running_max"] + seen["rdv_serial"]
        assert eng.bytes_in == seen["in"] > 0
        assert eng.bytes_out == seen["out"] > 0


def test_jax_engine_reads_devices_once_per_compiled_program():
    class Out:
        shape = (4, 2)
        calls = 0

        def devices(self):
            Out.calls += 1
            return {"dev0"}

    eng = se.JaxScanEngine()
    for _ in range(3):
        eng._record("maxplus", (1, 2), Out())
    eng._record("running_max", (1, 2), Out())
    assert Out.calls == 2
    assert eng.devices == {"dev0"}
    assert sum(eng.dispatches.values()) == 4


@pytest.mark.parametrize("factory,args,name", [
    (se._maxplus_kernel, 3, "jit_maxplus_scan"),
    (se._running_max_kernel, 2, "jit_running_max"),
])
def test_scan_kernels_carry_stable_program_names(factory, args, name):
    shifts = (1, 2)
    x = np.zeros((4, 3))
    masks = tuple(np.ones((4 - s, 1), bool) for s in shifts)
    lowered = factory(shifts).lower(*([x] * (args - 1)), masks)
    assert f"@{name}" in lowered.as_text()
