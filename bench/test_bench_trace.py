"""The trace reduction on synthetic intervals and on a trace recorded on
the chip (CPU only)."""

import gzip
import pathlib

import pytest

from benchlib import trace_reduce as tr


def test_union_merges_overlaps_and_keeps_gaps():
    tot, merged = tr.union_ns([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert tot == 35
    assert merged == [[0, 20], [30, 45]]


def test_busy_idle_programs_and_gaps():
    ops = {0: [("fusion.1", 100, 300), ("fusion.2", 250, 400),
               ("copy.3", 700, 800)]}
    modules = [("jit_kernel(7)", 100, 400), ("jit_kernel(8)", 700, 800)]
    spans = [("outer", 0, 1000), ("inner", 450, 650)]
    s = tr.reduce_events(ops, modules, spans, (0, 1000))
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.programs == {"jit_kernel": [2, pytest.approx(400e-9)]}
    assert s.device_ops[0] == ["fusion.1", pytest.approx(200e-9)]
    # gaps: 0-100 and 800-1000 under outer, 400-700 under the innermost
    # span that covers its middle
    assert s.idle_gaps == [["inner", pytest.approx(300e-9)],
                           ["outer", pytest.approx(200e-9)],
                           ["outer", pytest.approx(100e-9)]]


def test_events_are_clipped_to_the_window():
    ops = {0: [("a", 0, 100), ("b", 150, 400)]}
    s = tr.reduce_events(ops, [], [], (50, 200))
    assert s.busy_s == pytest.approx(100e-9)
    assert s.idle_gaps == [["outside any span", pytest.approx(50e-9)]]


def test_busy_is_averaged_over_chips():
    ops = {0: [("a", 0, 100)], 1: [("a", 0, 50)]}
    s = tr.reduce_events(ops, [], [], (0, 100))
    assert s.chips == 2
    assert s.busy_s == pytest.approx(75e-9)


def test_an_empty_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events({0: []}, [], [], (10, 10))


def test_a_trace_recorded_on_the_chip(tmp_path):
    """One sweep of replay-hpcg512-mc16 traced on a TPU v5e: 262 launches
    of the scan kernels, the device idle nearly all the window, every gap
    named by the benchmark's span around the replay."""
    recorded = pathlib.Path(__file__).parent / "testdata" / \
        "tiny_replay16.xplane.pb.gz"
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(recorded.read_bytes()))
    s = tr.reduce_file(str(path), chips=1,
                       span_names={"batch_fault_axes",
                                   "cost_program_scenarios"})
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.6707, rel=1e-3)
    assert 0 < s.busy_s < 0.01 * s.window_s
    assert s.programs["jit_kernel"][0] == 262
    assert s.device_ops[0][0].endswith("(custom-call)")
    assert {name for name, _ in s.idle_gaps} == {"cost_program_scenarios"}
    with pytest.raises(ValueError):
        tr.reduce_file(str(path), chips=0, span_names=())
    # a span the run did not open names no gap
    s = tr.reduce_file(str(path), chips=1, span_names=())
    assert {name for name, _ in s.idle_gaps} == {"outside any span"}
