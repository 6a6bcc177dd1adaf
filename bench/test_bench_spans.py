"""Self time of the program's spans: on synthetic intervals, in a CPU
rehearsal of ``spans_profile.py``, and on a sweep traced on the chip."""

import gzip
import pathlib

import pytest

import spans_profile
from benchlib import span_times as st
from benchlib import trace_reduce as tr


def test_nested_spans_lose_their_childrens_time():
    spans = [("root", 0, 100, "main"), ("a", 10, 60, "main"),
             ("b", 20, 30, "main"), ("b", 40, 50, "main"),
             ("a", 70, 90, "main")]
    t = st.self_times(spans, (0, 100))
    assert t["root"] == [1, pytest.approx(100e-9), pytest.approx(30e-9)]
    assert t["a"] == [2, pytest.approx(70e-9), pytest.approx(50e-9)]
    assert t["b"] == [2, pytest.approx(20e-9), pytest.approx(20e-9)]
    assert sum(own for _, _, own in t.values()) == pytest.approx(100e-9)


def test_a_span_on_another_line_does_not_subtract():
    spans = [("root", 0, 100, "main"), ("worker", 10, 90, "pool-1"),
             ("child", 40, 60, "main")]
    t = st.self_times(spans, (0, 100))
    assert t["root"][2] == pytest.approx(80e-9)
    assert t["worker"][2] == pytest.approx(80e-9)


def test_self_times_are_clipped_to_the_window():
    spans = [("root", 0, 100, "main"), ("a", 40, 80, "main"),
             ("late", 120, 130, "main")]
    t = st.self_times(spans, (50, 100))
    assert t["root"] == [1, pytest.approx(50e-9), pytest.approx(20e-9)]
    assert t["a"] == [1, pytest.approx(30e-9), pytest.approx(30e-9)]
    assert "late" not in t


def test_idle_intervals_and_their_intersection_with_spans():
    ops = [("x", 10, 20), ("y", 15, 30), ("z", 60, 70)]
    idle = st.idle_intervals(ops, (0, 100))
    assert idle == [[0, 10], [30, 60], [70, 100]]
    _, under = tr.union_ns([(5, 40), (35, 65), (90, 95)])
    assert st.intersection_ns(idle, under) == 5 + 30 + 5


def test_profile_rehearsal_on_the_cpu(tmp_path):
    """The tool end to end at a reduced size: every program span appears,
    and the self times partition the traced window."""
    out = spans_profile.profile_cell(
        "replay-hpcg512-mc16", 3000000029, 0.3, 1, trace_dir=tmp_path,
        require_chip=False, log=lambda _msg: None,
        overrides={"config": {"ranks": 64},
                   "traffic": {"columns": 8, "pool_blocks": 2,
                               "check_columns": 8}})
    assert out["correct"], out["checks"]
    trace = out["trace"]
    names = {n for members in spans_profile.GROUPS.values()
             for n in members}
    assert names | set(spans_profile.ROOTS) == set(trace["spans"])
    assert trace["self_sum_over_sweep"] == pytest.approx(1.0, abs=0.01)
    assert trace["self_sum_over_window"] == pytest.approx(1.0, abs=0.05)
    assert out["dispatches_per_sweep"] > 0
    assert out["bytes_in_per_sweep"] > 0 and out["bytes_out_per_sweep"] > 0
    assert set(out["sweep_s"]) == {"off", "profiler", "spans"}


def test_a_sweep_traced_on_the_chip_is_named_by_program_spans(tmp_path):
    """One warm sweep of replay-hpcg512-mc16 traced on a TPU v5e with the
    program's spans on (``spans_profile.py --keep-trace``): the device
    idles under program spans, which name its longest gaps, and the spans'
    self times add up to the sweep."""
    recorded = pathlib.Path(__file__).parent / "testdata" / \
        "replay16_spans.xplane.pb.gz"
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(recorded.read_bytes()))
    program = {n for members in spans_profile.GROUPS.values()
               for n in members}
    names = program | set(spans_profile.ROOTS)
    out = spans_profile.reduce_trace(str(path), 1, names, sweeps=1)
    assert {n for n, _ in out["idle_gaps"]} <= program
    assert out["idle_under_program_share"] >= 0.95
    assert out["self_sum_over_sweep"] == pytest.approx(1.0, abs=0.01)
    assert out["self_sum_over_window"] >= 0.98
    assert out["spans"]["scan.call"][0] == 145
    assert set(out["programs"]) == {"jit_maxplus_scan", "jit_running_max"}
    assert sum(n for n, _ in out["programs"].values()) == 145
