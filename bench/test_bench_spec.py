"""Discovery by name and the per-layer metric arithmetic of the benchmark
(CPU only)."""

import json
import types

import pytest

from benchlib import spec


def test_every_name_in_the_spec_finds_its_files():
    b = spec.load_spec()
    for w in b["workloads"]:
        cell = spec.find_cell(w["name"], b)
        assert spec.driver(cell.traffic["kind"]).Driver
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in b["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for c in b["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert json.loads((spec.ROOT / c["file"]).read_text())["name"] \
            == c["name"]


def test_a_missing_name_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.find_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v0")


def test_peaks_are_keyed_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_a_cell_reports_only_its_own_metrics():
    b = spec.load_spec()
    b["end_to_end"].append({"name": "elsewhere", "unit": "s",
                            "workloads": ["replay-hpcg512-mc1024"]})
    names = {m["name"] for m in
             spec.find_cell("replay-hpcg512-mc16", b).end_to_end}
    assert names == {"replay_columns_per_s", "setup_s"}
    assert "elsewhere" in {m["name"] for m in spec.find_cell(
        "replay-hpcg512-mc1024", b).end_to_end}


def _run(**kw):
    base = dict(trace=None, counters={}, samples={},
                peaks={}, window_s=1.0, cell=None)
    return types.SimpleNamespace(**{**base, **kw})


def test_a_reader_with_nothing_to_read_returns_nothing():
    for m in spec.load_spec()["per_layer"]:
        assert spec.reader(m["name"])(_run()) is None


def test_readers_divide_by_the_whole_window_and_every_sweep():
    trace = types.SimpleNamespace(window_s=2.0, busy_s=0.5,
                                  idle_share=0.75, programs={})
    assert spec.reader("device_idle_share.replay")(_run(trace=trace)) == 75.0
    assert spec.reader("scan_dispatches_per_replay")(
        _run(counters={"sweeps": 4, "scan_dispatches": 1048})) == 262
