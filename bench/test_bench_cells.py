"""CPU rehearsals of each cell's loop at a reduced size, called through the
harness with the chip check skipped; the control and the planted faults
each come out not correct."""

import dataclasses
import time

import pytest

from benchlib import harness

REPLAY = {"config": {"ranks": 64},
          "traffic": {"columns": 8, "pool_blocks": 2, "check_columns": 8}}


def _run(workload, overrides, *, seed=3000000029, variant=None,
         seconds=0.5):
    return harness.run_cell(workload, seed, seconds, False,
                            t0=time.perf_counter(), require_chip=False,
                            overrides=overrides, variant=variant,
                            log=lambda _msg: None)


@pytest.mark.parametrize("workload,overrides,metrics", [
    ("replay-hpcg512-mc16", REPLAY, {"replay_columns_per_s", "setup_s"}),
    ("replay-hpcg512-mc1024", REPLAY, {"replay_columns_per_s", "setup_s"}),
])
def test_cell_rehearsal_is_correct_and_reports_its_metrics(
        workload, overrides, metrics):
    out = _run(workload, overrides)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_without_a_chip_the_run_stops_before_any_work():
    with pytest.raises(harness.NoChip):
        harness.run_cell("replay-hpcg512-mc16", 1, 1.0, False,
                         t0=time.perf_counter())


def test_replay_float32_control_is_not_correct():
    out = _run("replay-hpcg512-mc16", REPLAY, variant="float32_scan")
    assert not out["correct"], out["checks"]


# ------------------------------------------------------- planted faults
def _scan_state_unchanged(self, D, T, takes):
    return D, T


def _scan_half_the_columns(self, D, T, takes):
    from repro.core.exanet.scan_engine import NUMPY
    D, T = D.copy(), T.copy()
    h = T.shape[-1] // 2
    D[..., :h], T[..., :h] = NUMPY.maxplus_scan(D[..., :h].copy(),
                                                T[..., :h].copy(), takes)
    return D, T


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_columns",
                                   "answer_altered"])
def test_replay_fault_is_not_correct(monkeypatch, fault):
    from repro.core.exanet import scan_engine
    from repro.core.machine import ExanetMachine
    if fault == "state_unchanged":
        monkeypatch.setattr(scan_engine.JaxScanEngine, "maxplus_scan",
                            _scan_state_unchanged)
    elif fault == "half_the_columns":
        monkeypatch.setattr(scan_engine.JaxScanEngine, "maxplus_scan",
                            _scan_half_the_columns)
    else:
        inner = ExanetMachine.cost_program_scenarios

        def altered(self, prog, **kw):
            return [dataclasses.replace(r, clocks=(r.clocks[0] * 1.001,
                                                   *r.clocks[1:]))
                    for r in inner(self, prog, **kw)]
        monkeypatch.setattr(ExanetMachine, "cost_program_scenarios", altered)
    out = _run("replay-hpcg512-mc16", REPLAY)
    assert not out["correct"], out["checks"]
