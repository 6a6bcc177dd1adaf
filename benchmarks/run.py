"""Benchmark driver (deliverable d): one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. The ExaNet-model benchmarks run
everywhere; the dry-run/roofline section is included when results/dryrun
JSONs exist (see scripts/run_dryrun_all.sh).
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import paper_tables  # noqa: E402


def dryrun_rows(out_dir: str = "results/dryrun"):
    rows = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        d = json.load(open(f))
        tag = os.path.basename(f)[:-5]
        if "error" in d:
            rows.append((f"dryrun/{tag}", 0.0, "ERROR " + d["error"][:60]))
        elif "skipped" in d:
            rows.append((f"dryrun/{tag}", 0.0, d["skipped"][:60]))
        else:
            r = d["roofline"]
            rows.append((f"dryrun/{tag}", r["step_bound_s"] * 1e6,
                         f"bottleneck={r['bottleneck']} "
                         f"roofline_frac={r['roofline_fraction']:.3f} "
                         f"peak={d['memory']['peak_gb']:.1f}GB"))
    return rows


SECTIONS = [
    ("Fig14/Table2 osu_latency", paper_tables.osu_latency_rows),
    ("Fig15 osu_bw", paper_tables.osu_bw_rows),
    ("Fig16/18 osu_bcast", paper_tables.osu_bcast_rows),
    ("Fig17 osu_allreduce", paper_tables.osu_allreduce_rows),
    ("Pluggable allreduce schedules", paper_tables.allreduce_schedule_rows),
    ("Collective zoo (schedule split)", paper_tables.collective_zoo_rows),
    ("Fig19 allreduce accelerator", paper_tables.allreduce_accel_rows),
    ("Fig13 IP-over-ExaNet", paper_tables.ip_overlay_rows),
    ("Fig20-22/Table3 app scaling", paper_tables.apps_scaling_rows),
    ("S7 matmul accelerator", paper_tables.matmul_accel_rows),
    ("LayerB TPU collectives", paper_tables.collectives_tpu_rows),
    ("Dry-run roofline", dryrun_rows),
]


def main() -> int:
    """Print every section; returns the number of sections that raised
    (each prints a ``nan,ERROR`` row and the rest still run)."""
    print("name,us_per_call,derived")
    failed = 0
    for title, fn in SECTIONS:
        print(f"# --- {title} ---")
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.3f},{derived}")
        except Exception as e:  # noqa: BLE001 — report, run the rest
            failed += 1
            print(f"{title},nan,ERROR {type(e).__name__}: {e}")
    return failed


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
