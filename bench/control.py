"""Read a cell's numbers compared with the control in the program's place.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For every seed, one run of the cell with its driver's control variant
(``CONTROL`` in ``benchlib/kinds/<kind>.py``; the replay's: its scan
kernels in float32 instead of float64).  Prints one JSON line of checks
per seed.  All runs share one
process, so set-up compiles once.  The benchmark's own runs never run it;
its readings set the upper end of each limit (``PERF.md``).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchlib import harness, spec
    cell = spec.find_cell(args.workload)
    variant = spec.driver(cell.traffic["kind"]).CONTROL
    for seed in args.seeds:
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t0=time.perf_counter(), variant=variant,
                               log=lambda m: print(m, flush=True))
        print(json.dumps({"seed": seed, "variant": variant,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
