"""Scan-kernel dispatches the jax lane made per sweep in the window
(``JaxScanEngine.dispatches``, a program counter)."""


def read(run):
    sweeps = run.counters.get("sweeps", 0)
    if not sweeps or "scan_dispatches" not in run.counters:
        return None
    return run.counters["scan_dispatches"] / sweeps
