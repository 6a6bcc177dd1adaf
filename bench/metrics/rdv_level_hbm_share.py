"""Share of the HBM roofline that the fused rendez-vous levels reach: the
bytes their calls must read and write once (operand, resident row table,
result; counter ``rdv_level_bytes``) over the device seconds of
``jit_rdv_serial`` in the trace times the chip's HBM bandwidth, in %."""


def read(run):
    if run.trace is None:
        return None
    nbytes = run.counters.get("rdv_level_bytes")
    busy = run.trace.programs.get("jit_rdv_serial", (0, 0.0))[1]
    bw = run.peaks.get("hbm_bytes_per_s")
    if not nbytes or not busy or not bw:
        return None
    return 100.0 * nbytes / (busy * bw)
