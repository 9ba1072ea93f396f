"""Bytes the decode steps need (``flops.decode``) over the decode
program's device time times the chip's peak HBM bandwidth."""


def read(run):
    t = run.trace.phase_s.get("decode") if run.trace else None
    if not t:
        return None
    _, byts = run.work("decode")
    return 100.0 * byts / (t * run.peaks["hbm_bytes_per_s"])
