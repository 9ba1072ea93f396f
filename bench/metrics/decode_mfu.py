"""Operations the decode steps need (``flops.decode``) over the decode
program's device time times the chip's peak bf16 rate."""


def read(run):
    t = run.trace.phase_s.get("decode") if run.trace else None
    if not t:
        return None
    ops, _ = run.work("decode")
    return 100.0 * ops / (t * run.peaks["flops_bf16"])
