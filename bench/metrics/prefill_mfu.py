"""Operations the prefills need (``flops.prefill``, real prompt tokens)
over the prefill program's device time times the chip's peak bf16 rate."""


def read(run):
    t = run.trace.phase_s.get("prefill") if run.trace else None
    if not t:
        return None
    ops, _ = run.work("prefill")
    return 100.0 * ops / (t * run.peaks["flops_bf16"])
