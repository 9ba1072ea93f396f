"""Median device-clock gap from the end of one ``jit_decode_step``
execution to the start of the next, over the pairs in the window with no
``jit_prefill`` execution between: the host's share of a decode step
(sampling, position reads, the next step's launch), as the device sees
it (``progtrace``)."""
import progtrace


def read(run):
    return progtrace.decode_gap_ms_p50(progtrace.of(run))
