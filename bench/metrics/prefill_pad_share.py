"""Share of the prefilled tokens that are bucket padding, from the
engine's own counters (``ServeEngine.stats``) over the window and drain."""


def read(run):
    real = run.stats.get("prefill_tokens", 0)
    pad = run.stats.get("prefill_padded_tokens", 0)
    return 100.0 * pad / (real + pad) if real + pad else None
