"""Device-to-host reads per decode step, from the engine's own counters
(``ServeEngine.stats``: ``host_fetches`` over ``decode_steps``) over the
window and drain: each is a round trip the next step waits behind."""


def read(run):
    fetches, steps = run.stats.get("host_fetches"), run.stats.get("decode_steps")
    return fetches / steps if fetches is not None and steps else None
