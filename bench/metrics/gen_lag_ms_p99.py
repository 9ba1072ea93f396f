"""How late the load generator submitted requests: p99 of submit time
minus due time, on the host clock. A late generator delays every first
token by the same amount; the synchronous engine's step is what it
waits behind."""
import stats


def read(run):
    v = stats.percentile([r.submit - r.due for r in run.drive.records], 99)
    return None if v is None else 1000.0 * v
