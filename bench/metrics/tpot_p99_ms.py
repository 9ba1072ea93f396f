"""p99 of the pooled gaps between output tokens: the stalls in which the
synchronous engine prefills an admission while every active slot waits."""
import stats


def read(run):
    v = stats.percentile(stats.token_gaps(run.drive.records), 99)
    return None if v is None else 1000.0 * v
