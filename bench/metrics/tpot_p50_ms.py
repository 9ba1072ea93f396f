"""Median of all gaps between consecutive output tokens, pooled over the
requests due in the window: the decode step as users feel it."""
import stats


def read(run):
    v = stats.percentile(stats.token_gaps(run.drive.records), 50)
    return None if v is None else 1000.0 * v
