"""Time to first token, p90 over every request due in the window: the
first token's host stamp minus the request's *scheduled* arrival."""
import stats


def read(run):
    v = stats.percentile(stats.ttfts(run.drive.records), 90)
    return None if v is None else 1000.0 * v
