"""Queue wait in the serving engine: p90 of the start of the step() that
admitted a request minus its due time (host clock)."""
import stats


def read(run):
    v = stats.percentile([r.admit - r.due for r in run.drive.records
                          if r.admit is not None], 90)
    return None if v is None else 1000.0 * v
