"""Queue wait inside the serving engine: p90 of each request's
``admitted_at`` (taken off the engine's queue) minus ``submitted_at``
(``submit()``), both the engine's own host-clock stamps, over the
window's requests."""
import stats


def read(run):
    waits = [r.request.admitted_at - r.request.submitted_at
             for r in run.drive.records
             if getattr(r.request, "admitted_at", None) is not None
             and getattr(r.request, "submitted_at", None) is not None]
    v = stats.percentile(waits, 90)
    return None if v is None else 1000.0 * v
