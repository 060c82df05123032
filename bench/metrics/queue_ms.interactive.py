"""p95 of ``RequestTrace.queue_ms`` (submit to first dispatch, on the
frontend's clock) over the window's served requests (ms)."""
from bench.stats import percentile


def read(win):
    q = [r.queue_ms for r in win.requests if r.status == "served"]
    return percentile(q, 95) if q else None
