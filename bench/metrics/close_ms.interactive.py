"""p95 over the window's served requests of ``frontend.close``: submit
until the close of the batch that took the request's first unit, the
admission policy's wait, on the frontend's clock (ms).  None where the
program stamps no close."""
from bench.stats import percentile


def read(win):
    ms = [getattr(r, "close_ms", None) for r in win.requests
          if r.status == "served"]
    ms = [m for m in ms if m is not None]
    return percentile(ms, 95) if ms else None
