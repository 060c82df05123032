"""p95 of how late the generator sent each request: actual submit
minus scheduled send, on the benchmark's clock (ms)."""
from bench.stats import percentile


def read(win):
    lags = [(s.t_submit - s.t_sched) * 1e3 for s in win.sent]
    return percentile(lags, 95) if lags else None
