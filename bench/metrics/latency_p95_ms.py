"""95th percentile over every request served of its completion minus
its scheduled send, on the benchmark's clock (ms)."""
from bench.stats import percentile


def read(win):
    lat = [(s.t_done - s.t_sched) * 1e3 for s in win.sent
           if s.status == "served"]
    return percentile(lat, 95) if lat else None
