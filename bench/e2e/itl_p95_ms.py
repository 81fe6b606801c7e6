"""p95 over every gap between consecutive tokens of one request inside
the window, pooled over all requests (a stall shows)."""
from bench.traffic import percentile


def read(w):
    p = percentile(w.itl_s, 95)
    return None if p is None else p * 1e3
