"""p95 over every request whose first token came in the window, from its
submission to its first token, on the client's clock."""
from bench.traffic import percentile


def read(w):
    return percentile(w.ttft_s, 95)
