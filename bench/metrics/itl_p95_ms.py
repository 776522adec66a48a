"""95th percentile of every gap between consecutive output tokens (ms),
as the engine records them (``ServeMetrics.itl_samples``)."""

from bench.stats import percentile


def read(rec):
    xs = rec.serve.itl_samples
    return percentile(xs, 95) * 1e3 if xs else None
