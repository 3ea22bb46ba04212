"""A percentile of one of the sample series the driver kept on its own
clock during the window (``samples`` of the run)."""

from benchmark.harness.stats import percentile


def read(ctx, series, q):
    values = ctx["run"]["samples"].get(series)
    if not values:
        return None
    return percentile(values, q)
