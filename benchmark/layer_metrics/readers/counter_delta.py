"""A counter of the server's /metrics, after the run minus before it."""

from harness.client import metric_sum


def read(ctx, metric: str, labels: "dict | None" = None):
    labels = labels or {}
    if not any(n == metric for n, _, _ in ctx.after):
        return None
    return (metric_sum(ctx.after, metric, **labels)
            - metric_sum(ctx.before, metric, **labels))
