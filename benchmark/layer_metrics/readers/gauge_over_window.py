"""A gauge of the server's /metrics, polled once a second over the window:
its mean, optionally as a percentage of one of the configuration's serve
flags (slots, pages)."""

from harness.client import metric_values


def read(ctx, metric: str, percent_of_flag: "str | None" = None):
    w0, w1 = ctx.window
    vals = [v for t, samples in ctx.polls if w0 <= t < w1
            for v in metric_values(samples, metric)]
    if not vals:
        return None
    value = sum(vals) / len(vals)
    if percent_of_flag:
        value = 100.0 * value / float(
            ctx.cell.config["serve_flags"][percent_of_flag])
    return value
