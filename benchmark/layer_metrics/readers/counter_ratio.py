"""A ratio of counters of the server's /metrics over the window: each
side is what one or more series grew by between the window's start and
its end (as ``counter_delta`` reads them), or, for the denominator, the
window's own length in seconds. ``num`` and ``den`` are one
``{"metric": name, "labels": {...}}`` or a list of them (summed);
``den`` may be the string ``"window_s"``. None where a series is not
exported at all (an older program), or the denominator did not move."""

from harness.client import metric_sum


def _delta(ctx, specs):
    specs = [specs] if isinstance(specs, dict) else specs
    total = 0.0
    for spec in specs:
        name, labels = spec["metric"], spec.get("labels") or {}
        if not any(n == name for n, _, _ in ctx.after):
            return None
        total += (metric_sum(ctx.after, name, **labels)
                  - metric_sum(ctx.before, name, **labels))
    return total


def read(ctx, num, den, scale: float = 1.0, per_decode_step: bool = False):
    top = _delta(ctx, num)
    if den == "window_s":
        bottom = ctx.window[1] - ctx.window[0]
    else:
        bottom = _delta(ctx, den)
    if top is None or bottom is None or bottom <= 0:
        return None
    value = scale * top / bottom
    if per_decode_step:
        value /= float(ctx.cell.config["decode_steps_per_dispatch"])
    return value
