"""A percentile over the window's requests of the time from when a
request was DUE to its first streamed token, client side: the first-token
time a user sees, through router, server and engine. A failed, refused or
cut-off request is as late as the run could see. (As an end-to-end metric
it did not repeat within half of the largest bound: PERF.md, PR 45.)"""

from harness import stats


def read(ctx, pct: float):
    vals = [stats.ttft_ms(r, ctx.censor_at) for r in ctx.records
            if r.part == "window"]
    return stats.percentile(vals, pct) if vals else None
