"""A percentile over the window's requests of the time to the first token
(from when each was DUE) or of the per-request time per output token. A
failed, refused or cut-off request is as late as the run could see."""

from harness import stats


def read(ctx, what: str, pct: float):
    window = [r for r in ctx.records if r.part == "window"]
    if what == "ttft":
        vals = [stats.ttft_ms(r, ctx.censor_at) for r in window]
    elif what == "tpot":
        vals = stats.tpots_with_worst(window)
    else:
        raise ValueError(f"unknown latency {what!r}")
    return stats.percentile(vals, pct) if vals else None
