"""Pooled gaps between consecutive streamed chunks of the window's
requests, client side: with a fused K-step decode, K - 1 near-zero gaps
and one long one per dispatch, so a high percentile is the long one's."""

from harness import stats


def read(ctx, pct: float):
    gaps = stats.pooled_gaps_ms([r for r in ctx.records
                                 if r.part == "window"])
    return stats.percentile(gaps, pct) if gaps else None
