"""A percentile of one named span's duration over the requests whose
traces the server finished inside the window (``/debug/traces``, polled
and de-duplicated by id; host clock of the server)."""

import time

from harness import stats


def read(ctx, span: str, pct: float):
    w0, w1 = ctx.window
    # traces carry wall-clock starts; the window is on the monotonic clock
    offset = time.time() - time.monotonic()
    vals = [s["duration_ms"] for t in ctx.spans.values()
            if w0 <= t.get("started", 0.0) - offset < w1
            for s in t.get("spans", ()) if s.get("name") == span]
    return stats.percentile(vals, pct) if vals else None
