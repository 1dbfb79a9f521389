"""Where the slow requests' token gap goes: one part of one named span,
per output token, averaged over the slow tail of the requests whose
traces the server finished inside the window (``/debug/traces``, polled
and de-duplicated by id; host clock of the server; the window's bounds
as ``span_percentile`` draws them).

Of every ``span`` with ``tokens`` >= 2 the time per token is
``duration_ms / (tokens - 1)``; the tail is the spans at or above the
``from_pct``-th percentile of that. ``part`` is ``"duration_ms"``, an
attribute of the span in ms (``ride_ms``), or the name of a child span
(one whose ``parent_span_id`` is the span's ``span_id``: ``decode.emit``),
whose duration it then is; the value is the mean over the tail of
``part / (tokens - 1)``. So metrics that read disjoint parts of one span
with one ``from_pct`` add up to the one that reads ``duration_ms``,
where every span of the tail carries them. None where no span of the
tail carries the part (an older program, a server without its ledger)."""

import time

from harness import stats


def _part_ms(trace, span, part):
    if part in span:
        return span[part]
    for s in trace.get("spans", ()):
        if (s.get("name") == part and span.get("span_id")
                and s.get("parent_span_id") == span["span_id"]):
            return s.get("duration_ms")
    return None


def read(ctx, span: str, part: str, from_pct: float):
    w0, w1 = ctx.window
    # traces carry wall-clock starts; the window is on the monotonic clock
    offset = time.time() - time.monotonic()
    found = [(t, s) for t in ctx.spans.values()
             if w0 <= t.get("started", 0.0) - offset < w1
             for s in t.get("spans", ())
             if s.get("name") == span and (s.get("tokens") or 0) >= 2
             and s.get("duration_ms") is not None]
    if not found:
        return None
    per_token = [s["duration_ms"] / (s["tokens"] - 1) for _t, s in found]
    cut = stats.percentile(per_token, from_pct)
    vals = []
    for (t, s), each in zip(found, per_token):
        ms = _part_ms(t, s, part) if each >= cut else None
        if ms is not None:
            vals.append(ms / (s["tokens"] - 1))
    return sum(vals) / len(vals) if vals else None
