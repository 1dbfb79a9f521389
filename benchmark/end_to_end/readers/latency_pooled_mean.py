"""The mean time per output token over all the work and all the time of
the window: the seconds in which a stream was between its first token and
its last, inside the window, summed over every stream, over the tokens
the streams delivered inside the window after their first groups.

Every stream counts, the preroll's too: it decodes inside the window. A
stream's tokens and time after the window closes do not. So the bounds are
the window's own and not those of the requests that were due in it, whose
last streams drain with no prompt arriving behind them and whose first
find an engine that is still filling: which requests meet those edges is
the seed's choice, and a statistic over requests reads it.

A request that failed or was cut off stays a stream that delivers nothing
until the run stopped looking (``ctx.censor_at``), from its first token,
or from when it was due if it delivered none."""


def read(ctx):
    w0, w1 = ctx.window
    seconds = tokens = 0.0
    for r in ctx.records:
        if not r.events and r.ok:
            continue
        start = r.events[0][0] if r.events else r.due
        end = r.events[-1][0] if r.ok else ctx.censor_at
        seconds += max(0.0, min(end, w1) - max(start, w0))
        tokens += sum(n for t, n in r.events[1:] if w0 <= t < w1)
    return 1000.0 * seconds / tokens if tokens else None
