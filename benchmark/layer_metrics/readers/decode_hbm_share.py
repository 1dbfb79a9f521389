"""The decode step's share of the HBM roofline in the traced window: the
bytes one token step must read (every layer matrix and the output head
once, plus the cached keys and values of the sequences decoding, from
the shape counts the cell's configuration names: ``harness/shapes.py``
where it names none) over the peak bandwidth, over the measured device
time per token step. Batch and contexts are the client's own count of the
streams that were between their first and last token, sampled every 50 ms
of the traced window."""

from harness import manifest


def decoding_at(records, t: float):
    """(streams decoding at t, the sum of their contexts at t)."""
    batch = ctx_sum = 0
    for r in records:
        if r.events and r.events[0][0] <= t < r.events[-1][0]:
            batch += 1
            ctx_sum += r.prompt_tokens + sum(n for at, n in r.events
                                             if at <= t)
    return batch, ctx_sum


def read(ctx, module_regex: str):
    step_ms = manifest.load_reader("per_layer", "module_time_ms").read(
        ctx, module_regex, per_decode_step=True)
    if step_ms is None or ctx.trace_window[1] is None:
        return None
    cfg = ctx.cell.config
    step_s = step_ms / 1000.0
    t0, t1 = ctx.trace_window
    samples = [decoding_at(ctx.records, t0 + 0.05 * i)
               for i in range(max(1, int((t1 - t0) / 0.05)))]
    samples = [s for s in samples if s[0]]
    if not samples:
        return None
    batch = sum(b for b, _ in samples) / len(samples)
    ctx_sum = sum(c for _, c in samples) / len(samples)
    shapes = manifest.shapes_of(cfg)
    need_s = (shapes.decode_step_bytes(cfg, batch, ctx_sum)
              / ctx.peaks["hbm_bytes_s"])
    return 100.0 * need_s / step_s
