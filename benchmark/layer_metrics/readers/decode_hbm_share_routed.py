"""The decode step's share of the HBM roofline in the traced window, for
a configuration whose layers route tokens to experts: ``decode_hbm_share``
with the experts a step READ taken from the program's own count, not from
an expectation.

``decode_hbm_share`` hands a shapes module ``batch`` and nothing of the
routing, so a module of routed experts counts those that ``batch`` rows
choosing independently at even routing would touch (``shapes_moe.py``'s
rule), and reads HIGH where the rows choose alike. Here the share of its
experts that a token step of a layer touched is what
``llm_moe_experts_touched_total`` grew by over ``llm_moe_expert_slots_
total`` (``touched`` over ``slots``: the server books both per token step
and expert layer from the dispatch's own pack) between the window's start
and its end; the traced tail offers the window's mix at the window's
rate, so the window's share stands for the tail's. The shapes module
takes it as ``decode_step_bytes(..., experts_read_share=)``; a module
that takes no such argument has nothing for this reader (None).

Batch and contexts are the client's count of the streams between their
first and last token, as ``decode_hbm_share`` samples them, but over the
``capture_s`` seconds the capture was ASKED for from the moment it was
posted: the post returns only when the profiler has stopped and written
its file, tens of seconds after the tail's last request was offered, and
the streams drain meanwhile."""

import inspect

from harness import manifest


def read(ctx, module_regex: str, touched: dict, slots: dict):
    step_ms = manifest.load_reader("per_layer", "module_time_ms").read(
        ctx, module_regex, per_decode_step=True)
    if step_ms is None or ctx.trace_window[1] is None:
        return None
    cfg = ctx.cell.config
    shapes = manifest.shapes_of(cfg)
    if "experts_read_share" not in inspect.signature(
            shapes.decode_step_bytes).parameters:
        return None
    share = manifest.load_reader("per_layer", "counter_ratio").read(
        ctx, touched, slots)
    if share is None:
        return None
    decoding_at = manifest.load_reader(
        "per_layer", "decode_hbm_share").decoding_at
    t0, t1 = ctx.trace_window
    t1 = min(t1, t0 + float(ctx.cell.mix["trace"]["capture_s"]))
    samples = [decoding_at(ctx.records, t0 + 0.05 * i)
               for i in range(max(1, int((t1 - t0) / 0.05)))]
    samples = [s for s in samples if s[0]]
    if not samples:
        return None
    batch = sum(b for b, _ in samples) / len(samples)
    ctx_sum = sum(c for _, c in samples) / len(samples)
    need_s = (shapes.decode_step_bytes(cfg, batch, ctx_sum,
                                       experts_read_share=share)
              / ctx.peaks["hbm_bytes_s"])
    return 100.0 * need_s / (step_ms / 1000.0)
