"""Mean device time of one XLA module (a jitted step of the program) in
the traced window, from the trace's module line; optionally divided by the
configuration's decode steps per dispatch, to give the time per token
step of a fused dispatch."""


def read(ctx, module_regex: str, per_decode_step: bool = False):
    import re

    if not ctx.trace:
        return None
    total = count = 0.0
    for dev in ctx.trace["devices"].values():
        for name, m in dev["modules"].items():
            if re.search(module_regex, name):
                total += m["total_s"]
                count += m["count"]
    if not count:
        return None
    ms = 1000.0 * total / count
    if per_decode_step:
        ms /= float(ctx.cell.config["decode_steps_per_dispatch"])
    return ms
