"""How often the gateway turned traffic away or lost its replica in the
window: 503s the clients saw, plus flips of ``llm_replica_healthy`` between
consecutive polls of the router's /metrics."""

from harness.client import metric_values


def read(ctx):
    if not ctx.router_polls:
        return None
    seen = sum(1 for r in ctx.records
               if r.part == "window" and r.status == 503)
    states = [tuple(metric_values(s, "llm_replica_healthy"))
              for _, s in ctx.router_polls]
    flips = sum(1 for a, b in zip(states, states[1:]) if a != b)
    return float(seen + flips)
