"""Device/runtime telemetry: HBM pressure, live buffers, jit compile cost.

The serving metrics in :mod:`server.metrics` describe *requests*; this
module describes the *runtime underneath them* — the layer that goes dark
first when a TPU pod misbehaves (ROADMAP north star: "heavy traffic"
needs HBM headroom and compile-stall visibility, not just TTFT):

- ``llm_device_memory_bytes{device,kind}``: per-device allocator stats
  from ``Device.memory_stats()`` (``bytes_in_use``, ``bytes_limit``,
  ``peak_bytes_in_use``, ...). TPU/GPU runtimes report these; the CPU
  backend returns ``None``, so a live-buffer fallback keeps the family
  populated everywhere (tests and local runs included).
- ``llm_device_live_buffer_bytes{device}``: bytes of live jax arrays per
  device, from ``jax.live_arrays()`` — backend-independent, and the only
  device-memory signal the CPU backend has.
- jit compile counters (``jax.monitoring`` listeners, engine/jit_events.py):
  ``llm_jit_compiles_total`` / ``llm_jit_compile_seconds_total`` count
  backend (XLA) compiles — each one is a jit-cache *miss* that stalled a
  request behind compilation; ``llm_jit_cache_hits_total`` counts
  persistent-compilation-cache hits when that cache is enabled.

A device without ``memory_stats`` or a refresh failure mid-scrape must
never take down ``/metrics``.
"""

from __future__ import annotations

from llms_on_kubernetes_tpu.engine import jit_events
from llms_on_kubernetes_tpu.server.metrics import Counter, Gauge, Registry

# memory_stats() keys worth exporting when present (allocator-dependent;
# unknown keys are ignored rather than exploding label cardinality)
_MEMORY_STAT_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "bytes_reserved",
    "largest_free_block_bytes",
    "pool_bytes",
    "num_allocs",
)


def runtime_metrics(registry: Registry) -> dict:
    """The runtime telemetry metric set (registered unconditionally so the
    series exist on every scrape even before the first refresh)."""
    return {
        "device_memory": Gauge(
            "llm_device_memory_bytes",
            "Per-device allocator statistics from Device.memory_stats(); "
            "kind=live_buffer_bytes is the CPU-backend fallback",
            registry, label_names=("device", "kind")),
        "live_buffers": Gauge(
            "llm_device_live_buffer_bytes",
            "Bytes of live jax arrays per device (backend-independent)",
            registry, label_names=("device",)),
        "jit_compiles": Counter(
            "llm_jit_compiles_total",
            "Backend (XLA) compiles observed — each is a jit compile-cache "
            "miss that stalled work behind compilation", registry),
        "jit_compile_seconds": Counter(
            "llm_jit_compile_seconds_total",
            "Cumulative seconds spent in backend (XLA) compilation",
            registry),
        "jit_cache_hits": Counter(
            "llm_jit_cache_hits_total",
            "Persistent compilation-cache hits (0 unless the cache is "
            "enabled)", registry),
    }


class RuntimeTelemetry:
    """Samples the JAX runtime into a :func:`runtime_metrics` set.

    ``refresh()`` is called from the ``/metrics`` handler (scrape-time
    freshness) and is cheap: ``memory_stats()`` is a dict read,
    ``live_arrays()`` walks the live-buffer list. The compile counters are
    the process's own (:mod:`engine.jit_events`: ``jax.monitoring``
    listeners put on once per process, by ``serve`` before its warmup
    compiles), copied here at every scrape, so a warm restart's
    persistent-cache hits are on its first scrape and the engine, which
    reads the same totals around each dispatch, knows nothing of this
    module.
    """

    def __init__(self, registry: Registry):
        self.metrics = runtime_metrics(registry)
        jit_events.install()
        self._copy_jit_totals()

    # -- compile counters (the process's, copied at scrape) -------------

    def _copy_jit_totals(self) -> None:
        compiles, seconds, hits = jit_events.totals()
        for key, total in (("jit_compiles", compiles),
                           ("jit_compile_seconds", seconds),
                           ("jit_cache_hits", hits)):
            counter = self.metrics[key]
            counter.inc(max(0.0, total - counter.value))

    # -- device memory (pull, at scrape) -------------------------------

    def refresh(self) -> None:
        """Re-sample device memory + live buffers and copy the compile
        counters. Never raises."""
        self._copy_jit_totals()
        try:
            self._refresh_device_memory()
        except Exception:
            pass

    def _refresh_device_memory(self) -> None:
        import jax

        devices = jax.local_devices()
        live: dict[str, float] = {str(d): 0.0 for d in devices}
        try:
            for arr in jax.live_arrays():
                devs = list(arr.devices())
                if not devs:
                    continue
                share = arr.nbytes / len(devs)
                for d in devs:
                    key = str(d)
                    if key in live:
                        live[key] += share
        except Exception:
            pass  # live_arrays can race a deleting buffer; partial is fine

        mem = self.metrics["device_memory"]
        buf = self.metrics["live_buffers"]
        for d in devices:
            name = str(d)
            buf.labels(device=name).set(live.get(name, 0.0))
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats:
                for key in _MEMORY_STAT_KEYS:
                    v = stats.get(key)
                    if isinstance(v, (int, float)):
                        mem.labels(device=name, kind=key).set(float(v))
            else:
                # CPU-safe fallback: the backend reports no allocator
                # stats, so live-buffer bytes stand in for bytes_in_use
                mem.labels(device=name,
                           kind="live_buffer_bytes").set(live.get(name, 0.0))
