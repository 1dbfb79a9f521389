#!/usr/bin/env bash
# CI gate: fast unit tests, native router build + integration tests, and an
# ASan/UBSan pass over the native router (new concurrency — the prober
# thread — and the failover/deadline paths get sanitizer coverage on every
# run). Then a CPU-mode bench.py --smoke (full engine->gateway pipeline +
# the one-line JSON stdout contract) and the entry-point contract checks.
#
# Usage: scripts/ci.sh
# Env:   PYTHON=python3.12 scripts/ci.sh   # alternate interpreter
#
# Exits nonzero if any gate fails. Gates that need a missing toolchain
# (make/g++) are skipped with a notice, not failed, so the script stays
# useful on python-only machines.
set -u

REPO="$(cd "$(dirname "$0")/.." && pwd)"
PY="${PYTHON:-python3}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
fails=0

note() { printf '\n== %s ==\n' "$*"; }

note "unit tests (pytest -m unit)"
if ! "$PY" -m pytest "$REPO/tests" -q -m unit \
    -p no:cacheprovider --continue-on-collection-errors; then
  echo "ci: unit test gate FAILED"
  fails=$((fails + 1))
fi

note "int8 KV parity (teacher-forced margin triage + fused-write kernels)"
# quantized KV pages are a capacity move, not an accuracy move: the
# teacher-forced argmax must agree at every decisive position (PR-4-style
# margin triage) and the quantize-at-write Pallas kernels must produce
# pool bytes identical to the XLA write path
if ! "$PY" -m pytest "$REPO/tests/test_kv_int8.py" -q \
    -p no:cacheprovider --continue-on-collection-errors; then
  echo "ci: int8 KV parity gate FAILED"
  fails=$((fails + 1))
fi

if command -v make >/dev/null 2>&1 && command -v g++ >/dev/null 2>&1; then
  note "native router build"
  if make -C "$REPO/native/router"; then
    note "native router integration tests"
    if ! "$PY" -m pytest "$REPO/tests/test_native_router.py" -q \
        -p no:cacheprovider; then
      echo "ci: native router tests FAILED"
      fails=$((fails + 1))
    fi
  else
    echo "ci: native router build FAILED"
    fails=$((fails + 1))
  fi

  note "native router under ASan/UBSan"
  # the test skips itself if the sanitizer runtime is not installed
  if ! "$PY" -m pytest \
      "$REPO/tests/test_native_sanitizers.py::test_router_under_asan_ubsan" \
      -q -p no:cacheprovider; then
    echo "ci: sanitizer gate FAILED"
    fails=$((fails + 1))
  fi
else
  echo "ci: no C++ toolchain (make/g++) — skipping native gates"
fi

# after the native block so the smoke's gateway phase finds a built
# llkt-router when the toolchain exists (it falls back to the Python
# router — with a warning — when it doesn't)
note "bench smoke (CPU end-to-end: engine + gateway + JSON contract)"
# the smoke's gateway phase dumps both /metrics scrape targets (API
# server + gateway) here for the exposition-format lint gate below
metrics_dump="$(mktemp -d)"
trap 'rm -rf "$metrics_dump"' EXIT
if smoke_out="$(JAX_PLATFORMS=cpu LLMK_METRICS_DUMP="$metrics_dump" \
      "$PY" "$REPO/bench.py" --smoke)" \
    && printf '%s\n' "$smoke_out" | tail -n 1 \
       | "$PY" -c 'import json, sys; json.loads(sys.stdin.readline())'; then
  printf '%s\n' "$smoke_out" | tail -n 1
  echo "ci: bench smoke OK"

  note "multi-tenant adapter smoke (base:adapter through the gateway)"
  # the smoke's gateway phase fires one model=<base>:<adapter> request
  # through the router (native llkt-router when built above) plus an
  # unknown-adapter 404 check; gateway_adapter_ok records the verdict
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
sys.exit(0 if doc.get("gateway_adapter_ok") is True else 1)'; then
    echo "ci: adapter smoke OK"
  else
    echo "ci: adapter smoke FAILED (gateway_adapter_ok not true)"
    fails=$((fails + 1))
  fi

  note "spike smoke (scale-from-zero wake + preemption drain, 0 drops)"
  # the smoke's spike phase bursts streaming clients at a router with
  # zero live replicas, brings two up cold, preempts one mid-serve;
  # every stream must complete or fail over — dropped_streams is a hard 0
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
sys.exit(0 if doc.get("dropped_streams") == 0 else 1)'; then
    echo "ci: spike smoke OK (dropped_streams == 0)"
  else
    echo "ci: spike smoke FAILED (dropped_streams != 0)"
    fails=$((fails + 1))
  fi

  note "resume smoke (kill mid-stream under load, zero client-visible drops)"
  # the smoke's resume phase kills one stream per wave on a live replica
  # (kill_mid_stream fault); the router journal must splice every one —
  # drops are a hard 0 AND at least one resume must actually have fired
  # (a run where the fault never landed would pass the 0-drop check
  # without proving anything)
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
sys.exit(0 if doc.get("resume_client_visible_drops") == 0
         and (doc.get("resumed_streams") or 0) >= 1 else 1)'; then
    echo "ci: resume smoke OK (0 drops, >=1 resumed stream)"
  else
    echo "ci: resume smoke FAILED (drops != 0 or no stream resumed)"
    fails=$((fails + 1))
  fi

  note "fairness smoke (noisy neighbor: QoS keeps interactive TTFT bounded)"
  # the smoke's fairness phase floods a rate-limited batch tenant at 4x
  # its admitted capacity while paced interactive probes run; QoS must
  # keep the interactive p95 TTFT under 2x the unloaded baseline, land
  # >=90% of the sheds on the noisy tenant, let every tenant complete
  # at least one request, and shed batch with the overload 429 body
  # under a forced brownout
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
ratio = doc.get("fairness_ttft_ratio")
frac = doc.get("fairness_shed_noisy_fraction")
sys.exit(0 if ratio is not None and ratio < 2.0
         and (doc.get("fairness_min_tenant_completed") or 0) >= 1
         and frac is not None and frac >= 0.9
         and doc.get("fairness_overload_shed_ok") is True else 1)'; then
    echo "ci: fairness smoke OK (interactive p95 bounded, sheds on noisy)"
  else
    echo "ci: fairness smoke FAILED (starvation, unbounded TTFT, or"
    echo "    sheds not landing on the noisy tenant)"
    fails=$((fails + 1))
  fi

  note "fused decode smoke (K>1 window actually amortizes dispatches)"
  # the smoke engine runs the fused multi-step decode path (decode_steps
  # defaults to 4); dispatches_per_token is per slot, so anything >= 1
  # means every token paid its own device launch — the fusion is off
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
dpt = doc.get("dispatches_per_token")
sys.exit(0 if (doc.get("decode_steps") or 1) > 1
         and dpt is not None and dpt < 1 else 1)'; then
    echo "ci: fused decode smoke OK (dispatches_per_token < 1)"
  else
    echo "ci: fused decode smoke FAILED (dispatches_per_token >= 1)"
    fails=$((fails + 1))
  fi

  note "spec decode smoke (drafts accepted, outputs bit-identical)"
  # the smoke's spec phase runs greedy traffic with speculation on/off:
  # outputs must match exactly (speculation is a pure-perf transform),
  # drafts must actually be accepted on lookup-friendly traffic, and the
  # per-row dispatch rate must beat the plain fused window's 1/(K-1)
  # (0.334 at K=4 — the spec window carries K tokens where the plain
  # multi path pays a dispatch per K-1 after the pipelined overlap)
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
dpt = doc.get("spec_dispatches_per_token")
sys.exit(0 if doc.get("spec_parity_ok") is True
         and (doc.get("spec_accept_ratio") or 0) > 0
         and dpt is not None and dpt < 0.286 else 1)'; then
    echo "ci: spec decode smoke OK (parity, accepts, dispatch rate)"
  else
    echo "ci: spec decode smoke FAILED (parity broken, no accepted"
    echo "    drafts, or spec_dispatches_per_token >= 0.286)"
    fails=$((fails + 1))
  fi

  note "session smoke (int8 KV + host offload tier: reuse beats reprefill)"
  # the smoke's session phase interleaves multi-turn sessions on a device
  # pool too small to keep idle sessions resident: returning turns must
  # actually reuse cached pages (hit ratio > 0, host-tier hits land),
  # produce bit-identical greedy output vs a cache-less engine, come
  # back materially faster than a full re-prefill, report the int8
  # density win (> 1.5x bytes/token vs full-width), and not thrash the
  # host tier (evictions stay below the pages spilled)
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
hits = doc.get("kv_host_cache_hits") or 0
ev = doc.get("kv_host_cache_evictions")
spilled = doc.get("kv_host_cache_spilled_pages") or 0
reuse = doc.get("session_ttft_reuse_ms")
repre = doc.get("session_ttft_reprefill_ms")
sys.exit(0 if doc.get("session_parity_ok") is True
         and (doc.get("session_reuse_hit_ratio") or 0) > 0
         and hits > 0
         and reuse is not None and repre is not None and reuse < repre
         and (doc.get("session_max_streams_ratio") or 0) > 1.5
         and ev is not None and ev <= spilled else 1)'; then
    echo "ci: session smoke OK (reuse hits, parity, TTFT < reprefill)"
  else
    echo "ci: session smoke FAILED (no reuse, parity broken, reuse TTFT"
    echo "    not below reprefill, or host-tier eviction accounting off)"
    fails=$((fails + 1))
  fi

  note "disagg smoke (prefill/decode split: handoff parity, 0 drops)"
  # the smoke's disagg phase runs a prefill + decode + both(fallback)
  # stack behind the two-hop router flow, a long-context flood, and the
  # kill_prefill_replica/drop_handoff fault waves. Gates: greedy stream
  # parity with colocated, zero client-visible drops under faults, each
  # degraded path proven live (ok/reprefill/fallback all fired), decode
  # tok/s under flood at colocated level, the decode pod's ledger idle
  # fraction below the colocated baseline, and interactive TTFT p50
  # bounded under the flood (the 1.2x p99 target is a TPU-pod number;
  # on this GIL-shared CPU sandbox every stack inflates together, so
  # the gate trips on head-of-line blocking, not scheduler noise)
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
ratio = doc.get("disagg_ttft_flood_ratio_p50")
tps = doc.get("disagg_decode_tps_ratio")
idle = doc.get("disagg_decode_idle_frac")
base = doc.get("colocated_decode_idle_frac")
sys.exit(0 if doc.get("disagg_parity_ok") is True
         and doc.get("disagg_dropped_streams") == 0
         and (doc.get("disagg_handoff_ok") or 0) >= 1
         and (doc.get("disagg_handoff_reprefill") or 0) >= 1
         and (doc.get("disagg_handoff_fallback") or 0) >= 1
         and tps is not None and tps >= 0.5
         and idle is not None and base is not None and idle < base
         and ratio is not None and ratio <= 6.0 else 1)'; then
    echo "ci: disagg smoke OK (parity, 0 drops, degraded paths live)"
  else
    echo "ci: disagg smoke FAILED (parity broken, dropped streams,"
    echo "    a degraded handoff path never fired, decode tok/s or"
    echo "    idle fraction regressed vs colocated, or interactive"
    echo "    TTFT blew up under the long-context flood)"
    fails=$((fails + 1))
  fi

  note "chaos smoke (gray failure: outlier ejection + retry budget)"
  # the smoke's chaos phase degrades one of three replicas to 1/8 decode
  # speed while its probes stay green; the router's latency outlier
  # detector must quarantine it from in-band TTFT alone, the surviving
  # pool's p95 TTFT must return to <= 1.5x baseline, the 1/3 ejection
  # guard must have held (one quarantined, two serving), every stream
  # must complete, and a retry wave against an all-dead pool must stay
  # within the token budget and shed with code=retry_budget_exhausted
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
ratio = doc.get("chaos_p95_ttft_ratio")
sys.exit(0 if doc.get("chaos_quarantined_ok") is True
         and doc.get("chaos_guard_ok") is True
         and doc.get("chaos_dropped_streams") == 0
         and ratio is not None and ratio <= 1.5
         and doc.get("chaos_retry_volume_ok") is True
         and (doc.get("chaos_budget_exhausted_sheds") or 0) >= 1
         else 1)'; then
    echo "ci: chaos smoke OK (quarantine, guard, bounded retries)"
  else
    echo "ci: chaos smoke FAILED (no quarantine, guard breached, p95"
    echo "    not recovered, dropped streams, or retry volume over budget)"
    fails=$((fails + 1))
  fi

  note "affinity smoke (cache-aware routing vs blind P2C)"
  # the smoke's affinity phase runs the same shared-system-prompt
  # session workload against a 3-replica stack twice: blind P2C, then
  # with prefix_affinity armed. Gates: affinity-routed TTFT p50 below
  # blind, the session reuse hit ratio above 0.5, total prefill chip-ms
  # below blind (the cache hits the router placed are real chip-time
  # saved, read from the per-pod ledgers), zero dropped streams in every
  # wave, and the quarantine-integration wave: a degraded-but-probe-
  # green pinned replica must be quarantined AND its keys re-pinned to
  # peers with zero drops
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
p50 = doc.get("affinity_ttft_p50_ms")
blind_p50 = doc.get("affinity_blind_ttft_p50_ms")
chip = doc.get("affinity_prefill_chip_ms")
blind_chip = doc.get("affinity_blind_prefill_chip_ms")
ratio = doc.get("affinity_hit_ratio")
sys.exit(0 if None not in (p50, blind_p50, chip, blind_chip, ratio)
         and p50 < blind_p50
         and chip < blind_chip
         and ratio > 0.5
         and doc.get("affinity_dropped_streams") == 0
         and doc.get("affinity_quarantined_ok") is True
         and doc.get("affinity_repin_dropped_streams") == 0
         and doc.get("affinity_repin_ok") is True else 1)'; then
    echo "ci: affinity smoke OK (TTFT/chip-ms below blind P2C, re-pin clean)"
  else
    echo "ci: affinity smoke FAILED (TTFT or prefill chip-ms not below"
    echo "    blind P2C, hit ratio <= 0.5, dropped streams, or the"
    echo "    quarantine re-pin wave broke)"
    fails=$((fails + 1))
  fi

  note "trace smoke (hop-stitched waterfalls + OTLP export)"
  # the smoke's trace phase pushes hedged, resume-spliced and
  # prefill/decode-handoff waves through the tracing router: every wave
  # must stitch into exactly ONE fully-parented waterfall on
  # /debug/trace/<id> (expected hop count, no orphan spans, span
  # interval-union bounded by the stitched e2e) and every hop's spans
  # must reach the local OTLP collector with zero export failures
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
sys.exit(0 if doc.get("trace_stitch_ok") == 1
         and doc.get("trace_export_failures") == 0
         and (doc.get("trace_hops_p50") or 0) >= 2
         and (doc.get("trace_collector_spans") or 0) > 0 else 1)'; then
    echo "ci: trace smoke OK (stitched waterfalls, clean OTLP export)"
  else
    echo "ci: trace smoke FAILED (unstitched or orphaned waterfall,"
    echo "    missing hops, or OTLP span export failures)"
    fails=$((fails + 1))
  fi

  note "goodput ledger smoke (chip-time conservation within 5%)"
  # the engine-phase ledger must conserve wall time: attributed (prefill
  # + decode) + wasted (spec tails, early exits) + idle device gaps
  # reproduce the independently measured engine-loop busy wall within 5%
  # — a leak here means some dispatch path stopped being metered
  if printf '%s\n' "$smoke_out" | tail -n 1 | "$PY" -c '
import json, sys
doc = json.loads(sys.stdin.readline())
attr = doc.get("chip_ms_attributed")
wasted = doc.get("chip_ms_wasted")
idle = doc.get("chip_ms_idle")
wall = doc.get("engine_busy_wall_ms")
if None in (attr, wasted, idle, wall) or wall <= 0:
    sys.exit(1)
total = attr + wasted + idle
sys.exit(0 if abs(total - wall) / wall <= 0.05
         and doc.get("goodput_tokens_per_chip_s") is not None else 1)'; then
    echo "ci: goodput ledger smoke OK (conservation within 5%)"
  else
    echo "ci: goodput ledger smoke FAILED (attributed + wasted + idle"
    echo "    drifts > 5% from the engine-loop busy wall)"
    fails=$((fails + 1))
  fi

  note "metrics lint (Prometheus exposition format on scraped /metrics)"
  if [ -s "$metrics_dump/api_metrics.txt" ] \
      && [ -s "$metrics_dump/gateway_metrics.txt" ] \
      && "$PY" "$REPO/scripts/metrics_lint.py" \
           "$metrics_dump/api_metrics.txt" \
           "$metrics_dump/gateway_metrics.txt"; then
    echo "ci: metrics lint OK"
  else
    echo "ci: metrics lint FAILED"
    fails=$((fails + 1))
  fi
else
  echo "ci: bench smoke FAILED"
  fails=$((fails + 1))
fi

note "bench regression compare (advisory — sandbox numbers are noisy)"
# diff the two most recent BENCH_r*.json; a >20% regression prints loudly
# but does not fail the gate (operators run this on stable hardware)
if "$PY" "$REPO/scripts/bench_compare.py"; then
  echo "ci: bench compare OK"
else
  echo "ci: bench compare flagged regressions (advisory only)"
fi

note "manifest goldens (autoscaler HPA/ScaledObject + helm/python parity)"
# explicit gate on the rendered-manifest contract: the Python renderer's
# golden dicts plus (when a helm binary exists) Go-template parity
if "$PY" -m pytest "$REPO/tests/test_manifests.py" \
    "$REPO/tests/test_helm_golden.py" -q -p no:cacheprovider; then
  echo "ci: manifest goldens OK"
else
  echo "ci: manifest goldens FAILED"
  fails=$((fails + 1))
fi

note "monitoring artifacts (alert rules + dashboard + chart sync)"
if "$PY" "$REPO/scripts/check_monitoring.py"; then
  echo "ci: monitoring artifacts OK"
else
  echo "ci: monitoring artifacts FAILED"
  fails=$((fails + 1))
fi

echo
if [ "$fails" -ne 0 ]; then
  echo "ci: $fails gate(s) failed"
  exit 1
fi
echo "ci: all gates passed"
