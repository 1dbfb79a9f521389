"""The serving engine: continuous batching over jitted prefill/decode steps.

This is the TPU-native replacement for the vLLM container the reference
pulled (reference vllm-models/helm-chart/templates/model-deployments.yaml:21
— continuous batching, paged attention, OpenAI serving all lived in that
image). Design, per SURVEY §7 "hard parts" #2:

- **Static shapes under jit.** Decode runs a fixed slot batch
  [max_decode_slots]; idle slots ride along with length 0. Prompts are
  padded to a small set of prefill buckets. Result: exactly
  1 + len(buckets) compiled executables, no recompilation storms.
- **One scheduler iteration** = admit-waiting → prefill (≤1 bucket call) →
  one decode step for all active slots. Tokens stream out per iteration —
  requests join/leave the batch without stopping it (continuous batching).
- **Paged KV** (engine/cache.py): pages allocated on demand per step;
  pool exhaustion preempts the youngest request back to the wait queue
  (it re-prefills later — prompt + generated so far).
- **Sampling fused into the step** (engine/sampling.py): only [B] int32
  token ids cross the host boundary per step.

The engine is synchronous; server/openai_api.py runs it on a thread and
bridges to asyncio.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import queue
import sys
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from llms_on_kubernetes_tpu.configs import ModelConfig, get_config
from llms_on_kubernetes_tpu.engine import jit_events
from llms_on_kubernetes_tpu.engine.cache import (
    CacheConfig, HostKVCache, PageAllocator, init_pages,
)
from llms_on_kubernetes_tpu.engine.ledger import DECODE_LAUNCH_RULES, SAMPLERS
from llms_on_kubernetes_tpu.engine.qos import (
    TenantFairQueue, normalize_priority, priority_rank,
)
from llms_on_kubernetes_tpu.engine.sampling import (
    MAX_CANDIDATES, HostSample, sample,
)
from llms_on_kubernetes_tpu.models.decoder import (
    LayerAux, forward_chunk, forward_decode, forward_prefill, forward_verify,
    init_conv_state, init_params,
)

Params = dict[str, Any]


# the engine thread's phases (llmk.admit, llmk.pack, llmk.dispatch,
# llmk.harvest, llmk.wait, llmk.emit) on the profiler's clock: any capture
# shows what the host did in each device gap. Outside a capture an
# annotation costs well under a microsecond.
_phase = jax.profiler.TraceAnnotation

# a timed launch aims at least this far ahead of the device running dry
_LEAD_FLOOR_S = 0.004
# the lead falls by this share of its distance to what a launch needed
_LEAD_DECAY = 1.0 / 16.0


class EngineStallError(RuntimeError):
    """The device failed to complete a step within the watchdog budget.

    Raised on the engine thread when a wedged device (or its transport)
    stops producing step completions. ``Engine.step`` converts it into a
    clean shed: every in-flight and waiting request finishes with reason
    "stalled", the engine marks itself ``wedged`` (readiness flips, no
    further dispatch), and submit() rejects new work — HTTP 503 upstream
    instead of a hung serving loop."""


class QueueFullError(RuntimeError):
    """Admission rejected: the waiting queue is at max_waiting capacity.
    The API layer maps this to HTTP 429 + Retry-After."""


class UnknownAdapterError(LookupError):
    """The request named a LoRA adapter this engine does not serve. The
    API layer maps this to a structured HTTP 404 (adapter_not_found) —
    NOT the unknown-model fallback."""


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 1.0
    # 0 => no top-k filter; values > sampling.MAX_CANDIDATES are rejected
    # at submit() (the candidate pool is a hard bound — silent clamping
    # would change the semantics the client asked for)
    top_k: int = 0
    top_p: float = 1.0
    max_tokens: int = 128
    stop_token_ids: tuple[int, ...] = ()
    seed: Optional[int] = None
    # OpenAI penalties over the OUTPUT tokens generated so far (vLLM
    # semantics); applied on device from the engine's per-slot counts
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # top alternatives the client asked to see per token (response shaping
    # only — the engine always records LOGPROB_TOPK alternatives)
    logprobs: int = 0
    # OpenAI logit_bias: ((token_id, bias), ...) added to the raw logits
    # on device every step; at most LOGIT_BIAS_SLOTS entries (rejected at
    # submit beyond that — the packed-row column budget is a hard bound)
    logit_bias: tuple = ()
    # grammar-constrained decoding: a grammar.CompiledGrammar (OpenAI
    # response_format json_object/json_schema, forced tool_choice). The
    # engine masks every sampled token to the grammar's allowed set and
    # advances the FSM on device (engine/grammar.py).
    grammar: Optional[Any] = None
    # resume-after-failure: output tokens ALREADY generated for this
    # request by a previous (now dead) replica. The engine seeds
    # req.output with them, so admission takes the resumed re-prefill
    # path (prompt + prefix, chunked prefill + prefix cache) and decoding
    # continues at sequence position len(prompt) + len(prefix) — the
    # position-keyed sampling chain then draws exactly the tokens the
    # uninterrupted stream would have drawn (bit-identical for a fixed
    # seed, trivially for greedy). Prefix tokens count toward max_tokens
    # and toward the presence/frequency penalty counts, exactly as if
    # this engine had generated them itself.
    prefix_tokens: tuple[int, ...] = ()


@dataclasses.dataclass
class EngineConfig:
    model: str = "debug-tiny"
    dtype: str = "bfloat16"
    max_decode_slots: int = 8
    page_size: int = 64
    num_pages: int = 512
    pages_per_slot: int = 32
    prefill_buckets: tuple[int, ...] = (64, 256, 1024)
    quantization: Optional[str] = None  # None | "int8" (weight-only)
    # multi-host pod group: coordinator broadcasts each step's inputs so
    # follower processes enter the same SPMD programs (engine/multihost.py)
    multihost: bool = False
    # async (pipelined) scheduling: keep up to async_depth decode steps in
    # flight, feeding each step's on-device sampled tokens straight into the
    # next launch; host copies are read by a dedicated harvester thread,
    # one result at a time in launch order, so the ENGINE thread never
    # blocks on device work except for backpressure at full depth —
    # admissions and their prefills dispatch immediately (vLLM-style
    # async scheduling, re-done for JAX's dispatch model), and a first
    # token whose read lands during that wait is handed to its request
    # from inside it. async_depth is the CAP on unharvested steps (it
    # bounds speculation on finishes); WHEN the next steady-state step is
    # launched is timed against the device's queue (Engine._decode_due):
    # a new request's prefill waits behind whatever is enqueued, so a
    # step is enqueued a measured lead before the device would run dry
    # and not as soon as the cap has room. Finishes/stop tokens are detected a
    # transfer-latency late; the speculative extra steps are harmless
    # (their writes land in pages that are only reused after
    # device-ordered completion). Works under multihost too: the packed
    # broadcast tells followers which device-resident token reference
    # feeds each merge.
    async_scheduling: bool = True
    async_depth: int = 2
    # async admission: up to this many same-bucket waiting requests prefill
    # together in one [K, bucket] call (padded to exactly 1 or admit_batch
    # rows so each bucket compiles two executables, not one per K)
    admit_batch: int = 4
    # admission control: submit() raises QueueFullError beyond this many
    # waiting requests (HTTP 429 upstream) — an unbounded queue lets a
    # burst pin memory and inflate TTFT without bound
    max_waiting: int = 256
    # prefix caching: full pages of a prompt already computed by an earlier
    # request are adopted instead of re-prefilled (page-level hash-chained
    # reuse — the vLLM-image capability, SURVEY §2.3 row 1); the remainder
    # prefills through the chunk path with history = the cached length
    prefix_caching: bool = True
    # multimodal: images per request the mm-prefill executable is compiled
    # for (requests with more are rejected at submit); the embeds buffer
    # is padded to this count, so raising it costs only prefill-input HBM
    max_images_per_request: int = 4
    # KV cache storage dtype: None => engine dtype; "int8" => per-token
    # quantized KV (halved decode-attention HBM traffic, doubled token
    # capacity; accuracy pinned by logit-tolerance tests). None also
    # falls through to env LLMK_KV_DTYPE ("" / "none" => off) so the
    # deployment chart can set it without CLI plumbing.
    kv_cache_dtype: Optional[str] = None
    # host-RAM offload tier (engine/cache.HostKVCache): finished and
    # preempted slots spill their full KV pages to a host-side LRU of
    # this many GB, keyed by (tenant, prefix digest); a returning session
    # whose prompt extends a spilled prefix re-uploads the pages and
    # skips straight to decode instead of re-prefilling. Requires
    # prefix_caching (the digest chain IS the addressing scheme).
    # None => env LLMK_KV_HOST_CACHE_GB; <= 0 disables.
    kv_host_cache_gb: Optional[float] = None
    # disaggregated serving role: "both" (default) serves prefill+decode
    # colocated; "prefill" replicas answer generation requests with a KV
    # handoff ticket (prompt ingested, first token sampled, pages spilled
    # to the host tier keyed by chained digest) instead of streaming;
    # "decode" replicas adopt handed-off pages and run the fused K-step
    # loop. The engine itself stays fully capable under every role — the
    # role gates SERVER behavior (openai_api) and deployment shape, so a
    # decode replica can always fall back to colocated serving (full
    # re-prefill) when a handoff goes missing. None => env LLMK_ROLE.
    role: Optional[str] = None
    # grammar-constrained decoding device-table capacities (static jit
    # shapes). A grammar whose tables exceed states/classes caps is
    # rejected at submit (400); distinct RESIDENT grammars beyond
    # max_grammars wait for a slot like page-pool pressure. The arrays
    # only exist once the first constrained request is admitted —
    # grammar-free serving compiles the exact pre-grammar executables.
    max_grammars: int = 4
    grammar_states: int = 4096
    grammar_classes: int = 512
    # watchdog: a device step that produces no completion within
    # max(watchdog_stall_s, 50 x recent step estimate) is declared stalled
    # — the engine sheds all work (EngineStallError -> "stalled" finishes,
    # wedged state, 503s upstream) instead of blocking forever in a
    # harvester wait. None => env LLMK_WATCHDOG_S (default 120); <= 0
    # disables.
    watchdog_stall_s: Optional[float] = None
    # multi-tenant LoRA (engine/adapters.py + ops/lora.py): (name, ref)
    # pairs of servable adapters. Requests pick one by name
    # (model=base:adapter upstream); adapter_slots bounds how many live in
    # the device stacks at once (LRU-recycled), adapter_rank is the stack
    # rank every adapter is padded to (rank > cap rejected at load), and
    # adapter_targets names the weights the stacks attach to. Empty
    # adapters => no stacks exist and every executable is byte-identical
    # to the pre-LoRA engine.
    adapters: tuple = ()
    adapter_slots: int = 4
    adapter_rank: int = 16
    adapter_targets: tuple = ("wq", "wk", "wv", "wo")
    # fused multi-step decode: one jitted dispatch runs this many decode
    # steps via lax.scan — sampling, penalties, stop-token detection, the
    # grammar FSM advance, and per-row early-exit masks all stay on device
    # (_decode_multi_packed_step); the harvester drains up to K packed
    # tokens per dispatch. Amortizes the per-dispatch host round trip
    # (ROADMAP item 5) and is the substrate the verify-k-tokens
    # speculative path lands on. None => env LLMK_DECODE_STEPS (default
    # 4). Forced to 1 under multihost: the message carries K and
    # followers enter the same step, but no two-process run has shown a
    # window across hosts (ROADMAP D6). Streams are bit-identical to
    # decode_steps=1
    # (same PRNG positions, same penalty-count evolution) — pinned by
    # tests/test_decode_multistep.py.
    decode_steps: Optional[int] = None
    # speculative decoding on the fused window (engine/speculation.py):
    # "ngram" drafts up to decode_steps-1 tokens per slot by prompt-lookup
    # over the request's own context; "draft" rolls out a small draft
    # model (draft_model: registry name or .gguf path). Drafted tokens
    # ride the packed window and the target scores all K positions in one
    # verify dispatch — greedy outputs are bit-identical to speculation
    # off (exact-match acceptance), seeded sampling matches through the
    # fold_in(base, seed)+position PRNG chain. None/"off" disables. Forced
    # off under multihost (the K=1 clamp leaves no draft room).
    # Env: LLMK_SPECULATION / LLMK_DRAFT_MODEL.
    speculation: Optional[str] = None
    draft_model: Optional[str] = None
    # per-tenant QoS (engine/qos.py): admission runs deficit-weighted fair
    # queuing across tenants inside strict priority classes. qos_weights /
    # qos_priorities are (tenant, value) pairs (dicts normalize); unlisted
    # tenants get qos_default_weight / qos_default_priority. Starvation
    # aging: a lower-class head waiting > qos_starvation_s is served ahead
    # of higher classes (<= 0 disables). With no tenants configured and no
    # tenant-tagged submissions the queue degenerates to FIFO — every
    # request lands in the one default bucket — so single-tenant serving
    # (and K=1/K=4 decode parity) is byte-identical to the old deque.
    qos_weights: tuple = ()
    qos_priorities: tuple = ()
    qos_default_weight: float = 1.0
    qos_default_priority: str = "normal"
    qos_starvation_s: float = 5.0
    # goodput ledger (engine/ledger.py): per-request chip-time attribution
    # across prefill/decode/spec_waste/early_exit, MFU/MBU accounting, and
    # the step-time anomaly detector. None => env LLMK_LEDGER (default on).
    # Off leaves the dispatch timeline alone (segments and per-shape device
    # times, which the scheduler's launch timing reads): no attribution.
    ledger: Optional[bool] = None
    # anomaly-triggered auto-profiling: when the ledger's EWMA + z-score
    # detector sees a sustained per-dispatch slowdown, the serving loop
    # captures ONE bounded profile (rate-limited by the cooldown). None =>
    # env LLMK_ANOMALY_PROFILE (default on while the ledger is on) /
    # LLMK_ANOMALY_Z (z-score threshold) / LLMK_ANOMALY_COOLDOWN_S.
    anomaly_profile: Optional[bool] = None
    anomaly_z: Optional[float] = None
    anomaly_cooldown_s: Optional[float] = None
    seed: int = 0

    def __post_init__(self):
        import os

        if self.decode_steps is None:
            self.decode_steps = int(os.environ.get("LLMK_DECODE_STEPS", "4"))
        if self.decode_steps < 1:
            raise ValueError(
                f"decode_steps must be >= 1, got {self.decode_steps}")
        if self.multihost and self.decode_steps > 1:
            # no run has had two processes under K > 1 (ROADMAP D6)
            self.decode_steps = 1
        if self.speculation is None:
            self.speculation = os.environ.get("LLMK_SPECULATION") or None
        if self.draft_model is None:
            self.draft_model = os.environ.get("LLMK_DRAFT_MODEL") or None
        if self.speculation in ("off", "none", ""):
            self.speculation = None
        if self.speculation is None and self.draft_model is not None:
            self.speculation = "draft"  # a draft model implies the tier
        if self.speculation not in (None, "ngram", "draft"):
            raise ValueError(
                f"speculation must be one of None/'off'/'ngram'/'draft', "
                f"got {self.speculation!r}")
        if self.speculation == "draft" and self.draft_model is None:
            raise ValueError(
                "speculation='draft' requires draft_model (registry name "
                "or .gguf path)")
        if self.multihost and self.speculation is not None:
            # the K=1 clamp above leaves no draft room, and MSG_DECODE
            # announces the plain step only — reject cleanly rather than
            # diverge
            self.speculation = None
        if self.watchdog_stall_s is None:
            self.watchdog_stall_s = float(
                os.environ.get("LLMK_WATCHDOG_S", "120"))
        if self.kv_cache_dtype is None:
            self.kv_cache_dtype = os.environ.get("LLMK_KV_DTYPE") or None
        if self.kv_cache_dtype in ("off", "none", ""):
            self.kv_cache_dtype = None
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None/'int8', got "
                f"{self.kv_cache_dtype!r}")
        if self.kv_host_cache_gb is None:
            self.kv_host_cache_gb = float(
                os.environ.get("LLMK_KV_HOST_CACHE_GB", "0"))
        if self.kv_host_cache_gb < 0:
            self.kv_host_cache_gb = 0.0
        if self.role is None:
            self.role = os.environ.get("LLMK_ROLE", "both").strip() or "both"
        if self.role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode', or 'both', got "
                f"{self.role!r}")
        if self.role == "prefill" and self.kv_host_cache_gb <= 0:
            raise ValueError(
                "role='prefill' requires kv_host_cache_gb > 0 — handoff "
                "tickets point decode replicas at pages spilled into the "
                "host tier")
        if self.multihost and self.role != "both":
            raise ValueError(
                "role is unsupported under multihost (the KV handoff "
                "rides the coordinator-local host tier)")
        _off = ("0", "false", "off", "no")
        if self.ledger is None:
            self.ledger = (os.environ.get("LLMK_LEDGER", "1")
                           .strip().lower() not in _off)
        if self.anomaly_profile is None:
            self.anomaly_profile = (os.environ.get("LLMK_ANOMALY_PROFILE", "1")
                                    .strip().lower() not in _off)
        if self.anomaly_z is None:
            self.anomaly_z = float(os.environ.get("LLMK_ANOMALY_Z", "4.0"))
        if self.anomaly_z <= 0:
            raise ValueError(
                f"anomaly_z must be > 0, got {self.anomaly_z}")
        if self.anomaly_cooldown_s is None:
            self.anomaly_cooldown_s = float(
                os.environ.get("LLMK_ANOMALY_COOLDOWN_S", "600"))
        if self.anomaly_cooldown_s < 0:
            self.anomaly_cooldown_s = 0.0
        # grammar tables are int16 on device; ABSOLUTE (rebased) state and
        # class ids must fit, or the rebase in _ensure_grammar would wrap
        # silently and mask the wrong tokens
        if not 0 < self.grammar_states <= 32767:
            raise ValueError(
                f"grammar_states must be in (0, 32767], got "
                f"{self.grammar_states}")
        if not 0 < self.grammar_classes <= 32767:
            raise ValueError(
                f"grammar_classes must be in (0, 32767], got "
                f"{self.grammar_classes}")
        # normalize adapters to sorted (name, ref) pairs; names must be
        # usable inside OpenAI model strings ("base:adapter") and metric
        # label values
        if isinstance(self.adapters, dict):
            self.adapters = tuple(sorted(self.adapters.items()))
        else:
            self.adapters = tuple((str(n), str(r)) for n, r in self.adapters)
        seen_names: set = set()
        for name, _ref in self.adapters:
            if not name or ":" in name or "," in name or "=" in name \
                    or any(c.isspace() for c in name):
                raise ValueError(
                    f"adapter name {name!r} is invalid (no ':', ',', '=', "
                    f"whitespace, or empty)")
            if name in seen_names:
                raise ValueError(f"duplicate adapter name {name!r}")
            seen_names.add(name)
        if self.adapters:
            if self.adapter_slots < 1:
                raise ValueError(
                    f"adapter_slots must be >= 1 when adapters are "
                    f"configured, got {self.adapter_slots}")
            if self.adapter_rank < 1:
                raise ValueError(
                    f"adapter_rank must be >= 1, got {self.adapter_rank}")
        self.adapter_targets = tuple(self.adapter_targets)
        # normalize the QoS maps to sorted (tenant, value) pairs, same
        # convention as adapters (hashable config, deterministic order)
        from llms_on_kubernetes_tpu.engine.qos import MIN_WEIGHT, PRIORITIES
        if isinstance(self.qos_weights, dict):
            self.qos_weights = tuple(sorted(self.qos_weights.items()))
        self.qos_weights = tuple(
            (str(t), float(w)) for t, w in self.qos_weights)
        for tenant, w in self.qos_weights:
            if w < MIN_WEIGHT:
                raise ValueError(
                    f"qos weight for tenant {tenant!r} must be >= "
                    f"{MIN_WEIGHT}, got {w}")
        if isinstance(self.qos_priorities, dict):
            self.qos_priorities = tuple(sorted(self.qos_priorities.items()))
        self.qos_priorities = tuple(
            (str(t), str(p)) for t, p in self.qos_priorities)
        for tenant, p in self.qos_priorities:
            if p not in PRIORITIES:
                raise ValueError(
                    f"qos priority for tenant {tenant!r} must be one of "
                    f"{PRIORITIES}, got {p!r}")
        if self.qos_default_priority not in PRIORITIES:
            raise ValueError(
                f"qos_default_priority must be one of {PRIORITIES}, got "
                f"{self.qos_default_priority!r}")
        if self.qos_default_weight < MIN_WEIGHT:
            raise ValueError(
                f"qos_default_weight must be >= {MIN_WEIGHT}, got "
                f"{self.qos_default_weight}")

    @property
    def max_model_len(self) -> int:
        return self.page_size * self.pages_per_slot


@dataclasses.dataclass
class Request:
    id: str
    prompt: list[int]
    params: SamplingParams
    # multimodal: preprocessed pixels [n_images, H, W, C] float32; the
    # prompt carries matching image-soft-token runs (cfg.image_token_id)
    images: Optional[Any] = None
    # mrope models (Qwen3-VL): rope position = token index + this delta
    # for text continuation after images (set at mm admission)
    mrope_delta: int = 0
    # prefix-cache digest salt, computed ONCE at submit: b"" for text,
    # an image-bytes hash for cacheable multimodal prompts, None = skip
    cache_salt: Optional[bytes] = b""
    # resolved sampling seed (user's params.seed, or engine-drawn): the
    # request's sampled stream is fold(base_key, seed, position) — a pure
    # function of the request, never of batch composition or preemption
    seed: int = 0
    submitted_at: float = dataclasses.field(default_factory=time.monotonic)
    # absolute time.monotonic() deadline (end-to-end budget threaded from
    # the client via router/API). Expired in the waiting queue => shed
    # without ever being admitted; expired in a slot => aborted, both with
    # finish_reason "timeout". None = no budget.
    deadline: Optional[float] = None
    # runtime state
    output: list[int] = dataclasses.field(default_factory=list)
    # per output token: (logprob, top_ids, top_logprobs) — recorded by
    # _emit before the token's event is delivered, so readers may index
    # it by token position for any delivered token
    output_logprobs: list = dataclasses.field(default_factory=list)
    slot: int = -1
    pending_token: int = -1        # sampled but KV not yet cached
    # grammar-constrained decoding: the request's row in the device class
    # table and its absolute start state (set at admission, -1 = none);
    # pending_fsm_state carries a host-replayed state the next decode
    # launch must force onto the device (resume-after-preemption)
    fsm_row: int = -1
    fsm_start: int = -1
    pending_fsm_state: Optional[int] = None
    # multi-tenant LoRA: the adapter NAME this request decodes with (None
    # = base model) and its pinned device slot in the LoRA stacks (-1
    # until admission acquires one; released at finish/preemption)
    adapter: Optional[str] = None
    adapter_slot: int = -1
    # per-tenant QoS: the fair-queue bucket this request bills to ("" =
    # the shared default bucket) and its resolved priority class — both
    # fixed at submit; the queue keys on them and preemption prefers
    # lower-priority victims
    tenant: str = ""
    priority: str = "normal"
    # disaggregated serving: True for a prefill-only handoff request (the
    # server answers with a ticket, not a stream) — its spilled pages are
    # drained to the host tier eagerly at finish even on a both-role
    # replica, so the decode replica's pull never races a lazy drain
    handoff: bool = False
    # goodput ledger: device milliseconds attributed to this request per
    # phase (prefill/decode/spec_waste/early_exit) — written by the engine
    # thread as dispatches harvest, surfaced in the OpenAI usage block,
    # the X-LLMK-Chip-Ms header, and the request's trace spans
    chip_ms: dict = dataclasses.field(default_factory=dict)
    finished: bool = False
    finish_reason: Optional[str] = None
    abort_reason: Optional[str] = None  # set by any thread; reaped by step()
    admitted_at: Optional[float] = None  # prefill dispatched (TTFT breakdown)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None  # slot released (_finish)
    # the event that finishes the request was put on its queue
    # (_hand_over): where its ``decode`` span ends and ``stream`` starts
    last_token_at: Optional[float] = None
    # from the dispatch record of the prefill that produced the first
    # token (engine/ledger.py writes each once): the call that enqueued
    # it returned, the device was free for it, its read landed. They
    # split admitted_at..first_token_at for the trace; None without ledger
    prefill_launched_at: Optional[float] = None
    prefill_started_at: Optional[float] = None
    prefill_read_at: Optional[float] = None
    # seqs of the booked dispatches in which the request consumed a decode
    # token, oldest first (the ledger appends one as it books such a
    # dispatch): what GoodputLedger.decode_account splits first_token_at..
    # last_token_at by; empty without ledger
    ride_seqs: list = dataclasses.field(default_factory=list)
    # server-side trace sink (duck-typed: anything with .event(name, **kv));
    # the API layer points this at the request's Trace so engine-side
    # preemption/deadline/stall land on the distributed timeline. None for
    # direct engine use — the engine never requires it.
    trace: Optional[Any] = None
    events: "queue.SimpleQueue[tuple[list[int], bool, Optional[str]]]" = dataclasses.field(
        default_factory=queue.SimpleQueue
    )
    # optional push delivery: called from the ENGINE thread with each
    # event payload (the API server points this at its asyncio loop via
    # call_soon_threadsafe — a blocking queue.get per active stream would
    # park one executor thread per request and starve concurrency)
    on_event: Optional[Any] = None


@dataclasses.dataclass
class StepEvent:
    request: Request
    new_tokens: list[int]
    finished: bool
    finish_reason: Optional[str]
    first: bool = False      # carries the request's first token
    handed_over: bool = False  # already on the request's queue


@dataclasses.dataclass
class InflightStep:
    """A launched-but-unharvested decode dispatch (async scheduling):
    one dispatch carries a WINDOW of up to decode_steps tokens per slot;
    ``planned`` records how many tokens each slot's row was budgeted
    for."""
    pack: Any                              # device packed result [K, B, W]
    toks: Any                              # device [B] sampled tokens (merge)
    active: list[tuple[int, Request]]      # (slot, request) snapshot at launch
    seq: int                               # harvester sequence number
    planned: dict                          # slot -> tokens planned this window
    spec: bool = False                     # speculative verify dispatch:
    #                                        pack is (packs [K,B,W], accept [B])
    drafted: Optional[dict] = None         # slot -> drafted tokens this window
    dseq: int = -1                         # its dispatch record (ledger)


@dataclasses.dataclass
class ChunkChain:
    """A prompt on the chunk path, part-way (async scheduling): one chunk
    is launched a ``step()``, so a decode window, and a bucket's waiting
    prompts every other turn, run between two chunks of it."""
    slot: int
    req: Request
    tokens: list[int]                      # the whole prefill stream
    start: int                             # where the chain began (adopted prefix)
    pos: int                               # the next chunk's first position
    resumed: bool
    yielded: bool = False                  # prompts were let through since
    #                                        its newest chunk


class _Harvester(threading.Thread):
    """Off-thread device->host reader for async scheduling.

    The engine thread pushes device results in LAUNCH order; this thread
    waits on them in that order, one at a time, and publishes each the
    moment it is on the host. The device runs dispatches in launch order
    and ``push`` has begun the transfer, so waiting on the oldest alone
    loses nothing: by the time a result is complete every result
    launched before it has been read. A first token is published when
    ITS prefill is done, never when a neighbour's is, and a decode step
    is read while first tokens are queued behind it (batched reads by
    several readers published a batch when its LAST item landed, and
    starved the decode steps while every reader sat in a first-token
    batch).
    The engine thread polls ``is_done``/``key_done``/``get`` without
    ever blocking on device work, so a newly submitted request is
    admitted and its prefill dispatched IMMEDIATELY, instead of queueing
    behind a blocking read of ``async_depth`` in-flight decode steps (the
    round-2 gateway-TTFT finding). All engine state stays on the engine
    thread; this thread touches only device arrays and the results dict.

    Two classes of key:
    - decode steps (non-negative dense seqs): done-ness is monotone
      (``is_done(s)`` implies every earlier step is done), so the engine
      harvests a strict prefix each step.
    - first tokens (prefill results, negative keys): ``key_done``; the
      engine's backpressure wait (``wait_done(keys=...)``) wakes for one."""

    def __init__(self):
        super().__init__(daemon=True, name="engine-harvester")
        self._cv = threading.Condition()
        self._queue: "collections.deque[tuple[int, Any]]" = collections.deque()
        # key -> (host copy, monotonic time the result was complete on the
        # device: this thread woke from block_until_ready. The goodput
        # ledger segments busy time on these)
        self._done: dict[int, tuple[Any, float]] = {}
        self._done_upto = -1
        self._stopping = False
        # a device_get failure (e.g. an OOM surfacing on the read)
        # must surface on the ENGINE thread, not silently kill the reader —
        # otherwise every wait_done/wait_key blocks forever (observed as a
        # bench hang). All waiters re-raise it.
        self._error: Optional[BaseException] = None
        # cumulative seconds this thread spent blocked on the device and
        # its reads — the engine folds it into its kernel-vs-host
        # attribution; plain float += is safe: only this thread writes,
        # readers tolerate a slightly stale value
        self.device_time_s = 0.0

    def push(self, key: int, res: Any) -> None:
        _start_host_copy(res)  # transfer overlaps with device compute
        with self._cv:
            self._queue.append((key, res))
            self._cv.notify_all()

    def run(self) -> None:
        from llms_on_kubernetes_tpu import faults

        while True:
            with self._cv:
                while not self._queue and not self._stopping:
                    self._cv.wait()
                if not self._queue:
                    return
                key, res = self._queue.popleft()
            try:
                # deterministic fault hooks (LLMK_FAULT=): a wedged device
                # read ("engine_stall" hangs here; the engine thread's
                # watchdog wait must fire) or a slow-but-live device
                # ("slow_step" delays each result)
                faults.inject_hang("engine_stall")
                faults.inject_delay("slow_step", 0.2)
                t0 = time.perf_counter()
                jax.block_until_ready(res)
                t_ready = time.monotonic()
                host = jax.device_get(res)
                self.device_time_s += time.perf_counter() - t0
            except BaseException as e:  # noqa: BLE001 — must not die silent
                with self._cv:
                    self._error = e
                    self._cv.notify_all()
                return
            with self._cv:
                self._done[key] = (host, t_ready)
                if key >= 0:
                    self._done_upto = key
                self._cv.notify_all()

    def _check_error(self) -> None:
        if self._error is not None:
            # re-raise the ORIGINAL exception (same type): callers up the
            # stack classify errors by type+message (bench.py retries
            # JaxRuntimeError UNAVAILABLE)
            raise self._error

    def is_done(self, seq: int) -> bool:
        self._check_error()
        return seq <= self._done_upto

    def key_done(self, key: int) -> bool:
        self._check_error()
        return key in self._done

    def get(self, key: int) -> Any:
        with self._cv:
            return self._done[key][0]

    def done_time(self, key: int) -> float:
        """Monotonic time key's result was complete on the device (ledger
        segmenting); falls back to now for a key already discarded."""
        with self._cv:
            done = self._done.get(key)
        return done[1] if done else time.monotonic()

    def wait_done(self, seq: int, wake: Optional[threading.Event] = None,
                  keys: tuple = (), timeout_s: Optional[float] = None,
                  until: Optional[float] = None) -> None:
        """Block until step ``seq`` is done — or one of the first-token
        ``keys`` is (the caller hands that token over and waits again) —
        or, if ``wake`` is given, until it is set (a new submission wants
        admission NOW — submit() pokes this cv; the caller re-enters its
        loop and the next step() admits before waiting again) — or the
        monotonic clock reaches ``until`` (the moment the caller means to
        launch the next step at). With ``timeout_s`` (the engine's
        watchdog budget) raises EngineStallError if the step is still
        incomplete at the deadline."""
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._cv:
            while self._done_upto < seq:
                self._check_error()
                if wake is not None and wake.is_set():
                    return
                if any(k in self._done for k in keys):
                    return
                now = time.monotonic()
                if until is not None and now >= until:
                    return
                if deadline is not None and now >= deadline:
                    raise EngineStallError(
                        f"device step {seq} produced no completion within "
                        f"{timeout_s:.1f}s watchdog budget")
                waits = [t - now for t in (until, deadline) if t is not None]
                # (the 1 s cap re-checks a reader's error)
                self._cv.wait(timeout=min(waits + [1.0]) if waits else None)

    def poke(self) -> None:
        """Wake any wait_done(wake=...) waiter (called from submit())."""
        with self._cv:
            self._cv.notify_all()

    def wait_key(self, key: int, timeout_s: Optional[float] = None,
                 wake: Optional[threading.Event] = None) -> None:
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._cv:
            while key not in self._done:
                self._check_error()
                if wake is not None and wake.is_set():
                    return
                if deadline is None:
                    self._cv.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise EngineStallError(
                        f"prefill result {key} did not arrive within "
                        f"{timeout_s:.1f}s watchdog budget")
                self._cv.wait(timeout=min(remaining, 1.0))

    def discard_upto(self, seq: int) -> None:
        with self._cv:
            for s in [s for s in self._done if 0 <= s <= seq]:
                del self._done[s]

    def discard_key(self, key: int) -> None:
        with self._cv:
            self._done.pop(key, None)

    def stop(self) -> None:
        with self._cv:
            self._stopping = True
            self._cv.notify_all()


def _merge_tokens(last_toks, src, vals, prefill_toks, prefill_row):
    """Builds the decode input vector on device: per slot take the previous
    in-flight step's sampled token (src 0), a host-known value (src 1), or
    the token sampled by this step's prefill at row prefill_row (src 2)."""
    return jnp.where(src == 0, last_toks,
                     jnp.where(src == 1, vals, prefill_toks[prefill_row]))


def _count_decode_tokens(counts, tokens, active):
    """counts[b, tokens[b]] += active[b] — unrolled DUS (in-place; an HLO
    scatter would copy the [B, V] buffer, see cache.write_tokens), and none
    of its 2 x B one-element ops in a token step where no row is active.

    A row's counts are read by that row's own penalties alone, a request's
    penalties never change while it lives, and a slot's counts are reset
    where a request is admitted to it (_rebuild_count_rows: every prefill
    row, a chunked prompt's first chunk; a resumed request replays its
    outputs through those). So the callers pass as ``active`` the live rows
    that ASK for a penalty (_window_asks), and a slot whose request asks for
    none keeps whatever its counts held."""
    B = counts.shape[0]

    def count(counts):
        inc = active.astype(counts.dtype)
        for b in range(B):
            cur = jax.lax.dynamic_slice(counts, (b, tokens[b]), (1, 1))
            counts = jax.lax.dynamic_update_slice(
                counts, cur + inc[b], (b, tokens[b]))
        return counts

    return jax.lax.cond(active.any(), count, lambda counts: counts, counts)


def _rebuild_count_rows(counts, tokens, slots, history, prompt_len, lengths,
                        reset):
    """Rebuild per-slot output-token counts from a prefill/chunk batch.

    Row semantics: a request's FIRST chunk (reset[r] != 0 — history may be
    nonzero when a cached prefix was adopted) resets the slot's counts; a
    continuation accumulates. Only tokens at global positions >=
    prompt_len count (penalties cover OUTPUT tokens — vLLM semantics);
    that's non-empty exactly for resumed (preempted) re-prefills, whose
    prompt+output tokens replay through this path."""
    K, T = tokens.shape
    V = counts.shape[1]
    t_iota = jnp.arange(T, dtype=jnp.int32)
    for r in range(K):
        out_mask = ((history[r] + t_iota >= prompt_len[r])
                    & (t_iota < lengths[r])).astype(counts.dtype)
        contrib = jnp.zeros((V,), counts.dtype).at[tokens[r]].add(
            out_mask, mode="drop")
        existing = jax.lax.dynamic_slice(counts, (slots[r], 0), (1, V))[0]
        row = jnp.where(reset[r] != 0, 0, existing) + contrib
        # idle/padded rows (lengths 0) keep their slot's counts untouched
        row = jnp.where(lengths[r] > 0, row, existing)
        counts = jax.lax.dynamic_update_slice(
            counts, row[None], (slots[r], 0))
    return counts


# --- packed single-upload step variants (async scheduling) -----------------
# One packed upload per step: every host->device transfer has a fixed
# cost, so the scheduler's small arrays ship as one packed int32 array
# (floats ride along bitcast) instead of one transfer each. The token merge
# and the PRNG fold_in also move inside the executable so a decode step is
# exactly ONE upload + ONE dispatch.

# OpenAI logit_bias: per-request (token id, bias) pairs ride the packed
# rows as LOGIT_BIAS_SLOTS id columns + LOGIT_BIAS_SLOTS value columns
# (float bits), padding id -1 => dropped by the on-device scatter. Maps
# with more entries are rejected at submit() (400 upstream) — the column
# budget is a hard bound, like MAX_CANDIDATES for top_k.
LOGIT_BIAS_SLOTS = 32


# --- grammar-constrained decoding (engine/grammar.py) ----------------------
# The per-slot FSM lives ON DEVICE so constrained requests ride the async
# pipeline: each packed step masks the logits with the allowed-token set of
# the slot's current FSM state and advances the state by the token it
# samples — no host round trip. The packed rows carry:
#   prefill/chunk: [fsm_row, fsm_init]   row in the class table (-1 = this
#     row samples unconstrained) and the absolute start state to assume
#     before sampling (set at admission; -1 for resumed rows, whose decode
#     overrides with the host-replayed state)
#   decode:        [fsm_row, fsm_set, fsm_val]   fsm_set=1 overrides the
#     device state with fsm_val before masking (resume-after-preemption)
# The tables (class_of [G, V] int16, trans [S, C] int16) are device arrays
# rebuilt only when the RESIDENT GRAMMAR SET changes (admission-time, never
# per-step). fsm=None compiles the exact pre-grammar executables — serving
# without grammars pays nothing.


def _fsm_apply(fsm, g_rows, states):
    """Per-row mask + transition lookup: one [R, V] gather serves both.

    Returns (allowed [R, V] bool, nxt_all [R, V] int — the state each
    token would lead to, -1 = token not allowed). Rows with g_rows < 0
    are unconstrained (allowed all-True)."""
    _state_arr, class_of, trans = fsm
    classes = class_of[jnp.maximum(g_rows, 0)]             # [R, V] int16
    row_trans = trans[jnp.maximum(states, 0)]              # [R, C] int16
    nxt_all = jnp.take_along_axis(
        row_trans, classes.astype(jnp.int32), axis=1)      # [R, V]
    constrained = (g_rows >= 0) & (states >= 0)
    allowed = jnp.where(constrained[:, None], nxt_all >= 0, True)
    return allowed, nxt_all, constrained


def _fsm_next(nxt_all, tokens):
    """State after emitting the sampled token ([R] int32)."""
    return jnp.take_along_axis(
        nxt_all, tokens.astype(jnp.int32)[:, None], axis=1)[:, 0].astype(
        jnp.int32)


def _unpack_bias(packed, base: int):
    ids = packed[:, base:base + LOGIT_BIAS_SLOTS]
    vals = jax.lax.bitcast_convert_type(
        packed[:, base + LOGIT_BIAS_SLOTS:base + 2 * LOGIT_BIAS_SLOTS],
        jnp.float32)
    return ids, vals


def _pack_bias(packed: np.ndarray, row: int, base: int, params) -> None:
    packed[row, base:base + LOGIT_BIAS_SLOTS] = -1
    for j, (tid, bv) in enumerate(params.logit_bias):
        packed[row, base + j] = tid
        packed[row, base + LOGIT_BIAS_SLOTS + j] = np.float32(bv).view(np.int32)


# on-device stop-token detection (fused multi-step decode): each row
# carries its request's stop_token_ids so the window's early-exit mask can
# kill the row the moment one is sampled; -1 pads unused slots. More than
# STOP_SLOTS stop ids are rejected at submit() — the column budget is a
# hard bound, like LOGIT_BIAS_SLOTS.
STOP_SLOTS = 8

# packed decode columns: 0 lengths, 1 src, 2 vals, 3 top_k, 4 temps(bits),
# 5 top_p(bits), 6 seed, 7 prefill_row, 8 presence(bits),
# 9 frequency(bits), 10 pos_delta (mrope), 11 adapter_slot (-1 = base),
# 12-14 fsm (row, set, val), 15 window budget (planned alive iterations,
# 0 = the row rides masked), 16.. stop-token ids
# (STOP_SLOTS, -1 padded), then logit_bias ids/vals, then page_table
_ADP_DEC = 11
_FSM_DEC = 12
_BUD_DEC = 15
_STOP_DEC = 16
_BIAS_DEC = _STOP_DEC + STOP_SLOTS
_DEC_COLS = _BIAS_DEC + 2 * LOGIT_BIAS_SLOTS


def _window_asks(packed):
    """What a decode window's packed rows ask of the sampler beyond the
    plain draw: ``(penalised [B] bool, shaped scalar bool)``. A row counts
    if it is live (length > 0: an idle slot and a row that rides masked
    have 0); it is penalised if its presence or frequency penalty is not
    zero, and the window is shaped if a row that counts is penalised or
    carries a logit_bias entry (an id >= 0). ONE predicate for the
    executable (jnp rows) and for the host's booking of the same rows
    (numpy): a float is != 0 exactly where its bits less the sign are, so
    -0.0 is zero on both sides and no flush-to-zero rule can part them."""
    live = packed[:, 0] > 0
    penalised = live & ((packed[:, 8:10] & 0x7FFFFFFF) != 0).any(axis=1)
    biased = (packed[:, _BIAS_DEC:_BIAS_DEC + LOGIT_BIAS_SLOTS] >= 0).any(
        axis=1)
    return penalised, (penalised | (live & biased)).any()


def _forward(forward, cfg, conv, slots, *args, **kw):
    """``forward(*args, **kw)`` -> (logits, k_pages, v_pages, aux): with a
    ``LayerAux`` for a model with conv or Mamba layers (their per-slot
    state, ``conv``; ``slots``: each row's, None where row i is slot i) or
    experts (their row counts come back in it), and with None, traced
    exactly as before there was one, for every other model."""
    if not (cfg.keeps_slot_state or cfg.is_moe):
        return (*forward(*args, **kw), None)
    return forward(*args, aux=LayerAux(conv=conv, slots=slots), **kw)


def _pack_with_moe_rows(pack, aux):
    """A step's packed host rows [B, W] with the rows each expert got
    ([n_moe_layers, E], ``aux.moe_rows``) appended as whole rows, zero
    padded: they reach the host in the read the tokens are read in, and
    nothing that indexes a row by slot sees them (``moe_rows_of``)."""
    if aux is None or aux.moe_rows is None:
        return pack
    W = pack.shape[1]
    flat = aux.moe_rows.reshape(-1)
    n = -(-flat.shape[0] // W)
    flat = jnp.pad(flat, (0, n * W - flat.shape[0]))
    return jnp.concatenate([pack, flat.reshape(n, W).astype(pack.dtype)])


def moe_rows_of(arr: np.ndarray, rows: int, n_moe: int,
                experts: int) -> "np.ndarray | None":
    """The experts' rows [..., n_moe, E] that ``_pack_with_moe_rows`` put
    behind the ``rows`` sample rows of a host pack [..., rows + n, W];
    None where the pack carries none."""
    if not n_moe or arr.shape[-2] <= rows:
        return None
    tail = arr[..., rows:, :].reshape(*arr.shape[:-2], -1)
    return tail[..., :n_moe * experts].reshape(
        *arr.shape[:-2], n_moe, experts)


def _decode_multi_packed_step(params, cfg, K, packed, last_toks,
                              prefill_toks, k_pages, v_pages, counts,
                              base_key, fsm=None, conv=None):
    """The decode step, for every K >= 1: ONE dispatch runs K sampling
    steps via lax.scan, returning the K packed host rows stacked
    [K, B, W] (the synchronous loop enters it with K = 1).

    Parity with K chained K = 1 calls is exact: iteration j
    samples at sequence position lengths0 + j (same PRNG fold_in), counts
    its input token before sampling (same penalty evolution), and feeds
    its sampled token straight into iteration j+1's merge. Per-row
    early-exit: a row whose sampled token hits one of its stop ids — or
    whose planned budget (_BUD_DEC) runs out — is MASKED (lengths 0) for
    the remainder of the window, not recomputed: its KV writes divert to
    the trash page (cache.write_tokens pos<0), its counts stop
    accumulating, and its input token freezes so the host-side replay
    stays deterministic. The host (_emit) remains authoritative for
    finishes — the device mask can only under-run, never over-run, the
    stream. The penalty counts are kept, and penalties and logit_bias
    applied, only in a window where a live row asks for one (_window_asks:
    decided here from the packed rows, inside this one executable; the
    same bits either way). Grammar rows ride the loop: the FSM state is
    scan carry, masked+advanced per iteration. So does ``conv``, the conv
    layers' per-slot state (None for a model without them): a masked row
    leaves its slot's state where its last live step put it."""
    lengths0 = packed[:, 0]
    src, vals = packed[:, 1], packed[:, 2]
    top_ks = packed[:, 3]
    temps = jax.lax.bitcast_convert_type(packed[:, 4], jnp.float32)
    top_ps = jax.lax.bitcast_convert_type(packed[:, 5], jnp.float32)
    seeds = packed[:, 6]
    prefill_row = packed[:, 7]
    presence = jax.lax.bitcast_convert_type(packed[:, 8], jnp.float32)
    frequency = jax.lax.bitcast_convert_type(packed[:, 9], jnp.float32)
    pos_delta = packed[:, 10]
    adapter_idx = packed[:, _ADP_DEC]
    budget = packed[:, _BUD_DEC]
    stop_ids = packed[:, _STOP_DEC:_STOP_DEC + STOP_SLOTS]
    bias = _unpack_bias(packed, _BIAS_DEC)
    page_table = packed[:, _DEC_COLS:]

    toks0 = _merge_tokens(last_toks, src, vals, prefill_toks, prefill_row)
    if fsm is not None:
        g_rows = packed[:, _FSM_DEC]
        state0 = jnp.where(packed[:, _FSM_DEC + 1] == 1,
                           packed[:, _FSM_DEC + 2], fsm[0])
    else:
        state0 = jnp.zeros_like(lengths0)
    alive0 = (lengths0 > 0) & (budget > 0)
    penalised, shaped = _window_asks(packed)

    def body(carry, j):
        cur, alive, state, k_pages, v_pages, counts, conv = carry
        lengths = jnp.where(alive, lengths0 + j, 0)
        # the input token is always a previously-sampled OUTPUT token:
        # count it before sampling so this iteration's draw sees it
        counts = _count_decode_tokens(counts, cur, (lengths > 0) & penalised)
        logits, k_pages, v_pages, aux = _forward(
            forward_decode, cfg, conv, None,
            params, cfg, cur, lengths, k_pages, v_pages, page_table,
            pos_delta=pos_delta, adapter_idx=adapter_idx,
        )
        conv = aux and aux.conv
        keys = _slot_keys(base_key, seeds, lengths)
        allowed = nxt_all = constrained = None
        if fsm is not None:
            allowed, nxt_all, constrained = _fsm_apply(fsm, g_rows, state)
        res = sample(logits, keys, temps, top_ks, top_ps,
                     penalties=(presence, frequency, counts), bias=bias,
                     allowed=allowed, shaped=shaped)
        new_toks = jnp.where(alive, res.tokens, cur)
        if fsm is not None:
            state = jnp.where(constrained & alive,
                              _fsm_next(nxt_all, res.tokens), state)
        stopped = ((stop_ids >= 0)
                   & (stop_ids == res.tokens[:, None])).any(axis=1)
        alive = alive & ~stopped & (j + 1 < budget)
        return (new_toks, alive, state, k_pages, v_pages, counts, conv), \
            _pack_with_moe_rows(res.host_pack(), aux)

    carry0 = (toks0, alive0, state0, k_pages, v_pages, counts, conv)
    (toks, _alive, state, k_pages, v_pages, counts, conv), packs = \
        jax.lax.scan(body, carry0, jnp.arange(K, dtype=jnp.int32))
    new_state = state if fsm is not None else None
    return packs, toks, k_pages, v_pages, counts, new_state, conv


def _decode_spec_packed_step(params, cfg, K, packed, k_pages, v_pages,
                             counts, base_key, fsm=None):
    """Speculative verify dispatch: ONE forward pass scores a window of
    [committed token, K-1 drafted tokens] per slot (forward_verify), then
    K sampling iterations run over the precomputed logits — no further
    model dispatches. Returns ((packs [K, B, W], accept [B]), toks, ...):
    ``accept`` is the per-row count of VALID sampled tokens, which the
    harvest consumes instead of the planned budget.

    Parity with the fused scan (_decode_multi_packed_step) — and therefore
    with K=1 — is exact under greedy decoding and under seeded sampling:
    iteration j sees the SAME logits (forward_verify is the same chunk
    attention the sequential path produces position-by-position, pinned
    bit-identical by tests/test_speculation.py), the same PRNG key
    (_slot_keys folds base+seed+position), and the same penalty-count
    evolution. The only new exit condition is draft mismatch: iteration
    j's sampled token must equal draft j for iteration j+1's logits
    (conditioned on draft j) to be valid — exact-match acceptance, i.e.
    standard greedy speculative decoding. Rejected suffixes already wrote
    KV, but the next dispatch starts at the accepted length and overwrites
    them in place (the PR-8 tail-discard contract); page COUNTS never
    include rejected tokens because the host advances slot_len only for
    accepted ones.

    The packed layout appends K-1 draft columns AFTER the page table:
    [..., _DEC_COLS + pages_per_slot) is the page table, the trailing K-1
    columns are drafts (-1 = none; a row's drafts are prefix-contiguous).
    Spec dispatches launch only with host-known input tokens (src == 1 by
    construction), so last_toks/prefill_toks merging is unnecessary."""
    D = K - 1
    lengths0 = packed[:, 0]
    top_ks = packed[:, 3]
    temps = jax.lax.bitcast_convert_type(packed[:, 4], jnp.float32)
    top_ps = jax.lax.bitcast_convert_type(packed[:, 5], jnp.float32)
    seeds = packed[:, 6]
    presence = jax.lax.bitcast_convert_type(packed[:, 8], jnp.float32)
    frequency = jax.lax.bitcast_convert_type(packed[:, 9], jnp.float32)
    pos_delta = packed[:, 10]
    adapter_idx = packed[:, _ADP_DEC]
    budget = packed[:, _BUD_DEC]
    stop_ids = packed[:, _STOP_DEC:_STOP_DEC + STOP_SLOTS]
    bias = _unpack_bias(packed, _BIAS_DEC)
    page_table = packed[:, _DEC_COLS:packed.shape[1] - D]
    drafts = packed[:, packed.shape[1] - D:]                       # [B, D]

    toks0 = packed[:, 2]                                  # src==1 host value
    if fsm is not None:
        g_rows = packed[:, _FSM_DEC]
        state0 = jnp.where(packed[:, _FSM_DEC + 1] == 1,
                           packed[:, _FSM_DEC + 2], fsm[0])
    else:
        state0 = jnp.zeros_like(lengths0)
    alive0 = (lengths0 > 0) & (budget > 0)
    penalised, shaped = _window_asks(packed)

    # verify window: committed token + drafts; write length w covers only
    # prefix-contiguous drafts and never exceeds the planned page budget
    has = jnp.cumprod((drafts >= 0).astype(jnp.int32), axis=1)     # [B, D]
    n_drafts = has.sum(axis=1)
    w = jnp.where(alive0, jnp.minimum(budget, 1 + n_drafts), 0)
    verify = jnp.concatenate(
        [toks0[:, None], jnp.maximum(drafts, 0)], axis=1)          # [B, K]
    history = jnp.maximum(lengths0 - 1, 0)
    logits_all, k_pages, v_pages = forward_verify(
        params, cfg, verify, history, w, k_pages, v_pages, page_table,
        pos_delta=pos_delta, adapter_idx=adapter_idx,
    )

    cur, alive, state = toks0, alive0, state0
    accept = jnp.zeros_like(lengths0)
    packs = []
    for j in range(K):
        lengths = jnp.where(alive, lengths0 + j, 0)
        # the input token is always a previously-committed OUTPUT token:
        # count it before sampling so this iteration's draw sees it
        counts = _count_decode_tokens(counts, cur, (lengths > 0) & penalised)
        keys = _slot_keys(base_key, seeds, lengths)
        allowed = nxt_all = constrained = None
        if fsm is not None:
            allowed, nxt_all, constrained = _fsm_apply(fsm, g_rows, state)
        res = sample(logits_all[:, j], keys, temps, top_ks, top_ps,
                     penalties=(presence, frequency, counts), bias=bias,
                     allowed=allowed, shaped=shaped)
        new_toks = jnp.where(alive, res.tokens, cur)
        if fsm is not None:
            state = jnp.where(constrained & alive,
                              _fsm_next(nxt_all, res.tokens), state)
        accept = accept + alive.astype(accept.dtype)
        packs.append(res.host_pack())
        stopped = ((stop_ids >= 0)
                   & (stop_ids == res.tokens[:, None])).any(axis=1)
        alive = alive & ~stopped & (j + 1 < budget)
        if j < D:
            # iteration j+1's logits were conditioned on draft j: they are
            # valid only if the sampled token exactly matches the draft
            alive = alive & (res.tokens == drafts[:, j]) & (has[:, j] > 0)
        cur = new_toks
    new_state = state if fsm is not None else None
    return ((jnp.stack(packs), accept), cur, k_pages, v_pages, counts,
            new_state)


# packed prefill columns: 0 lengths, 1 top_k, 2 temps(bits), 3 top_p(bits),
# 4 seed, 5 presence(bits), 6 frequency(bits), 7 slot, 8 prompt_len,
# 9 adapter_slot (-1 = base), 10-11 fsm (row, init), 12.. logit_bias
# ids/vals, then page_table
_ADP_PRE = 9
_FSM_PRE = 10
_BIAS_PRE = 12
_PRE_COLS = _BIAS_PRE + 2 * LOGIT_BIAS_SLOTS


def _fsm_scatter(fsm, g_rows, init, nxt_all, tokens, lengths, slots):
    """Prefill/chunk/mm per-slot state scatter. Rows write their slot's
    FSM state only when they are constrained FRESH starts (fsm_row >= 0
    and fsm_init >= 0) and real (length > 0); everything else leaves the
    slot's device state alone (resumed rows are overridden by their first
    decode's fsm_set instead)."""
    nxt = _fsm_next(nxt_all, tokens)
    write = (g_rows >= 0) & (init >= 0) & (lengths > 0)
    slot_eff = jnp.where(write, slots, fsm[0].shape[0])  # OOB => dropped
    return fsm[0].at[slot_eff].set(nxt, mode="drop")


def _prefill_mm_packed_step(params, cfg, tokens, packed, img_embeds,
                            deepstack, pos3, k_pages, v_pages, counts,
                            base_key, fsm=None):
    """Multimodal prefill ([1, bucket]): image soft-token embeddings are
    substituted inside forward_prefill_mm; sampling/penalties identical
    to the text prefill. ``deepstack``/``pos3`` are None for gemma-3 and
    carry the DeepStack features / 3-axis mrope positions for Qwen3-VL."""
    from llms_on_kubernetes_tpu.models.decoder import forward_prefill_mm

    lengths = packed[:, 0]
    top_ks = packed[:, 1]
    temps = jax.lax.bitcast_convert_type(packed[:, 2], jnp.float32)
    top_ps = jax.lax.bitcast_convert_type(packed[:, 3], jnp.float32)
    seeds = packed[:, 4]
    presence = jax.lax.bitcast_convert_type(packed[:, 5], jnp.float32)
    frequency = jax.lax.bitcast_convert_type(packed[:, 6], jnp.float32)
    slots = packed[:, 7]
    prompt_len = packed[:, 8]
    adapter_idx = packed[:, _ADP_PRE]
    bias = _unpack_bias(packed, _BIAS_PRE)
    page_table = packed[:, _PRE_COLS:]

    counts = _rebuild_count_rows(
        counts, tokens, slots, jnp.zeros_like(lengths), prompt_len, lengths,
        jnp.ones_like(lengths))
    logits, k_pages, v_pages = forward_prefill_mm(
        params, cfg, tokens, lengths, k_pages, v_pages, page_table,
        img_embeds, deepstack=deepstack, pos3=pos3, prompt_len=prompt_len,
        adapter_idx=adapter_idx,
    )
    keys = _slot_keys(base_key, seeds, lengths)
    allowed = nxt_all = new_state = None
    if fsm is not None:
        g_rows, init = packed[:, _FSM_PRE], packed[:, _FSM_PRE + 1]
        allowed, nxt_all, _ = _fsm_apply(fsm, g_rows, init)
    res = sample(logits, keys, temps, top_ks, top_ps,
                 penalties=(presence, frequency, counts[slots]), bias=bias,
                 allowed=allowed)
    if fsm is not None:
        new_state = _fsm_scatter(fsm, g_rows, init, nxt_all, res.tokens,
                                 lengths, slots)
    return res.host_pack(), res.tokens, k_pages, v_pages, counts, new_state


def _prefill_packed_step(params, cfg, tokens, packed, k_pages, v_pages,
                         counts, base_key, fsm=None, conv=None):
    lengths = packed[:, 0]
    top_ks = packed[:, 1]
    temps = jax.lax.bitcast_convert_type(packed[:, 2], jnp.float32)
    top_ps = jax.lax.bitcast_convert_type(packed[:, 3], jnp.float32)
    seeds = packed[:, 4]
    presence = jax.lax.bitcast_convert_type(packed[:, 5], jnp.float32)
    frequency = jax.lax.bitcast_convert_type(packed[:, 6], jnp.float32)
    slots = packed[:, 7]
    prompt_len = packed[:, 8]
    adapter_idx = packed[:, _ADP_PRE]
    bias = _unpack_bias(packed, _BIAS_PRE)
    page_table = packed[:, _PRE_COLS:]

    counts = _rebuild_count_rows(
        counts, tokens, slots, jnp.zeros_like(lengths), prompt_len, lengths,
        jnp.ones_like(lengths))
    logits, k_pages, v_pages, aux = _forward(
        forward_prefill, cfg, conv, slots,
        params, cfg, tokens, lengths, k_pages, v_pages, page_table,
        adapter_idx=adapter_idx,
    )
    keys = _slot_keys(base_key, seeds, lengths)
    row_counts = counts[slots]
    allowed = nxt_all = new_state = None
    if fsm is not None:
        g_rows, init = packed[:, _FSM_PRE], packed[:, _FSM_PRE + 1]
        allowed, nxt_all, _ = _fsm_apply(fsm, g_rows, init)
    res = sample(logits, keys, temps, top_ks, top_ps,
                 penalties=(presence, frequency, row_counts), bias=bias,
                 allowed=allowed)
    if fsm is not None:
        new_state = _fsm_scatter(fsm, g_rows, init, nxt_all, res.tokens,
                                 lengths, slots)
    return (_pack_with_moe_rows(res.host_pack(), aux), res.tokens, k_pages,
            v_pages, counts, new_state, aux and aux.conv)


# packed chunk columns: 0 chunk_len, 1 history, 2 top_k, 3 temps(bits),
# 4 top_p(bits), 5 seed, 6 presence(bits), 7 frequency(bits), 8 slot,
# 9 prompt_len, 10 reset (first chunk of the request — history may be
# nonzero when a cached prefix was adopted), 11 pos_delta (mrope: a
# cache-hit Qwen3-VL remainder replays through this path with rope
# positions shifted by the request's mrope delta), 12 adapter_slot
# (-1 = base), 13-14 fsm (row, init — set only on the FINAL chunk, whose
# sample is the first real token), 15.. logit_bias ids/vals, then
# page_table. Sampling position is the TOTAL length (history + chunk_len)
# so a chunked prompt draws exactly the tokens a one-shot prefill of the
# same prompt would.
_ADP_CHK = 12
_FSM_CHK = 13
_BIAS_CHK = 15
_CHK_COLS = _BIAS_CHK + 2 * LOGIT_BIAS_SLOTS


def _chunk_packed_step(params, cfg, tokens, packed, k_pages, v_pages,
                       counts, base_key, fsm=None, conv=None):
    lengths = packed[:, 0]
    history = packed[:, 1]
    top_ks = packed[:, 2]
    temps = jax.lax.bitcast_convert_type(packed[:, 3], jnp.float32)
    top_ps = jax.lax.bitcast_convert_type(packed[:, 4], jnp.float32)
    seeds = packed[:, 5]
    presence = jax.lax.bitcast_convert_type(packed[:, 6], jnp.float32)
    frequency = jax.lax.bitcast_convert_type(packed[:, 7], jnp.float32)
    slots = packed[:, 8]
    prompt_len = packed[:, 9]
    reset = packed[:, 10]
    pos_delta = packed[:, 11]
    adapter_idx = packed[:, _ADP_CHK]
    bias = _unpack_bias(packed, _BIAS_CHK)
    page_table = packed[:, _CHK_COLS:]

    counts = _rebuild_count_rows(
        counts, tokens, slots, history, prompt_len, lengths, reset)
    logits, k_pages, v_pages, aux = _forward(
        forward_chunk, cfg, conv, slots,
        params, cfg, tokens, history, lengths, k_pages, v_pages, page_table,
        pos_delta=pos_delta, adapter_idx=adapter_idx,
    )
    keys = _slot_keys(base_key, seeds, history + lengths)
    allowed = nxt_all = new_state = None
    if fsm is not None:
        g_rows, init = packed[:, _FSM_CHK], packed[:, _FSM_CHK + 1]
        allowed, nxt_all, _ = _fsm_apply(fsm, g_rows, init)
    res = sample(logits, keys, temps, top_ks, top_ps,
                 penalties=(presence, frequency, counts[slots]), bias=bias,
                 allowed=allowed)
    if fsm is not None:
        new_state = _fsm_scatter(fsm, g_rows, init, nxt_all, res.tokens,
                                 lengths, slots)
    return (_pack_with_moe_rows(res.host_pack(), aux), res.tokens, k_pages,
            v_pages, counts, new_state, aux and aux.conv)


def _spill_gather_pages(k_pages, v_pages, flat_idx):
    """Gather m spilling pages' bytes across all layers for the host tier:
    ``flat_idx`` [m, L] holds each page's flat pool index per layer
    (l * P + page_id). Returns (k [n_kv, m, L, page, d], v, k_scale
    [n_kv, m, L, page] | None, v_scale) — raw pool bytes, so the later
    re-upload round-trips exactly (no requantize, no dtype change)."""
    k = jnp.take(k_pages.data, flat_idx, axis=1)
    v = jnp.take(v_pages.data, flat_idx, axis=1)
    ks = jnp.take(k_pages.scale, flat_idx, axis=1) if k_pages.quantized else None
    vs = jnp.take(v_pages.scale, flat_idx, axis=1) if v_pages.quantized else None
    return k, v, ks, vs


def _upload_scatter_pages(k_pages, v_pages, flat_idx, k, v, ks, vs):
    """Inverse of _spill_gather_pages: splice m host-cached pages back
    into freshly allocated pool pages (pools donated — in place)."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool

    kd = k_pages.data.at[:, flat_idx].set(k)
    vd = v_pages.data.at[:, flat_idx].set(v)
    ksc, vsc = k_pages.scale, v_pages.scale
    if ks is not None:
        ksc = ksc.at[:, flat_idx].set(ks)
        vsc = vsc.at[:, flat_idx].set(vs)
    return KVPool(kd, ksc), KVPool(vd, vsc)


def _start_host_copy(pack) -> None:
    """Begin async device->host transfer of a step's packed result (a
    device array, or a tuple of them for spec steps: (packs, accept))."""
    for arr in pack if isinstance(pack, (tuple, list)) else (pack,):
        arr.copy_to_host_async()


def _lp_entry(host_res, row: int) -> tuple:
    """(logprob, top_ids, top_logprobs) for one row of a HostSample."""
    return (float(host_res.logprobs[row]),
            host_res.top_ids[row].tolist(),
            host_res.top_logprobs[row].tolist())


def _slot_keys(base_key, seeds, lengths):
    """Per-slot PRNG keys: fold(base, request seed, stream position). The
    position is `lengths` — for both prefill and decode it equals the
    sampled token's sequence position, so a preempted-and-resumed request
    draws exactly the tokens it would have drawn uninterrupted."""
    return jax.vmap(
        lambda s, p: jax.random.fold_in(jax.random.fold_in(base_key, s), p)
    )(seeds, lengths)


def _refuse_what_runs_cannot(cfg, ec, mesh, model_dir) -> None:
    """A stack of several kinds of layer (``params["layers"]`` a tuple of
    runs; conv or Mamba layers with per-slot state) is served on one chip
    from seeded weights, by the plain decode window. Every feature that
    reads ``params["layers"]`` as one dict, or that moves KV pages without
    the per-slot state at their boundary, refuses such a model here, at
    start-up, rather than answer wrongly. So does a model with latent attention
    (its seeded tree is a tuple of runs whatever their number, and its
    pool is one latent row a token with no V side), which also refuses an
    int8 pool: the quantized write and the int8 kernels know K and V
    heads."""
    if len(cfg.layer_runs) == 1 and not cfg.is_mla:
        return
    asked = [
        ("a checkpoint (no tensor names of this family are mapped: serve "
         "it with --random-weights)", model_dir is not None),
        ("--quantization", ec.quantization is not None),
        ("a mesh of more than one device (--tp/--ep/--sp > 1)",
         mesh is not None and mesh.size > 1),
        ("multihost", ec.multihost),
        ("LoRA adapters", bool(ec.adapters)),
        ("speculation", ec.speculation is not None),
        ("the host KV tier (kv_host_cache_gb: a spilled page carries no "
         "conv or Mamba state)", bool(ec.kv_host_cache_gb)),
        ("a prefill or decode role (the handoff moves KV pages alone)",
         ec.role not in (None, "both")),
        ("an int8 KV cache (kv_cache_dtype: a latent row has no K and V "
         "heads to scale)", cfg.is_mla and ec.kv_cache_dtype is not None),
    ]
    bad = [what for what, on in asked if on]
    if bad:
        what = ("latent attention over a latent pool" if cfg.is_mla else
                f"a stack of {len(cfg.layer_runs)} runs of layers of "
                f"different kinds")
        raise ValueError(f"{cfg.name}: {what} does not support: "
                         + "; ".join(bad))


class Engine:
    """Multi-request continuous-batching engine for one model."""

    def __init__(
        self,
        engine_config: EngineConfig,
        model_config: Optional[ModelConfig] = None,
        params: Optional[Params] = None,
        mesh=None,
        model_dir: Optional[str] = None,
        weights_preload=None,
    ):
        from llms_on_kubernetes_tpu.ops.quant import SUPPORTED_QUANTIZATIONS

        self.config = engine_config
        if engine_config.quantization not in SUPPORTED_QUANTIZATIONS:
            raise ValueError(
                f"unknown quantization {engine_config.quantization!r} "
                f"(supported: {[q for q in SUPPORTED_QUANTIZATIONS if q]})"
            )
        self.model_config = model_config or get_config(engine_config.model)
        cfg = self.model_config
        _refuse_what_runs_cannot(cfg, engine_config, mesh, model_dir)
        if cfg.attention_summary:
            print(f"[model] {cfg.attention_summary}", file=sys.stderr,
                  flush=True)
        self.mesh = mesh
        from llms_on_kubernetes_tpu.parallel.mesh import (
            AXIS_MODEL, AXIS_SEQ, set_active_mesh,
        )

        if mesh is not None:
            sp = int(mesh.shape.get(AXIS_SEQ, 1))
            bad = [b for b in engine_config.prefill_buckets if b % sp != 0]
            if sp > 1 and bad:
                raise ValueError(
                    f"prefill buckets {bad} not divisible by the seq-parallel "
                    f"ring size {sp} (ring attention shards the bucket)"
                )
            if sp > 1 and engine_config.num_pages % sp != 0:
                # the flat pool axis shards over seq at page granularity
                # (ops/cp.py): each page's L layer slots stay on one device
                raise ValueError(
                    f"num_pages={engine_config.num_pages} not divisible by "
                    f"the seq-parallel ring size {sp} (the page pool is "
                    f"context-sharded)"
                )
        # ring-attention dispatch reads this at trace time; ALWAYS set it
        # (including to None) so a previous engine's mesh never leaks into
        # this engine's traces
        set_active_mesh(mesh)

        if params is not None:
            self.params = params
        elif model_dir is not None:
            from llms_on_kubernetes_tpu.engine.weights import load_hf_params
            self.params = load_hf_params(
                cfg, model_dir, mesh=mesh, dtype=engine_config.dtype,
                quantization=engine_config.quantization,
                preload=weights_preload,
            )
        else:  # random weights (tests / benchmarks / chip_smoke.py)
            if engine_config.quantization is not None:
                # random weights have no checkpoint format: every
                # quantization mode serves weight-only int8, generated AS
                # int8 — a 7B model's bf16 tree (14.5 GB) would not fit
                # the 16 GB chip it is about to be quantized for
                from llms_on_kubernetes_tpu.ops.quant import (
                    random_quantized_params,
                )
                self.params = random_quantized_params(
                    cfg, engine_config.seed, dtype=engine_config.dtype)
            else:
                self.params = init_params(
                    cfg, jax.random.key(engine_config.seed),
                    dtype=engine_config.dtype)
            if mesh is not None and len(cfg.layer_runs) == 1 \
                    and not cfg.is_mla:
                # (a stack of several runs is served on one device, where
                # there is nothing to shard: _refuse_what_runs_cannot)
                from llms_on_kubernetes_tpu.parallel.sharding import shard_params
                self.params = shard_params(self.params, cfg, mesh)

        cache_heads, cache_width = cfg.cache_row
        self.cache_config = CacheConfig(
            num_layers=cfg.num_attn_layers,
            num_kv_heads=cache_heads,
            head_dim=cache_width,
            latent=cfg.is_mla,
            num_pages=engine_config.num_pages,
            page_size=engine_config.page_size,
            pages_per_slot=engine_config.pages_per_slot,
            dtype=engine_config.dtype,
            kv_dtype=engine_config.kv_cache_dtype,
            model_shards=(int(mesh.shape[AXIS_MODEL])
                          if mesh is not None else 1),
        )
        sharding = None
        if mesh is not None:
            # each device allocates only its own shard: both whole pools
            # on device 0 would not fit beside its share of the weights
            from llms_on_kubernetes_tpu.parallel.sharding import pool_sharding
            sharding = pool_sharding(cfg, mesh)
        self.k_pages, self.v_pages = init_pages(self.cache_config, sharding)
        # the conv or Mamba layers' per-slot state, ONE object beside the
        # pools and donated through every step like them (an array, or a
        # decoder.MambaState); None for a model without such layers
        self.conv_state = init_conv_state(
            cfg, engine_config.max_decode_slots, engine_config.dtype)
        # a Mamba model's token step visits the rows live at the launch
        # where its state-space step is the kernel, every slot where it is
        # the XLA step: what llm_ssm_positions_total{path="decode"} books
        self._ssm_live_only = False
        if cfg.num_mamba_layers:
            from llms_on_kubernetes_tpu.ops.attention import ssm_step_mode

            self._ssm_live_only = ssm_step_mode(
                self.conv_state.ssm)[0] is not None
        if (engine_config.kv_cache_dtype == "int8"
                and engine_config.page_size % 128 != 0
                and jax.default_backend() == "tpu"):
            import logging
            logging.getLogger(__name__).warning(
                "kv_cache_dtype=int8 with page_size=%d: the Pallas int8 "
                "decode kernel needs a 128-multiple page size (Mosaic lane "
                "tiling); decode attention falls back to the slower XLA "
                "gather path", engine_config.page_size)

        B = engine_config.max_decode_slots
        self.allocator = PageAllocator(
            engine_config.num_pages, engine_config.page_size, B,
            engine_config.pages_per_slot,
            prefix_caching=engine_config.prefix_caching,
        )
        self.slots: list[Optional[Request]] = [None] * B
        self.slot_len = np.zeros((B,), np.int64)  # tokens whose KV is cached
        # host-RAM offload tier: finished/preempted slots spill full pages
        # here; a returning session re-uploads them and skips straight to
        # decode (engine/cache.HostKVCache). Requires prefix caching — the
        # allocator's digest chain is the addressing scheme for both tiers.
        # (disabled under multihost: uploads mutate the pools outside the
        # broadcast protocol, so follower pods would silently diverge)
        self.host_kv: Optional[HostKVCache] = None
        if (engine_config.kv_host_cache_gb > 0
                and engine_config.prefix_caching
                and not engine_config.multihost):
            self.host_kv = HostKVCache(
                int(engine_config.kv_host_cache_gb * (1 << 30)),
                engine_config.page_size)
        # spills in flight: [(tenant, [digests], device gather)] — the
        # device->host copy is dispatched at free/preempt time but the
        # blocking np.asarray read happens at the next admission probe
        # (_drain_spills), keeping it off the decode hot path
        self._pending_spills: list = []
        # per-slot host-tier adoption staged between the admission probe
        # and its commit/rollback: (matched digests, payloads)
        self._host_adopt: dict = {}
        self.kv_upload_obs: "collections.deque[float]" = collections.deque(
            maxlen=4096)  # seconds per host->device page upload batch
        self.kv_uploaded_tokens = 0  # tokens whose re-prefill was skipped
        self._spill_gather = jax.jit(_spill_gather_pages)
        self._upload_scatter = jax.jit(_upload_scatter_pages,
                                       donate_argnums=(0, 1))
        # per-tenant fair admission (engine/qos.py): priority classes +
        # deficit round-robin keyed by Request.tenant; deque-compatible
        # for every scheduler call site (peek/popleft/appendleft/...)
        self.waiting: TenantFairQueue = TenantFairQueue(
            weights=dict(engine_config.qos_weights),
            default_weight=engine_config.qos_default_weight,
            starvation_s=engine_config.qos_starvation_s,
        )
        # per-tenant admission accounting, drained by the serving loop
        # into llm_tenant_* series: total admitted per (tenant, priority)
        # and (tenant, queue-wait seconds, priority) observations
        self.tenant_admitted: "collections.Counter" = collections.Counter()
        self.tenant_wait_obs: "collections.deque" = collections.deque(
            maxlen=4096)
        self._key = jax.random.key(engine_config.seed)
        self._id_counter = iter(range(2 ** 62))
        self._seed_rng = np.random.default_rng(engine_config.seed)
        self._lock = threading.Lock()
        self.preemptions = 0  # total KV-pressure preemptions (metrics)
        # admissions that could have adopted a cached prefix and did not,
        # by reason; drained into llm_prefix_reuse_skipped_total{why}
        self.prefix_reuse_skipped = {"recurrent_state": 0}
        # what the expert layers did, by kind of dispatch, booked where a
        # dispatch's tokens are read (_book_moe); drained into llm_moe_*
        from llms_on_kubernetes_tpu.engine.ledger import MOE_STATS

        self.moe_stats = {kind: dict.fromkeys(MOE_STATS, 0.0)
                          for kind in ("prefill", "chunk", "decode")}
        self.moe_last: Optional[dict] = None   # /debug/engine "experts"
        # prompt tokens each attention path took (a bucket's own rows, or
        # a chunk over cached ones); with decode_tokens, what
        # llm_mla_tokens_total{path} says of a latent model
        self.path_tokens = {"prefill": 0, "chunk": 0}
        # positions the Mamba layers' scan (prefill, chunk) or step
        # (decode) ran over, padding included, and idle rows where the
        # step visits them (_ssm_rows), as _dispatch is told them:
        # llm_ssm_positions_total{path}. path_tokens and decode_tokens are
        # the real ones among them
        self.ssm_positions = {"prefill": 0, "chunk": 0, "decode": 0}
        # rows of cached keys and values the window layers HOLD ("cached")
        # and can still READ ("reached": the last sliding_window of them),
        # summed over every planned token step of every live row, from the
        # lengths the scheduler holds when it plans a decode window:
        # llm_attn_window_rows_total{rows}. The pool keeps every token of
        # every layer while its sequence lives, so cached - reached is
        # what a cache that held window layers at their window would give
        # back. 0 for a model without window layers
        self.window_rows = {"cached": 0, "reached": 0}
        # fused multi-step decode accounting (metrics + bench):
        self.decode_dispatches = 0   # decode device dispatches
        self.decode_tokens = 0       # tokens committed to streams by decode
        self.early_exit_steps = 0    # planned row-steps wasted mid-window
        # speculative decoding accounting (metrics + bench): drafted /
        # accepted count DRAFT tokens only (the bonus token is ordinary
        # decode output), so accepted/drafted is the pure draft hit-rate
        self.spec_dispatches = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        # per-dispatch consumed window depth; drained by the serving
        # loop into the llm_decode_steps_per_dispatch histogram
        self.steps_obs: "collections.deque[int]" = collections.deque(
            maxlen=4096)
        # seconds the ENGINE thread spent blocked on device reads (sync
        # path); async-path device waits land on the harvester thread's
        # own counter — device_wait_s() sums both for step attribution
        self._device_time_s = 0.0
        # goodput ledger: chip-time attribution + MFU/MBU + step-time
        # anomaly detection (engine/ledger.py); None = accounting off
        self.ledger = None
        if engine_config.ledger:
            import os

            from llms_on_kubernetes_tpu.engine.ledger import (
                GoodputLedger, StepAnomalyDetector,
            )

            det = None
            if engine_config.anomaly_profile:
                det = StepAnomalyDetector(
                    threshold=engine_config.anomaly_z,
                    cooldown_s=engine_config.anomaly_cooldown_s,
                    sustain=int(os.environ.get("LLMK_ANOMALY_SUSTAIN", "3")),
                    warmup=int(os.environ.get("LLMK_ANOMALY_WARMUP", "12")),
                )
            self.ledger = GoodputLedger(cfg, detector=det)
            # a dispatch during which the process compiled, or fetched an
            # executable from its persistent cache, re-traced its step
            jit_events.install()
        # every dispatch launched and not yet booked, in launch order, and
        # the device time each shape last took: the ledger's own records
        # where it is on, the bare timeline where it is off
        self.timeline = self.ledger
        if self.timeline is None:
            from llms_on_kubernetes_tpu.engine.ledger import DispatchTimeline

            self.timeline = DispatchTimeline()
        # the clock launches are stamped and timed on (a test's to replace,
        # with the harvester whose completions it stamps)
        self._clock = time.monotonic
        # one number per device dispatch, in launch order: the ledger's
        # record, the seq of its llmk.dispatch trace annotation and (for a
        # prefill, as -1 - seq) the key of its first-token read
        self._dispatch_seq = itertools.count()
        # step() found the engine without work since the last launch: the
        # next dispatch's idle gap is nobody's fault
        self._saw_no_work = False

        # the conv state (the last argument; None for most models) is
        # donated through every step like the pools
        self._prefill_packed = jax.jit(
            _prefill_packed_step, static_argnums=(1,),
            donate_argnums=(4, 5, 6, 9)
        )
        self._decode_multi = jax.jit(
            _decode_multi_packed_step, static_argnums=(1, 2),
            donate_argnums=(6, 7, 8, 11)
        )
        self._decode_spec = jax.jit(
            _decode_spec_packed_step, static_argnums=(1, 2),
            donate_argnums=(4, 5, 6)
        )
        self._chunk_packed = jax.jit(
            _chunk_packed_step, static_argnums=(1,),
            donate_argnums=(4, 5, 6, 9)
        )
        if cfg.vision is not None:
            from llms_on_kubernetes_tpu.models.vision import (
                encode_images, encode_images_qwen3vl, encode_video_qwen3vl,
            )

            self._mm_prefill_packed = jax.jit(
                _prefill_mm_packed_step, static_argnums=(1,),
                donate_argnums=(7, 8, 9))
            qwen = cfg.vision.family == "qwen3vl"
            enc = encode_images_qwen3vl if qwen else encode_images
            self._encode_images = jax.jit(enc, static_argnums=(1,))
            if qwen:
                self._encode_video = jax.jit(encode_video_qwen3vl,
                                             static_argnums=(1,))
        # per-slot OUTPUT-token counts for presence/frequency penalties;
        # donated through every step like the page pools
        self.token_counts = jnp.zeros((B, cfg.vocab_size), jnp.int32)

        # multi-host: every device call is announced in one packed broadcast
        # (engine/multihost.py). Async scheduling works across hosts — the
        # decode merge consumes device-resident tokens, so followers never
        # need host values.
        if engine_config.multihost:
            from llms_on_kubernetes_tpu.engine.multihost import ProtoShapes

            self._mh_shapes = ProtoShapes.from_engine_config(
                engine_config, self.model_config)

        # async scheduling state (see EngineConfig.async_scheduling)
        self._async = bool(engine_config.async_scheduling)
        self._inflight: "collections.deque[InflightStep]" = collections.deque()
        # (request, harvester key, row) awaiting a first-token read; row -1:
        # the read of a chunk that is not its chain's last, which carries
        # no token and is made for its completion time alone
        self._pending_first: list[tuple[Request, int, int]] = []
        # harvester key of a first-token read -> (kind of its dispatch, its
        # sample rows): what _book_moe needs when the read is consumed
        self._first_reads: dict = {}
        # the one chunked prompt under way (ChunkChain), or None
        self._chain: Optional[ChunkChain] = None
        self._seq_counter = iter(range(2 ** 62))     # decode steps (dense)
        # set by submit(): breaks the backpressure wait so admission (and
        # the new request's prefill dispatch) never waits out a read
        self._admit_wake = threading.Event()
        # first tokens handed to their requests, by who did it (_hand_over);
        # the serving loop drains it into llm_first_tokens_total{delivered}
        self.first_tokens_handed = {"backpressure": 0, "step": 0}
        # seconds from a decode window's completion on the device to the
        # hand-over of its events at the end of the step() that collected
        # it, summed by the window's kind (llm_decode_emit_seconds_total
        # {kind}; per window: over llm_dispatches_total of that kind).
        # _collected: (kind, completion) of the windows collected since
        # the last hand-over, which ONE reading of the clock prices; kept
        # with the ledger alone, whose drain is the counter's only reader
        self.decode_emit_s = {"decode": 0.0, "spec": 0.0}
        self._collected: list[tuple[str, float]] = []
        self._harvester: Optional[_Harvester] = None
        if self._async:
            self._harvester = _Harvester()
            self._harvester.start()
            import weakref
            weakref.finalize(self, self._harvester.stop)
        # what a decode step is passed for a token input no row reads
        # (nothing in flight; no admission): the newest real one, so that
        # the step is traced for ONE sharding annotation of each. With
        # zeros (uncommitted) every combination was a cache entry of its
        # own, and one that the traffic before it never reached was
        # traced and lowered under a request (17-20 s at 32 layers).
        # A follower keeps the same two (multihost.follower_loop): the
        # newest decode step's tokens and the newest prefill's. Zeros
        # until the first of each
        self._unread_toks = jnp.zeros((B,), jnp.int32)
        self._unread_prefill_toks = jnp.zeros((1,), jnp.int32)
        # decode-row template cache (PR 3, profile-guided): the decode
        # packed array is mostly request-STATIC sampling columns, and
        # rebuilding every one of them per step in a Python loop (plus
        # _pack_bias over the whole bias block) was the top non-kernel
        # slice of the decode step. The static columns are written once
        # per slot OCCUPANCY into this template; each step memcpys it and
        # fills only the dynamic columns (_dec_template below).
        self._dec_rows = np.zeros(
            (B, _DEC_COLS + engine_config.pages_per_slot), np.int32)
        self._dec_rows[:, 1] = 1                               # src: host
        self._dec_rows[:, 5] = np.float32(1.0).view(np.int32)  # top_p off
        self._dec_rows[:, _ADP_DEC] = -1                       # base model
        self._dec_rows[:, _FSM_DEC] = -1                       # no grammar
        self._dec_rows[:, _STOP_DEC:_STOP_DEC + STOP_SLOTS] = -1  # no stops
        self._dec_row_owner: list = [None] * B
        # grammar-constrained decoding: resident-grammar registry + device
        # tables, created lazily on the first constrained admission
        # (engine/grammar.py; _ensure_grammar/_fsm_args below)
        self._g_resident: dict = {}      # key -> [row, base, size, refs, g]
        self._fsm_state = None           # device [B] int32
        self._g_class_h = None           # host [G, vocab] int16
        self._g_trans_h = None           # host [S_cap, C_cap] int16
        self._g_dev = None               # (class_of, trans) device arrays
        # the device step time, measured from harvest completion spacing
        # (in steady state the loop is device-paced): the watchdog's
        # budget and the API's Retry-After read it
        self._est_step = 0.02
        self._last_harvest_t: Optional[float] = None
        # how long before the device runs dry a steady-state decode step
        # is launched (_decode_due): what a launch was seen to need, from
        # the moment aimed at to the enqueue returning (the thread waking
        # late, the step() around it, packing, the jitted call) and by
        # how much the device was free sooner than estimated. Tracked from
        # ABOVE (_note_launch), the estimates' rule mirrored: both err
        # towards launching early
        self._lead = _LEAD_FLOOR_S
        # decode steps launched, by the rule that launched each; the
        # serving loop drains it into llm_decode_launches_total{when}
        self.decode_launches = dict.fromkeys(DECODE_LAUNCH_RULES, 0)
        # decode windows launched, by what their rows asked of the sampler
        # (_book_sampler); drained into llm_decode_windows_total{sampler}
        self.decode_windows = dict.fromkeys(SAMPLERS, 0)
        # watchdog: set by _shed_wedged() when a device step exceeded the
        # stall budget; a wedged engine rejects submissions (the server
        # flips readiness and a restart is the only recovery)
        self.wedged = False
        # prompt scoring (echo+logprobs): wrapper built eagerly — jit()
        # itself is free, compilation is per-shape on first use, and an
        # unsynchronized lazy init would let concurrent requests each pay
        # a duplicate compile through their own wrapper
        from llms_on_kubernetes_tpu.models.decoder import forward_score

        self._score_jit = jax.jit(forward_score, static_argnums=(1, 4))

        # multi-tenant LoRA: attach zeroed per-target LoRAStacks to the
        # params and build the slot manager. With no adapters configured
        # the stacks are never created and every trace above stays the
        # byte-identical pre-LoRA program.
        self._adapters = None
        if engine_config.adapters:
            self._init_adapters()

        # speculative decoding (engine/speculation.py): built only when
        # configured AND structurally possible — the async fused window is
        # the substrate (drafts ride _BUD_DEC rows), so sync scheduling or
        # K=1 (incl. the multihost clamp) leaves self._spec = None and the
        # engine byte-identical to the pre-speculation program
        self._spec = None
        if (engine_config.speculation is not None and self._async
                and engine_config.decode_steps > 1):
            from llms_on_kubernetes_tpu.engine.speculation import (
                build_speculator,
            )
            self._spec = build_speculator(engine_config, self.model_config)

    # ------------------------------------------------------------------
    # multi-tenant LoRA (engine/adapters.py, ops/lora.py)
    # ------------------------------------------------------------------

    @property
    def adapters(self):
        """The AdapterManager, or None on an adapter-free engine."""
        return self._adapters

    def _init_adapters(self) -> None:
        from llms_on_kubernetes_tpu.engine.adapters import (
            AdapterManager, load_adapter,
        )
        from llms_on_kubernetes_tpu.ops.lora import lora_zeros

        cfg = self.model_config
        ec = self.config
        if ec.multihost:
            # follower pods replay packed steps against their own params
            # copy and have no upload path for slot residency changes
            raise ValueError(
                "multi-tenant LoRA adapters are not supported with "
                "multihost=true")
        if cfg.num_experts and any(
                t.startswith("w_") for t in ec.adapter_targets):
            raise ValueError(
                f"adapter_targets {ec.adapter_targets} include MLP "
                f"projections, but {cfg.name!r} is MoE (per-expert LoRA "
                f"is not supported); use attention-only targets")
        D, F = cfg.hidden_size, cfg.intermediate_size
        H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        # (in_shape, out_shape) in the decoder's einsum layouts
        shapes = {
            "wq": ((D,), (H, hd)), "wk": ((D,), (KV, hd)),
            "wv": ((D,), (KV, hd)), "wo": ((H, hd), (D,)),
            "w_gate": ((D,), (F,)), "w_up": ((D,), (F,)),
            "w_down": ((F,), (D,)),
        }
        S, r = ec.adapter_slots, ec.adapter_rank
        for t in ec.adapter_targets:
            if t not in shapes:
                raise ValueError(f"unknown adapter target {t!r} "
                                 f"(supported: {sorted(shapes)})")
            stack = lora_zeros(cfg.num_layers, S, *shapes[t], r)
            if self.mesh is not None:
                from llms_on_kubernetes_tpu.parallel.sharding import (
                    shard_lora_stack,
                )
                stack = shard_lora_stack(stack, self.mesh)
            self.params["layers"]["lora_" + t] = stack

        def _load(name: str, ref: str):
            from llms_on_kubernetes_tpu.engine.hub import ensure_adapter_dir

            return load_adapter(name, ensure_adapter_dir(ref), cfg, r,
                                targets=ec.adapter_targets)

        self._adapters = AdapterManager(
            dict(ec.adapters), S, _load, self._upload_adapter)

    def _upload_adapter(self, slot: int, loaded) -> None:
        """Copy one adapter's factors into device slot ``slot`` of every
        target stack (zeroing targets it doesn't train). Safe while steps
        are in flight: params is a non-donated jit argument, so dispatched
        steps hold the previous buffers by value."""
        from llms_on_kubernetes_tpu.ops.lora import LoRAStack

        layers = self.params["layers"]
        for t in self.config.adapter_targets:
            stack = layers["lora_" + t]
            fac = loaded.factors.get(t)
            if fac is None:
                a = stack.a.at[:, slot].set(0.0)
                b = stack.b.at[:, slot].set(0.0)
            else:
                a = stack.a.at[:, slot].set(jnp.asarray(fac[0]))
                b = stack.b.at[:, slot].set(jnp.asarray(fac[1]))
            layers["lora_" + t] = LoRAStack(a, b, rank_axis=stack.rank_axis)

    def _ensure_adapter(self, req: "Request") -> bool:
        """Pin ``req``'s adapter into a device slot; False = every slot is
        pinned by running requests — the caller waits, like page pressure."""
        if req.adapter is None or req.adapter_slot >= 0:
            return True
        slot = self._adapters.acquire(req.adapter)
        if slot is None:
            return False
        req.adapter_slot = slot
        return True

    def _release_adapter(self, req: "Request") -> None:
        if req.adapter_slot >= 0 and self._adapters is not None:
            self._adapters.release(req.adapter_slot)
            req.adapter_slot = -1

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def submit(
        self,
        prompt: list[int],
        params: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        on_event=None,
        images=None,
        deadline: Optional[float] = None,
        adapter: Optional[str] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
        handoff: bool = False,
    ) -> Request:
        if self.wedged:
            raise EngineStallError(
                "engine wedged: a device step stalled past the watchdog "
                "budget; restart the server to recover")
        params = params or SamplingParams()
        max_len = self.config.max_model_len
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if adapter is not None and (
                self._adapters is None or not self._adapters.known(adapter)):
            raise UnknownAdapterError(
                f"adapter {adapter!r} is not served by this engine "
                f"(configured: {self._adapters.names() if self._adapters else []})")
        if images is not None:
            # normalize to a LIST of float32 arrays — [H, W, C] = an
            # image, [F, H, W, C] = a VIDEO's frames (Qwen3-VL; F frames
            # in temporal-patch multiples). Dynamic resolution allows
            # per-entry grids, so one request may mix shapes and kinds.
            images = [np.asarray(im, np.float32) for im in images]
            params = self._validate_images(prompt, params, images)
        if params.top_k > MAX_CANDIDATES:
            raise ValueError(
                f"top_k={params.top_k} exceeds the sampling candidate pool "
                f"({MAX_CANDIDATES}); values above it are not supported"
            )
        if params.grammar is not None:
            g = params.grammar
            if (g.n_states > self.config.grammar_states
                    or g.n_classes > self.config.grammar_classes):
                raise ValueError(
                    f"grammar needs {g.n_states} states / {g.n_classes} "
                    f"token classes; this engine's device-table caps are "
                    f"{self.config.grammar_states} / "
                    f"{self.config.grammar_classes}")
            if len(g.class_of) > self.model_config.vocab_size:
                raise ValueError(
                    f"grammar was compiled for a {len(g.class_of)}-token "
                    f"vocabulary; the model's is "
                    f"{self.model_config.vocab_size}")
        for name in ("presence_penalty", "frequency_penalty"):
            val = getattr(params, name)
            if not -2.0 <= val <= 2.0:
                raise ValueError(f"{name} must be in [-2, 2], got {val}")
        if len(params.logit_bias) > LOGIT_BIAS_SLOTS:
            raise ValueError(
                f"logit_bias supports at most {LOGIT_BIAS_SLOTS} entries, "
                f"got {len(params.logit_bias)}")
        if len(params.stop_token_ids) > STOP_SLOTS:
            # the fused decode window's on-device early-exit mask carries
            # stop ids in STOP_SLOTS packed columns — a hard bound
            raise ValueError(
                f"stop_token_ids supports at most {STOP_SLOTS} entries, "
                f"got {len(params.stop_token_ids)}")
        seen_bias: set[int] = set()
        for tid, _bv in params.logit_bias:
            if not 0 <= tid < self.model_config.vocab_size:
                raise ValueError(
                    f"logit_bias token id {tid} outside the vocabulary "
                    f"(size {self.model_config.vocab_size})")
            if tid in seen_bias:
                # the on-device scatter-ADD would apply duplicates
                # cumulatively, silently diverging from the documented
                # map semantics (unreachable via the API — dict keys are
                # unique — but direct submit()s must not differ)
                raise ValueError(f"logit_bias has duplicate token id {tid}")
            seen_bias.add(tid)
        # prompts longer than the largest prefill bucket are served too:
        # admission splits them into bucket-size chunks against the paged
        # pool (chunked prefill — forward_chunk). The only hard limit is
        # the slot's page capacity below.
        # prompt + 1 sampled token must fit a slot's pages — a prompt that can
        # never be admitted would livelock the whole waiting queue behind it.
        if len(prompt) + 1 > max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens cannot fit max_model_len="
                f"{max_len} (page_size*pages_per_slot) with room to generate"
            )
        if len(prompt) + params.max_tokens > max_len:
            params = dataclasses.replace(
                params, max_tokens=max(1, max_len - len(prompt))
            )
        prefix = list(params.prefix_tokens or ())
        if prefix:
            vocab = self.model_config.vocab_size
            for t in prefix:
                if not isinstance(t, int) or not 0 <= t < vocab:
                    raise ValueError(
                        f"prefix_tokens contains id {t!r} outside the "
                        f"vocabulary (size {vocab})")
            if len(prompt) + len(prefix) + 1 > max_len:
                raise ValueError(
                    f"prompt ({len(prompt)}) + prefix_tokens ({len(prefix)}) "
                    f"cannot fit max_model_len={max_len} with room to "
                    f"generate")
            if len(prefix) >= params.max_tokens:
                raise ValueError(
                    f"prefix_tokens ({len(prefix)}) already meets "
                    f"max_tokens ({params.max_tokens}); nothing left to "
                    f"generate")
        # mask to int32 range: the seed rides in int32 device arrays, and
        # an unchecked 64-bit client seed would OverflowError inside step()
        seed = (params.seed if params.seed is not None
                else int(self._seed_rng.integers(0, 2 ** 31 - 1))) & 0x7FFFFFFF
        # mrope delta is a pure function of the prompt: compute it ONCE at
        # submit so a cache-hit admission (which skips the mm prefill that
        # used to derive it) still decodes at the right rotary positions
        mrope_delta = 0
        if images is not None and self.model_config.mrope_section is not None:
            from llms_on_kubernetes_tpu.models.vision import qwen_mrope_positions

            _, mrope_delta = qwen_mrope_positions(
                list(prompt), self.model_config.image_token_id,
                self.model_config.vision.mm_tokens_per_image,
                grids=self._mm_grids(images))
        # QoS identity: the fair-queue bucket ("" = shared default) and the
        # priority class, resolved submit arg > per-tenant config > default
        tenant = str(tenant) if tenant else ""
        if priority is None:
            priority = dict(self.config.qos_priorities).get(
                tenant, self.config.qos_default_priority)
        priority = normalize_priority(
            priority, self.config.qos_default_priority)
        req = Request(
            id=request_id or f"req-{next(self._id_counter)}",
            prompt=list(prompt), params=params, seed=seed, images=images,
            mrope_delta=mrope_delta,
            cache_salt=self._cache_salt_for(images),
            deadline=deadline, adapter=adapter,
            tenant=tenant, priority=priority, handoff=handoff,
            # a non-empty output at submit makes admission take the
            # resumed re-prefill path (prompt + output), continuing the
            # stream exactly where the prefix left off; logprob data for
            # prefix tokens was generated elsewhere and is unrecoverable
            output=prefix, output_logprobs=[None] * len(prefix),
            on_event=on_event,  # attached BEFORE queueing: no missed events
        )
        with self._lock:
            if len(self.waiting) >= self.config.max_waiting:
                raise QueueFullError(
                    f"waiting queue is full ({self.config.max_waiting} "
                    f"requests); retry later"
                )
            self.waiting.append(req)
        if self._harvester is not None:
            self._admit_wake.set()
            self._harvester.poke()  # break any backpressure wait: admit NOW
        return req

    def _validate_images(self, prompt: list[int],
                         params: SamplingParams, images) -> SamplingParams:
        """Multimodal admission contract: the prompt's image-soft-token
        count must match the images, the whole prompt must fit one prefill
        bucket (the chunk path has no embedding substitution), and
        generation is capped so a preempted resume re-prefills in-bucket."""
        cfg = self.model_config
        if cfg.vision is None:
            raise ValueError(
                f"model {cfg.name!r} has no vision tower; images are not "
                f"supported")
        v = cfg.vision
        S2 = (v.image_size // v.patch_size) ** 2
        total_chunks = 0  # images count 1; videos F/temporal_patch_size
        for im in images:
            if im.ndim == 4:  # video frames [F, H, W, C]
                if v.family != "qwen3vl":
                    raise ValueError(
                        f"model {cfg.name!r} does not accept video input")
                F = im.shape[0]
                if F < v.temporal_patch_size or F % v.temporal_patch_size:
                    raise ValueError(
                        f"video frame count {F} must be a positive "
                        f"multiple of {v.temporal_patch_size}")
                frame, chunks = im[0], F // v.temporal_patch_size
            elif im.ndim == 3:
                frame, chunks = im, 1
            else:
                raise ValueError(
                    f"each entry must be an [H, W, C] image or an "
                    f"[F, H, W, C] video; got {tuple(im.shape)}")
            total_chunks += chunks
            if frame.shape[-1] != v.num_channels:
                raise ValueError(
                    f"images need {v.num_channels} channels; got "
                    f"{tuple(im.shape)}")
            sh = frame.shape[0] // v.patch_size
            sw = frame.shape[1] // v.patch_size
            if v.family == "qwen3vl":
                # dynamic resolution: any grid with the fixed patch budget
                # whose sides divide into merge blocks (the preprocessor
                # only produces these; validate so raw submit()s get 400s)
                m = v.spatial_merge_size
                if (sh * v.patch_size != frame.shape[0]
                        or sw * v.patch_size != frame.shape[1]
                        or sh % m or sw % m or sh * sw != S2):
                    raise ValueError(
                        f"image {frame.shape[0]}x{frame.shape[1]} is not an "
                        f"allowed dynamic-resolution grid ({S2} patches, "
                        f"sides divisible by {m * v.patch_size})")
            elif frame.shape[:2] != (v.image_size, v.image_size):
                raise ValueError(
                    f"{cfg.name} images must be {v.image_size}x"
                    f"{v.image_size}; got {frame.shape[0]}x{frame.shape[1]}")
        if total_chunks < 1 or total_chunks > self.config.max_images_per_request:
            raise ValueError(
                f"{total_chunks} image/frame blocks; this engine serves 1.."
                f"{self.config.max_images_per_request} per request (a video "
                f"counts one block per {v.temporal_patch_size} frames)")
        t_img = cfg.vision.mm_tokens_per_image
        soft = sum(1 for t in prompt if t == cfg.image_token_id)
        if soft != total_chunks * t_img:
            raise ValueError(
                f"prompt has {soft} image soft tokens; {total_chunks} "
                f"image/frame blocks need {total_chunks * t_img}")
        # soft tokens must form contiguous runs of exactly t_img (the
        # substitution/positions math assumes it; validating HERE keeps a
        # malformed prompt a 400, not an engine-thread exception later)
        i = 0
        while i < len(prompt):
            if prompt[i] == cfg.image_token_id:
                run = 0
                while (i < len(prompt)
                       and prompt[i] == cfg.image_token_id):
                    run += 1
                    i += 1
                if run != t_img:
                    raise ValueError(
                        f"image soft tokens must form runs of exactly "
                        f"{t_img}; found a run of {run}")
            else:
                i += 1
        bucket = max(self.config.prefill_buckets)
        if len(prompt) > bucket:
            raise ValueError(
                f"multimodal prompt of {len(prompt)} tokens exceeds the "
                f"largest prefill bucket ({bucket})")
        # keep prompt+output re-prefillable in one bucket after preemption
        if len(prompt) + params.max_tokens - 1 > bucket:
            params = dataclasses.replace(
                params, max_tokens=max(1, bucket - len(prompt) + 1))
        return params

    def has_work(self) -> bool:
        return (bool(self.waiting) or any(r is not None for r in self.slots)
                or bool(self._inflight) or bool(self._pending_first))

    # ------------------------------------------------------------------
    # scheduler iteration
    # ------------------------------------------------------------------

    def device_wait_s(self) -> float:
        """Cumulative seconds spent blocked on device work across the
        engine thread (sync reads) and the harvester (async reads). The
        serving loop differences this around each step() to attribute
        step wall time kernel-vs-host for the flight recorder."""
        total = self._device_time_s
        if self._harvester is not None:
            total += self._harvester.device_time_s
        return total

    def step(self) -> list[StepEvent]:
        # re-assert THIS engine's mesh for any trace this step triggers:
        # the active-mesh context is process-global, and constructing
        # another Engine (tests, rolling restarts) between our __init__
        # and our first trace would otherwise leak ITS mesh into OUR
        # executables (observed: a CP engine traced mesh-less)
        from llms_on_kubernetes_tpu.parallel.mesh import set_active_mesh

        set_active_mesh(self.mesh)
        events: list[StepEvent] = []
        if self.wedged:
            # nothing left to drive; reap anything that slipped in between
            # the wedge and the server's readiness flip
            events += self._reap_aborted()
            for ev in events:
                self._hand_over(ev)
            return events
        events += self._reap_aborted()
        if self._async:
            try:
                with _phase("llmk.admit"):
                    admitted = self._admit_async(events)
                with _phase("llmk.pack"):
                    status = self._launch_decode_async(admitted, events)
                # whatever the launch has to wait for (room in the
                # pipeline; the moment a step is due, "early"; device work
                # completing, "paced") is waited for in _harvest, where a
                # submission or a landed first token ends the wait
                with _phase("llmk.harvest"):
                    events += self._harvest(drain=status == "idle",
                                            paced=status == "paced")
            except EngineStallError as e:
                events += self._shed_wedged(str(e))
        else:
            with _phase("llmk.admit"):
                events += self._admit_one()
            with _phase("llmk.pack"):
                events += self._decode_once()
        with _phase("llmk.emit"):
            for ev in events:
                self._hand_over(ev)
            self._book_emit()
        if not self.has_work():
            self._saw_no_work = True
        return events

    def _book_emit(self) -> None:
        """The windows collected since the last call have just had their
        events handed over: book how long each waited for that since it
        was complete on the device (span ``decode.emit`` is the same lag
        for a request's last window, ``llmk.emit`` the phase's own time on
        the profiler's clock)."""
        if not self._collected:
            return
        now = self._clock()
        for kind, done in self._collected:
            self.decode_emit_s[kind] += max(0.0, now - done)
        self._collected.clear()

    def _hand_over(self, ev: StepEvent, where: str = "step") -> None:
        """Put an event on its request's queue, once. A first token's
        ``first_token_at`` is stamped HERE, where it leaves the engine,
        not where its read was collected: span ``prefill.emit`` ends when
        the client can have the token; and so is ``last_token_at``, with
        the event that finishes the request (span ``decode`` ends there,
        ``decode.emit`` with it). ``where`` says who handed a first
        token over: the backpressure wait of ``_harvest`` ("backpressure")
        or the end of a ``step()`` ("step")."""
        if ev.handed_over:
            return
        ev.handed_over = True
        if ev.first or ev.finished:
            now = self._clock()
            if ev.first:
                ev.request.first_token_at = now
                self.first_tokens_handed[where] += 1
            if ev.finished:
                ev.request.last_token_at = now
        payload = (ev.new_tokens, ev.finished, ev.finish_reason)
        ev.request.events.put(payload)
        if ev.request.on_event is not None:
            ev.request.on_event(payload)

    def abort(self, req: Request, reason: str = "abort") -> None:
        """Request cancellation from any thread (client disconnect, server-side
        stop sequence). The engine thread releases the slot/pages at the start
        of its next step and emits a final finished event."""
        req.abort_reason = reason

    def _reap_aborted(self) -> list[StepEvent]:
        # Deadline sweep first: an expired deadline becomes an abort with
        # reason "timeout", so the shed rides the exact same reap path as
        # client disconnects. Waiting requests are shed here WITHOUT ever
        # being admitted (no prefill burned); slotted requests release
        # their slot/pages at the start of this step.
        now = time.monotonic()
        with self._lock:
            for r in self.waiting:
                if (r.deadline is not None and now >= r.deadline
                        and not r.abort_reason and not r.finished):
                    r.abort_reason = "timeout"
        for r in self.slots:
            if (r is not None and r.deadline is not None
                    and now >= r.deadline
                    and not r.abort_reason and not r.finished):
                r.abort_reason = "timeout"
        events: list[StepEvent] = []
        with self._lock:
            doomed_waiting = [r for r in self.waiting
                              if r.abort_reason and not r.finished]
            for r in doomed_waiting:
                self.waiting.remove(r)
        for r in doomed_waiting:
            events.append(self._finish(r, r.abort_reason))
        for r in list(self.slots):
            if r is not None and r.abort_reason and not r.finished:
                events.append(self._finish(r, r.abort_reason))
        return events

    def _ssm_rows(self, live: int) -> int:
        """Rows a token step's state-space update visits, of which ``live``
        are live at the launch."""
        return live if self._ssm_live_only else len(self.slots)

    @contextlib.contextmanager
    def _dispatch(self, kind: str, name: str, shape: str,
                  rows: Optional[list] = None, positions: int = 0,
                  sampler: str = ""):
        """Around the jitted call(s) of ONE device dispatch: opens its
        ledger record, puts ``llmk.dispatch`` with its kind and seq on the
        profiler's timeline, and on the way out stamps the launch (the
        work is enqueued) with the host time the call took and whether the
        process re-traced meanwhile. Yields the dispatch's seq.
        ``positions``: how many positions every layer runs over (rows x
        bucket, or K x the rows a token step visits: ``_ssm_rows``),
        booked for a model with Mamba layers. ``sampler``: a decode
        window's (``_book_sampler``), for its record."""
        seq = next(self._dispatch_seq)
        # without the ledger nothing is attributed to a request (rows) and
        # nothing counts the process's compiles
        led = self.ledger
        events = jit_events.count() if led is not None else 0
        no_work, self._saw_no_work = self._saw_no_work, False
        rec = self.timeline.open(
            seq, kind, name, shape, self._clock(),
            rows=rows if led is not None else None, after_no_work=no_work)
        if self.model_config.num_mamba_layers:
            self.ssm_positions[kind] += positions
            rec.ssm_positions = positions
        rec.sampler = sampler
        try:
            with _phase("llmk.dispatch", kind=kind, seq=seq):
                yield seq
        except BaseException:
            self.timeline.abandon(seq)
            raise
        retraced = led is not None and jit_events.count() != events
        self.timeline.launched(rec, self._clock(), retraced)
        if retraced:
            from llms_on_kubernetes_tpu.server.tracing import jlog

            jlog("dispatch_retraced", kind=kind, step=name, shape=shape,
                 seq=seq, seconds=round(rec.enqueue_ms / 1000.0, 3))

    def _book_sampler(self, packed: np.ndarray) -> str:
        """Count a decode window about to be launched with these packed
        rows under what they ask of the sampler, "plain" or "shaped": the
        executable's own predicate on the same rows (_window_asks), so the
        count says how often a window's token steps skipped the penalty
        counts, the penalties and the bias scatter."""
        sampler = SAMPLERS[int(_window_asks(packed)[1])]
        self.decode_windows[sampler] += 1
        return sampler

    def _count_window_rows(self, first_lengths: dict, plan: dict) -> None:
        """Book a planned decode window's token steps in ``window_rows``:
        row i runs ``plan[i]`` steps at contexts ``first_lengths[i]``,
        + 1, ...: two arithmetic series a row, the second stopping at the
        window."""
        cfg = self.model_config
        layers, window = cfg.num_window_layers, cfg.sliding_window
        if not layers:
            return
        cached = reached = 0
        for i, first in first_lengths.items():
            n = plan.get(i, 0)
            inside = min(n, max(window - first, 0))    # steps under it
            cached += n * first + n * (n - 1) // 2
            reached += (inside * first + inside * (inside - 1) // 2
                        + (n - inside) * window)
        self.window_rows["cached"] += layers * cached
        self.window_rows["reached"] += layers * reached

    @property
    def slot_state_bytes(self) -> int:
        """Device bytes of the per-slot state beside the pools."""
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.conv_state))

    def _mh_send(self, op: int, **fields) -> None:
        """Announce the next device call to follower pods (no-op single-host).
        One packed broadcast per call — see engine/multihost.py."""
        if not self.config.multihost:
            return
        from llms_on_kubernetes_tpu.engine import multihost as mh

        mh.send_message(self._mh_shapes, op, **fields)

    def stop_followers(self) -> None:
        """Tell follower pods to exit their mirror loops (engine shutdown)."""
        if not self.config.multihost:
            return
        from llms_on_kubernetes_tpu.engine import multihost as mh

        from llms_on_kubernetes_tpu.parallel.distributed import is_coordinator

        if is_coordinator():
            mh.send_message(self._mh_shapes, mh.MSG_SHUTDOWN)

    def _pack_prefill_row(self, packed: np.ndarray, row: int, req: Request,
                          n: int, slot: int) -> None:
        packed[row, 0] = n
        packed[row, 1] = req.params.top_k
        packed[row, 2] = np.float32(req.params.temperature).view(np.int32)
        packed[row, 3] = np.float32(req.params.top_p).view(np.int32)
        packed[row, 4] = req.seed
        packed[row, 5] = np.float32(req.params.presence_penalty).view(np.int32)
        packed[row, 6] = np.float32(req.params.frequency_penalty).view(np.int32)
        packed[row, 7] = slot
        packed[row, 8] = len(req.prompt)  # output-token counting boundary
        packed[row, _ADP_PRE] = req.adapter_slot
        # fresh constrained rows start at the grammar's start state;
        # resumed rows (req.output non-empty) sample a DISCARDED token
        # unconstrained and their first decode fsm_sets the replayed state
        if req.fsm_row >= 0 and not req.output:
            packed[row, _FSM_PRE] = req.fsm_row
            packed[row, _FSM_PRE + 1] = req.fsm_start
        else:
            packed[row, _FSM_PRE:_FSM_PRE + 2] = -1
        _pack_bias(packed, row, _BIAS_PRE, req.params)
        packed[row, _PRE_COLS:] = self.allocator.page_tables[slot]

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _bucket_for(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"no prefill bucket fits {n} tokens")

    def _chunked_prefill(self, slot: int, req: Request,
                         prefill_tokens: list[int], led_rows: list,
                         start: int = 0):
        """Prefill a prompt in bucket-size chunks against the paged pool
        (prefill-with-history attention, forward_chunk), beginning at
        position ``start`` (> 0 when a cached prefix was adopted — those
        positions' KV is already in the slot's pages). The slot's pages
        for the WHOLE prompt are already allocated/adopted. Pure dispatch:
        each chunk chains on the previous through the donated page pool —
        no host read here. Returns the FINAL chunk's (packed result,
        device tokens) pair (row 0 is the request's first generated token)
        and the seq of the chain's one dispatch record. The synchronous
        scheduler's: the pipelined one launches a chunk a ``step()``
        (:meth:`_next_chunk`)."""
        n = len(prefill_tokens)
        step = max(self.config.prefill_buckets)
        chunks = -(-(n - start) // step)
        with self._dispatch("chunk", "_chunk_packed_step",
                            f"{chunks}x{self._bucket_for(min(step, n - start))}",
                            led_rows,
                            sum(self._bucket_for(min(step, n - p))
                                for p in range(start, n, step))) as dseq:
            pos = start
            while pos < n:
                pack, toks, m = self._launch_chunk(
                    slot, req, prefill_tokens, pos, start)
                pos += m
        self.slot_len[slot] = n
        return pack, toks, dseq

    def _launch_chunk(self, slot: int, req: Request,
                      prefill_tokens: list[int], pos: int, start: int):
        """Launch the chunk of ``prefill_tokens`` that begins at ``pos``
        (inside a dispatch record). Returns its packed result, its device
        tokens and how many tokens it took."""
        from llms_on_kubernetes_tpu.engine.multihost import MSG_CHUNK

        n = len(prefill_tokens)
        pps = self.allocator.pages_per_slot
        m = min(max(self.config.prefill_buckets), n - pos)
        bucket = self._bucket_for(m)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :m] = prefill_tokens[pos:pos + m]
        packed = np.zeros((1, _CHK_COLS + pps), np.int32)
        packed[0, 0] = m
        packed[0, 1] = pos
        packed[0, 2] = req.params.top_k
        packed[0, 3] = np.float32(req.params.temperature).view(np.int32)
        packed[0, 4] = np.float32(req.params.top_p).view(np.int32)
        packed[0, 5] = req.seed
        packed[0, 6] = np.float32(req.params.presence_penalty).view(np.int32)
        packed[0, 7] = np.float32(req.params.frequency_penalty).view(np.int32)
        packed[0, 8] = slot
        packed[0, 9] = len(req.prompt)
        packed[0, 10] = 1 if pos == start else 0  # first chunk: reset counts
        packed[0, 11] = req.mrope_delta
        packed[0, _ADP_CHK] = req.adapter_slot
        # only the FINAL chunk's sample is the request's first real
        # token; earlier chunks (and every chunk of a resumed
        # request) sample discarded tokens unconstrained
        final = pos + m >= n
        if final and req.fsm_row >= 0 and not req.output:
            packed[0, _FSM_CHK] = req.fsm_row
            packed[0, _FSM_CHK + 1] = req.fsm_start
        else:
            packed[0, _FSM_CHK:_FSM_CHK + 2] = -1
        use_fsm = packed[0, _FSM_CHK] >= 0
        _pack_bias(packed, 0, _BIAS_CHK, req.params)
        packed[0, _CHK_COLS:] = self.allocator.page_tables[slot]
        self._mh_send(MSG_CHUNK, pre_tokens=tokens, pre_packed=packed,
                      fsm_used=use_fsm)
        self.path_tokens["chunk"] += m
        (pack, toks, self.k_pages, self.v_pages, self.token_counts,
         new_state, self.conv_state) = self._chunk_packed(
            self.params, self.model_config, jnp.asarray(tokens),
            jnp.asarray(packed), self.k_pages, self.v_pages,
            self.token_counts, self._key,
            self._fsm_args() if use_fsm else None, self.conv_state,
        )
        if new_state is not None:
            self._fsm_state = new_state
        return pack, toks, m

    def _next_chunk(self) -> dict:
        """Launch the next chunk of the prompt under way, ONE a ``step()``
        and each a dispatch record of its own, so that the decode window
        the step launches behind it (an admission's) runs before the
        chunk after it: a stream waits behind a chunk, not behind a chain
        of them. Each chunk is read, for the time it completed (a record
        nobody reads would take the window behind it into its segment);
        the last one's read carries the first token. Returns the
        admission for the decode launch's merge: no slot before the last
        chunk."""
        ch = self._chain
        ch.yielded = False
        slot, req = ch.slot, ch.req
        n = len(ch.tokens)
        m = min(max(self.config.prefill_buckets), n - ch.pos)
        with self._dispatch("chunk", "_chunk_packed_step",
                            f"1x{self._bucket_for(m)}",
                            [(req, "prefill", m)],
                            self._bucket_for(m)) as dseq:
            pack, toks, m = self._launch_chunk(
                slot, req, ch.tokens, ch.pos, ch.start)
        ch.pos += m
        # what is written so far (a preemption spills no more than that)
        self.slot_len[slot] = ch.pos
        if ch.pos < n:
            key = -1 - dseq
            self._harvester.push(key, pack)
            self._first_reads[key] = ("chunk", 1)
            self._pending_first.append((req, key, -1))
            return {"toks": toks, "slots": {}}
        self._chain = None
        return self._lone_admission(slot, req, ch.resumed, pack, toks, dseq)

    def _lone_admission(self, slot: int, req: Request, resumed: bool,
                        pack, toks, dseq: int) -> dict:
        """The last (or only) dispatch of a prompt that runs alone is
        launched: register its prefix and queue its first-token read.
        Returns the admission for the decode launch's merge."""
        if req.cache_salt is not None:
            self.allocator.register_prefix(slot, req.prompt,
                                           salt=req.cache_salt)
        merge = {"toks": toks, "slots": {}}
        if resumed:
            # no first-token read: the host knows the re-prefill done
            # only when the decode step launched behind it is read
            req.pending_token = req.output[-1]
            merge["slots"][slot] = (True, req.output[-1], 0)
            self.timeline.close(dseq, None)
        else:
            key = -1 - dseq
            self._harvester.push(key, pack)
            self._first_reads[key] = ("chunk", 1)
            merge["slots"][slot] = (False, 0, 0)
            self._pending_first.append((req, key, 0))
        return merge

    def _cache_salt_for(self, images) -> Optional[bytes]:
        """Prefix-cache digest salt, computed ONCE at submit (a blocked
        admission retries every engine iteration — re-hashing megabytes of
        pixels there, under the lock, would stall the scheduler).

        Text requests use the empty salt. Multimodal prompts mix a hash
        of the IMAGE BYTES into the chain (soft tokens share one
        placeholder id, so token-only hashing would alias different
        images); a cache hit is then only usable when it covers the whole
        image region, because the remainder replays through the TEXT
        chunk path (enforced at admission). mrope (Qwen3-VL) prompts are
        cacheable too: the chunk path carries the request's position
        delta (packed col 11), computed at submit."""
        if images is None:
            return b""
        import hashlib

        h = hashlib.sha256()
        for im in images:  # shape first: same bytes at a different grid
            h.update(np.asarray(im.shape, np.int64).tobytes())
            h.update(im.tobytes())
        return h.digest()

    def _adopt_cached_prefix(self, slot: int, req: Request,
                             prefill_tokens: list[int]) -> int:
        """Adopt the longest usable cached prefix for an admission attempt
        (shared by the sync and async paths). A multimodal hit must cover
        every image token — the remainder prefills via forward_chunk,
        which has no embedding substitution — else it is rolled back.

        With the host tier on, the device hit is extended by walking the
        SAME digest chain through ``host_kv`` from where the device map
        stopped; the combined token count is returned so every caller's
        existing logic (can_allocate / commit_adopt / chunk ``start=hit``)
        is tier-agnostic. The probe is pure peek — payload references are
        staged in ``_host_adopt[slot]`` and only ``_host_kv_commit`` (at
        admission commit) uploads pages and touches stats/recency, since
        a blocked admission re-probes every engine iteration."""
        if req.cache_salt is None:
            return 0
        if self.conv_state is not None:
            # a cached page carries keys and values, not the conv or Mamba
            # layers' state at its end: nothing is adopted and the prompt is
            # prefilled whole (_note_admission counts the skipped reuse)
            return 0
        hit = self.allocator.adopt_prefix(
            slot, prefill_tokens[:len(req.prompt)], salt=req.cache_salt)
        combined = hit
        if self.host_kv is not None:
            self._drain_spills()
            page = self.allocator.page_size
            # the host chain runs over the FULL prefill stream (prompt +
            # replayed output for a resume) — spills digest generated
            # tokens too, so a returning session skips those as well.
            # Cap leaves >= 1 token to prefill (its logits seed sampling).
            cap_pages = (len(prefill_tokens) - 1) // page
            start = hit // page
            if cap_pages > start:
                digests = self.allocator._digests(
                    prefill_tokens[:cap_pages * page], salt=req.cache_salt)
                matched, payloads = self.host_kv.match_chain(
                    req.tenant, digests, start)
                # handoff-ingested payloads crossed a network: a corrupt
                # or truncated page is treated as missing (chain stops,
                # remainder re-prefills) rather than crashing the upload
                from llms_on_kubernetes_tpu.engine.cache import \
                    payload_shape_ok
                for i, pl in enumerate(payloads):
                    if not payload_shape_ok(pl, self.cache_config):
                        matched, payloads = matched[:i], payloads[:i]
                        break
                combined = hit + len(matched) * page
                self._host_adopt[slot] = (start, matched, payloads)
        if combined and req.images is not None:
            last_img = max(i for i, t in enumerate(req.prompt)
                           if t == self.model_config.image_token_id)
            if combined <= last_img:
                if hit:
                    self.allocator.rollback_adopt(slot)
                self._host_adopt.pop(slot, None)
                return 0
        return combined

    def _drain_spills(self) -> None:
        """Land pending device->host page copies in the host tier. The
        gathers were DISPATCHED at free/preempt time (device program order
        makes the bytes exactly the committed KV); the blocking host read
        happens here, off the decode hot path."""
        if not self._pending_spills:
            return
        for tenant, digests, (k, v, ks, vs) in self._pending_spills:
            k = np.asarray(jax.device_get(k))
            v = np.asarray(jax.device_get(v))
            ks = None if ks is None else np.asarray(jax.device_get(ks))
            vs = None if vs is None else np.asarray(jax.device_get(vs))
            for j, d in enumerate(digests):
                self.host_kv.put(tenant, d, {
                    "k": k[:, j].copy(), "v": v[:, j].copy(),
                    "ks": None if ks is None else ks[:, j].copy(),
                    "vs": None if vs is None else vs[:, j].copy(),
                })
        self._pending_spills.clear()

    def _spill_slot(self, req: Request) -> None:
        """Queue a finishing/preempted slot's full pages for the host
        tier. Must run BEFORE ``allocator.free`` reuses the pages: the
        gather is dispatched now (device order => it reads this slot's
        committed writes, not a successor's), only the host copy is
        deferred to :meth:`_drain_spills`."""
        if (self.host_kv is None or req.cache_salt is None
                or req.slot < 0):
            return
        slot = req.slot
        page = self.allocator.page_size
        tokens = req.prompt + req.output
        n_full = min(len(tokens), int(self.slot_len[slot])) // page
        if n_full <= 0:
            return
        digests = self.allocator._digests(tokens[:n_full * page],
                                          salt=req.cache_salt)
        pages = self.allocator.slot_pages[slot][:n_full]
        keep = [(d, p) for d, p in zip(digests, pages) if p != 0]
        if not keep:  # page 0 is the never-read trash page: never spilled
            return
        L = self.cache_config.num_layers
        P = self.cache_config.num_pages
        flat = np.asarray([[l * P + p for l in range(L)]
                           for _, p in keep], np.int32)
        gathered = self._spill_gather(self.k_pages, self.v_pages,
                                      jnp.asarray(flat))
        self._pending_spills.append(
            (req.tenant, [d for d, _ in keep], gathered))
        if len(self._pending_spills) > 32:
            self._drain_spills()

    def _host_kv_commit(self, slot: int, req: Request) -> None:
        """An admission with a staged host-tier match landed: upload the
        matched pages into the slot's freshly allocated device pages
        (before the chunk prefill that reads them is dispatched), count
        hits/misses, refresh recency. No-op without a staged probe."""
        staged = self._host_adopt.pop(slot, None)
        if staged is None or self.host_kv is None:
            return
        dev_pages, matched, payloads = staged
        self.host_kv.commit(req.tenant, matched)
        if not payloads:
            return
        t0 = time.perf_counter()
        m = len(payloads)
        pages = self.allocator.slot_pages[slot][dev_pages:dev_pages + m]
        L = self.cache_config.num_layers
        P = self.cache_config.num_pages
        flat = np.asarray([[l * P + p for l in range(L)] for p in pages],
                          np.int32)
        k = np.stack([pl["k"] for pl in payloads], axis=1)
        v = np.stack([pl["v"] for pl in payloads], axis=1)
        quant = payloads[0]["ks"] is not None
        ks = (np.stack([pl["ks"] for pl in payloads], axis=1)
              if quant else None)
        vs = (np.stack([pl["vs"] for pl in payloads], axis=1)
              if quant else None)
        self.k_pages, self.v_pages = self._upload_scatter(
            self.k_pages, self.v_pages, jnp.asarray(flat),
            k, v, ks, vs)
        self.kv_upload_obs.append(time.perf_counter() - t0)
        self.kv_uploaded_tokens += m * self.allocator.page_size

    # ------------------------------------------------------------------
    # disaggregated prefill/decode handoff (openai_api drives these from
    # server threads; HostKVCache is internally locked for exactly this)
    # ------------------------------------------------------------------

    def handoff_digests(self, tokens, salt: bytes = b"") -> "list[bytes]":
        """Chained digests of the FULL pages of ``tokens`` — the handoff
        ticket's addressing of what :meth:`_finish` spilled. Pure hashing
        (no allocator state), safe from any thread."""
        page = self.allocator.page_size
        n_full = len(tokens) // page
        if n_full <= 0:
            return []
        return self.allocator._digests(tokens[:n_full * page], salt=salt)

    def prefix_filter_digests(self) -> "list[bytes]":
        """Every chained page digest currently addressable as cache on
        this replica — device prefix cache plus host tier — feeding the
        /ready bloom-filter advertisement the routers use for cache-aware
        placement. Snapshot semantics, safe from server threads."""
        out = self.allocator.prefix_digests()
        if self.host_kv is not None:
            seen = set(out)
            out.extend(d for d in self.host_kv.digests() if d not in seen)
        return out

    def host_kv_export(self, tenant: str, digests: "list[bytes]") \
            -> "list[Optional[dict]]":
        """Host-tier payloads for a pulling decode replica (None per
        missing page). Empty when the tier is off."""
        if self.host_kv is None:
            return [None] * len(digests)
        return self.host_kv.export(tenant, digests)

    def host_kv_ingest(self, tenant: str, digest: bytes,
                       payload: dict) -> bool:
        """Land one pulled handoff page in the LOCAL host tier so the
        next admission's ``_adopt_cached_prefix`` chain walk finds it.
        Shape/dtype-validated against this engine's pools; a payload that
        does not match is refused (False) and the admission re-prefills
        that page instead — degraded, never wrong bytes."""
        from llms_on_kubernetes_tpu.engine.cache import payload_shape_ok

        if self.host_kv is None or not payload_shape_ok(
                payload, self.cache_config):
            return False
        self.host_kv.put(tenant, digest, payload)
        return True

    def _mm_grids(self, images) -> list[tuple[int, int]]:
        """Per-BLOCK merged grids (rows, cols) in prompt-run order: one
        per image, one per video temporal patch (all a video's blocks
        share its grid)."""
        v = self.model_config.vision
        d = v.patch_size * v.spatial_merge_size
        out = []
        for im in images:
            if im.ndim == 4:
                g = (im.shape[1] // d, im.shape[2] // d)
                out += [g] * (im.shape[0] // v.temporal_patch_size)
            else:
                out.append((im.shape[0] // d, im.shape[1] // d))
        return out

    def _encode_request_images(self, images):
        """Encode each entry through the vision tower (one jitted call per
        entry shape — dynamic resolution means per-entry pixel shapes,
        each compiling once). Video entries yield one embed block per
        temporal patch. Returns (embeds [n_blocks, t_img, D],
        deepstack [n_taps, n_blocks, t_img, D] | None)."""
        cfg = self.model_config
        qwen = cfg.vision.family == "qwen3vl"
        embeds_l, deep_l = [], []
        for im in images:
            if im.ndim == 4:  # video: [T', t_img, D] blocks in one call
                e, d = self._encode_video(self.params["vision"], cfg.vision,
                                          jnp.asarray(im))
                embeds_l += list(e)
                if d is not None:
                    deep_l += [d[:, t] for t in range(d.shape[1])]
                continue
            out = self._encode_images(self.params["vision"], cfg.vision,
                                      jnp.asarray(im)[None])
            if qwen:
                e, d = out
                deep_l.append(None if d is None else d[:, 0])
            else:
                e = out
            embeds_l.append(e[0])
        embeds = jnp.stack(embeds_l)
        deep = (jnp.stack(deep_l, axis=1)
                if qwen and deep_l and deep_l[0] is not None else None)
        return embeds, deep

    def _mm_execute(self, images, tokens: np.ndarray, packed: np.ndarray,
                    pos3: Optional[np.ndarray]):
        """Vision encode + multimodal prefill for one admission — runs
        IDENTICALLY on the coordinator and on follower pods (both enter
        the same jitted programs in the same order; followers get the
        inputs by broadcast). Updates the pools/counts and returns the
        device SampleResult."""
        from llms_on_kubernetes_tpu.parallel.mesh import set_active_mesh

        set_active_mesh(self.mesh)  # follower_loop calls this directly
        cfg = self.model_config
        embeds, deep = self._encode_request_images(images)
        n_max = self.config.max_images_per_request
        if embeds.shape[0] < n_max:  # pad image count to the compiled shape
            pad = jnp.zeros((n_max - embeds.shape[0],) + embeds.shape[1:],
                            embeds.dtype)
            embeds = jnp.concatenate([embeds, pad])
            if deep is not None:
                dpad = jnp.zeros(deep.shape[:1] + (n_max - deep.shape[1],)
                                 + deep.shape[2:], deep.dtype)
                deep = jnp.concatenate([deep, dpad], axis=1)
        if deep is not None:  # configs without deepstack taps: None
            # flatten per row: [n_taps, 1(row), n_img_max*t_img, D]
            deep = deep.reshape(deep.shape[0], -1, deep.shape[-1])[:, None]
        pos3_dev = None if pos3 is None else jnp.asarray(pos3)
        use_fsm = bool(packed[0, _FSM_PRE] >= 0)  # same bytes on followers
        (pack, toks, self.k_pages, self.v_pages, self.token_counts,
         new_state) = self._mm_prefill_packed(
            self.params, cfg, jnp.asarray(tokens), jnp.asarray(packed),
            embeds[None], deep, pos3_dev, self.k_pages, self.v_pages,
            self.token_counts, self._key,
            self._fsm_args() if use_fsm else None,
        )
        if new_state is not None:
            self._fsm_state = new_state
        return pack, toks

    def _dispatch_mm_prefill(self, slot: int, req: Request,
                             prefill_tokens: list[int], led_rows: list):
        """Build a multimodal admission's inputs, announce them to
        follower pods (control word + pixel payload), and run the encode
        + prefill (one dispatch record: only the prefill is read). Returns
        the device SampleResult and the dispatch's seq."""
        cfg = self.model_config
        n = len(prefill_tokens)
        bucket = self._bucket_for(n)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = prefill_tokens
        packed = np.zeros((1, _PRE_COLS + self.allocator.pages_per_slot),
                          np.int32)
        self._pack_prefill_row(packed, 0, req, n, slot)
        pos3 = None
        if cfg.vision.family == "qwen3vl":
            from llms_on_kubernetes_tpu.models.vision import qwen_mrope_positions

            # delta is NOT re-assigned here: submit() already derived it
            # (the authoritative value — cache-hit admissions skip this
            # dispatch entirely and still need it for decode)
            p3, _delta = qwen_mrope_positions(
                prefill_tokens, cfg.image_token_id,
                cfg.vision.mm_tokens_per_image,
                prompt_len=len(req.prompt),
                grids=self._mm_grids(req.images))
            pos3 = np.zeros((1, 3, bucket), np.int32)
            pos3[0, :, :n] = p3
        if self.config.multihost:
            from llms_on_kubernetes_tpu.engine import multihost as mh

            self._mh_send(mh.MSG_MM_PREFILL, pre_tokens=tokens,
                          pre_packed=packed)
            mh.send_mm_payload(self._mh_shapes, req.images,
                               None if pos3 is None else pos3[0])
        with self._dispatch("prefill", "_prefill_mm_packed_step",
                            f"1x{bucket}", led_rows) as dseq:
            pack, toks = self._mm_execute(req.images, tokens, packed, pos3)
        self.slot_len[slot] = n
        return pack, toks, dseq

    # ------------------------------------------------------------------
    # grammar-constrained decoding: device-table residency
    # ------------------------------------------------------------------

    def _fsm_args(self):
        """The (state, class_of, trans) device tuple, or None before the
        first constrained admission."""
        if self._fsm_state is None:
            return None
        return (self._fsm_state, *self._g_dev)

    def _fsm_any_active(self) -> bool:
        return any(r is not None and r.fsm_row >= 0 for r in self.slots)

    def _g_first_fit(self, size: int) -> Optional[int]:
        occ = sorted((e[1], e[1] + e[2]) for e in self._g_resident.values())
        base = 0
        for lo, hi in occ:
            if lo - base >= size:
                return base
            base = max(base, hi)
        return base if self.config.grammar_states - base >= size else None

    def _ensure_grammar(self, req: Request) -> bool:
        """Make the request's grammar resident in the device tables and
        point req.fsm_row/fsm_start at it (refcounted). Returns False when
        every table row / state range is pinned by RUNNING requests — the
        admission waits, exactly like page-pool pressure. Idempotent per
        request (a blocked admission retries every iteration)."""
        if req.fsm_row >= 0:
            return True
        g = req.params.grammar
        ent = self._g_resident.get(g.key)
        if ent is None:
            cfg = self.config
            if self._g_class_h is None:
                V = self.model_config.vocab_size
                self._g_class_h = np.zeros((cfg.max_grammars, V), np.int16)
                self._g_trans_h = np.full(
                    (cfg.grammar_states, cfg.grammar_classes), -1, np.int16)
                self._fsm_state = jnp.full(
                    (cfg.max_decode_slots,), -1, jnp.int32)
            while True:
                used = {e[0] for e in self._g_resident.values()}
                row = next((r for r in range(cfg.max_grammars)
                            if r not in used), None)
                base = self._g_first_fit(g.n_states)
                if row is not None and base is not None:
                    break
                victim = next((k for k, e in self._g_resident.items()
                               if e[3] == 0), None)
                if victim is None:
                    return False  # all pinned by running requests; wait
                del self._g_resident[victim]
            V = self.model_config.vocab_size
            co = np.full((V,), g.n_classes - 2, np.int16)  # pad: reject
            co[:len(g.class_of)] = g.class_of
            self._g_class_h[row] = co
            tr = g.trans.astype(np.int32)
            self._g_trans_h[base:base + g.n_states, :] = -1
            self._g_trans_h[base:base + g.n_states, :g.n_classes] = np.where(
                tr >= 0, tr + base, -1).astype(np.int16)
            ent = [row, base, g.n_states, 0, g]
            self._g_resident[g.key] = ent
            self._upload_grammars()
        ent[3] += 1
        req.fsm_row = ent[0]
        req.fsm_start = ent[1] + g.start
        return True

    def _upload_grammars(self) -> None:
        self._g_dev = (jnp.asarray(self._g_class_h),
                       jnp.asarray(self._g_trans_h))
        if self.config.multihost:
            from llms_on_kubernetes_tpu.engine import multihost as mh

            self._mh_send(mh.MSG_GRAMMAR)
            mh.send_grammar_payload(self._mh_shapes, self._g_class_h,
                                    self._g_trans_h)

    def _g_release(self, req: Request) -> None:
        """Drop the request's hold on its resident grammar (finish, abort,
        preemption — a resumed admission re-ensures residency)."""
        if req.fsm_row < 0 or req.params.grammar is None:
            return
        ent = self._g_resident.get(req.params.grammar.key)
        if ent is not None and ent[3] > 0:
            ent[3] -= 1
        req.fsm_row = -1
        req.fsm_start = -1
        req.pending_fsm_state = None

    def _fsm_replay(self, req: Request) -> None:
        """Resume-after-preemption: recompute the FSM state after every
        emitted token on the HOST (the device state was lost with the
        slot) and stage it for the next decode launch's fsm_set."""
        g = req.params.grammar
        s = g.start
        for t in req.output:
            s = g.next_state(s, t)
            if s < 0:
                import logging
                logging.getLogger(__name__).warning(
                    "request %s: emitted token %d not reachable in its own "
                    "grammar; continuing unconstrained", req.id, t)
                self._g_release(req)
                return
        req.pending_fsm_state = (req.fsm_start
                                 - g.start) + s  # base + replayed state

    def _admit_one(self) -> list[StepEvent]:
        """Admit + prefill at most one waiting request per iteration.

        A resumed (previously preempted) request re-prefills its prompt plus
        every already-emitted token except the pending one; the prefill's
        sampled token is discarded and the old pending token is restored, so
        the output stream is unaffected by preemption.
        """
        from llms_on_kubernetes_tpu import faults

        if faults.is_active("queue_stall"):
            # LLMK_FAULT=queue_stall: admission refuses while the flag is
            # set — waiting requests age in the queue (deadline-shed and
            # Retry-After paths become deterministically testable)
            return []
        with self._lock:
            if not self.waiting:
                return []
            slot = self._free_slot()
            if slot is None:
                return []
            req = self.waiting[0]
            if (req.params.grammar is not None
                    and not self._ensure_grammar(req)):
                return []  # all grammar rows pinned; wait like page pressure
            if req.adapter is not None and not self._ensure_adapter(req):
                return []  # every adapter slot pinned; wait like pages
            resumed = bool(req.output)
            prefill_tokens = req.prompt + (req.output[:-1] if resumed else [])
            n = len(prefill_tokens)
            if self.allocator.pages_needed(n + 1) > self.allocator.pages_per_slot:
                # resumed request grew beyond page reach; end it
                # gracefully rather than livelocking the queue behind it
                self.waiting.popleft()
                ev = self._finish(req, "length")
                return [ev]
            # adopt any cached prefix FIRST so can_allocate counts only the
            # private pages still needed; roll back if they don't fit yet
            hit = self._adopt_cached_prefix(slot, req, prefill_tokens)
            if not self.allocator.can_allocate(slot, n + 1):
                if hit:
                    self.allocator.rollback_adopt(slot)
                self._host_adopt.pop(slot, None)
                return []  # wait for pages to free up
            self.waiting.popleft()
        self.allocator.allocate(slot, n + 1)
        if hit:
            self.allocator.commit_adopt(slot, hit)
        self._note_admission(req)
        # host-tier pages upload BEFORE the prefill below is dispatched,
        # so its history attention reads the restored KV
        self._host_kv_commit(slot, req)
        self.slots[slot] = req
        req.slot = slot
        if resumed and req.fsm_row >= 0:
            self._fsm_replay(req)  # stages fsm_set for the next decode

        led_rows = [(req, "prefill", n - hit or n)]
        kind = "prefill"
        if req.images is not None and hit == 0:
            pack, toks, dseq = self._dispatch_mm_prefill(
                slot, req, prefill_tokens, led_rows)
        elif hit > 0 or n > max(self.config.prefill_buckets):
            # cache-hit admissions run the chunk path: prefill-with-history
            # attention over the remainder, history = the adopted prefix
            # (for a multimodal hit the remainder is pure text)
            kind = "chunk"
            pack, toks, dseq = self._chunked_prefill(
                slot, req, prefill_tokens, led_rows, start=hit)
        else:
            from llms_on_kubernetes_tpu.engine.multihost import MSG_PREFILL

            bucket = self._bucket_for(n)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :n] = prefill_tokens
            packed = np.zeros((1, _PRE_COLS + self.allocator.pages_per_slot),
                              np.int32)
            packed[:, _ADP_PRE] = -1
            packed[:, _FSM_PRE:_FSM_PRE + 2] = -1
            self._pack_prefill_row(packed, 0, req, n, slot)
            use_fsm = packed[0, _FSM_PRE] >= 0
            self._mh_send(MSG_PREFILL, pre_tokens=tokens, pre_packed=packed,
                          fsm_used=use_fsm)
            self.path_tokens["prefill"] += n
            with self._dispatch("prefill", "_prefill_packed_step",
                                f"1x{bucket}", led_rows, bucket) as dseq:
                (pack, toks, self.k_pages, self.v_pages, self.token_counts,
                 new_state, self.conv_state) = self._prefill_packed(
                    self.params, self.model_config, jnp.asarray(tokens),
                    jnp.asarray(packed), self.k_pages, self.v_pages,
                    self.token_counts, self._key,
                    self._fsm_args() if use_fsm else None, self.conv_state,
                )
            if new_state is not None:
                self._fsm_state = new_state
            self.slot_len[slot] = n
        # no row reads it; a follower passes its newest too (multihost)
        self._unread_prefill_toks = toks
        # the dispatched prefill writes these pages; device order makes
        # them valid for any later-dispatched adopter
        if req.cache_salt is not None:
            self.allocator.register_prefix(slot, req.prompt,
                                           salt=req.cache_salt)
        if resumed:
            req.pending_token = req.output[-1]
            self.timeline.close(dseq, None)   # nobody reads a re-prefill
            return []
        t0 = time.perf_counter()
        arr = np.asarray(jax.device_get(pack))
        host = HostSample(arr)
        self._device_time_s += time.perf_counter() - t0
        self.timeline.close(dseq, self._clock())
        self._book_moe(kind, arr, 1)
        first = int(host.tokens[0])
        req.pending_token = first
        return self._emit(req, first, _lp_entry(host, 0), first=True)

    def _emit(self, req: Request, token: int, lp: Optional[tuple] = None,
              first: bool = False) -> list[StepEvent]:
        """Record a sampled token (+ its logprob data) and decide whether
        the request finishes. ``first``: the request's first token, whose
        hand-over stamps ``first_token_at`` (_hand_over)."""
        req.output.append(token)
        req.output_logprobs.append(lp)
        reason = None
        if token in set(req.params.stop_token_ids):
            reason = "stop"
        elif len(req.output) >= req.params.max_tokens:
            reason = "length"
        elif self.slot_len[req.slot] + 1 >= self.config.max_model_len:
            reason = "length"
        if reason is not None:
            self._finish(req, reason)
        return [StepEvent(req, [token], req.finished, reason, first=first)]

    def _finish(self, req: Request, reason: str) -> StepEvent:
        """Release a request's slot/pages and mark it finished."""
        req.finished = True
        req.finish_reason = reason
        req.finished_at = time.monotonic()
        if req.trace is not None:
            req.trace.event("finish", request=req.id, reason=reason,
                            tokens=len(req.output))
        self._g_release(req)
        self._release_adapter(req)
        if req.slot >= 0:
            # spill full pages to the host tier BEFORE the allocator can
            # hand them to another sequence (skip a wedged device: the
            # gather would never complete)
            if reason != "stalled" and not self.wedged:
                self._spill_slot(req)
                # a prefill-role replica's whole product is the spilled
                # pages: land them in the host tier BEFORE the finish
                # event reaches the server, so the handoff ticket never
                # races the decode replica's pull against a lazy drain
                if ((self.config.role == "prefill" or req.handoff)
                        and self.host_kv is not None):
                    self._drain_spills()
            self.allocator.free(req.slot)
            self.slot_len[req.slot] = 0
            self.slots[req.slot] = None
            req.slot = -1
        return StepEvent(req, [], True, reason)

    # ------------------------------------------------------------------
    # watchdog
    # ------------------------------------------------------------------

    def _stall_budget(self) -> Optional[float]:
        """Max seconds a blocking harvest wait may sit with no completion.

        ``max(watchdog_stall_s, 50 x recent step estimate)`` — the floor
        keeps a slow-but-live device (long prefill compile, first-step
        tracing) out of the abort path, while the step-estimate multiple
        scales up for genuinely slow configs. None disables the watchdog.
        """
        limit = self.config.watchdog_stall_s
        if not limit or limit <= 0:
            return None
        return max(float(limit), 50.0 * self._est_step)

    def _shed_wedged(self, why: str) -> list[StepEvent]:
        """A device step blew the watchdog budget: the accelerator (or its
        transport) is wedged and no in-flight work will ever complete.
        Finish every request with reason "stalled" so clients get a clean
        terminal event instead of a hang, and mark the engine wedged —
        submit() rejects from here on and the server flips readiness; a
        process restart is the only recovery."""
        from llms_on_kubernetes_tpu.server.tracing import jlog

        jlog("engine_wedged", why=why, waiting=len(self.waiting),
             active=sum(r is not None for r in self.slots))
        self.wedged = True
        events: list[StepEvent] = []
        with self._lock:
            doomed = list(self.waiting)
            self.waiting.clear()
        for r in doomed:
            if not r.finished:
                events.append(self._finish(r, "stalled"))
        for r in list(self.slots):
            if r is not None and not r.finished:
                events.append(self._finish(r, "stalled"))
        for req, _key, _row in self._pending_first:
            if not req.finished:
                events.append(self._finish(req, "stalled"))
        self._inflight.clear()
        self._pending_first = []
        self._first_reads.clear()
        self._chain = None
        self.timeline.abandon()   # their reads will never come
        return events

    def _note_admission(self, req: Request) -> None:
        """Per-tenant admission accounting, recorded where a request takes
        its slot. Only FIRST admissions count (admitted_at is still None;
        a preemption round trip is not new tenant throughput) — the
        serving loop drains these into the llm_tenant_* series. The one
        writer of admitted_at, on every prefill path."""
        if (self.conv_state is not None and self.config.prefix_caching
                and req.cache_salt is not None):
            self.prefix_reuse_skipped["recurrent_state"] += 1
        if req.admitted_at is not None:
            return
        req.admitted_at = time.monotonic()
        self.tenant_admitted[(req.tenant, req.priority)] += 1
        self.tenant_wait_obs.append(
            (req.tenant, req.admitted_at - req.submitted_at, req.priority))

    def _preempt_youngest(self) -> None:
        """Free a victim's pages; requeue it to re-prefill (prompt +
        generated so far) when memory frees up. Victim selection is
        priority-aware: the lowest class sheds first (batch before normal
        before interactive), youngest submission breaking ties — KV
        pressure lands on the traffic the operator marked preemptible
        before it ever touches interactive streams."""
        victims = [r for r in self.slots if r is not None]
        if not victims:
            raise MemoryError("KV pool exhausted with no preemptable request")
        victim = max(victims,
                     key=lambda r: (priority_rank(r.priority), r.submitted_at))
        self.preemptions += 1
        if victim.trace is not None:
            victim.trace.event("preempted", request=victim.id,
                               tokens=len(victim.output))
        slot = victim.slot
        # park the victim's KV in the host tier: its re-admission resumes
        # from uploaded pages instead of re-prefilling from scratch
        self._spill_slot(victim)
        self.allocator.free(slot)
        self.slot_len[slot] = 0
        self.slots[slot] = None
        victim.slot = -1
        victim.pending_token = -1
        # release the grammar hold too: re-admission re-ensures residency
        # and host-replays the FSM state from the emitted tokens
        self._g_release(victim)
        # ... and the adapter pin: re-admission re-acquires (the factors
        # stay host-cached, so a round trip is an upload at worst)
        self._release_adapter(victim)
        with self._lock:
            self.waiting.appendleft(victim)

    def _dec_template(self, active) -> np.ndarray:
        """Fresh copy of the decode packed array with every request-STATIC
        column (sampling params, seed, mrope delta, grammar row, logit
        bias block) filled from the per-slot template cache. A slot's
        template rebuilds only when its occupant — or that occupant's
        grammar row — changes; vacated slots reset to the idle defaults.
        Callers write only the per-step dynamic columns (length, token
        source/value, prefill row, fsm force, page tables)."""
        tmpl = self._dec_rows
        owners = self._dec_row_owner
        occupant = dict(active)
        for i in range(self.config.max_decode_slots):
            r = occupant.get(i)
            if r is None:
                if owners[i] is not None:   # vacated: back to idle defaults
                    tmpl[i, :] = 0
                    tmpl[i, 1] = 1
                    tmpl[i, 5] = np.float32(1.0).view(np.int32)
                    tmpl[i, _ADP_DEC] = -1
                    tmpl[i, _FSM_DEC] = -1
                    tmpl[i, _STOP_DEC:_STOP_DEC + STOP_SLOTS] = -1
                    owners[i] = None
                continue
            fsm_row = r.fsm_row if r.fsm_row >= 0 else -1
            if (owners[i] is r and tmpl[i, _FSM_DEC] == fsm_row
                    and tmpl[i, _ADP_DEC] == r.adapter_slot):
                continue
            tmpl[i, :] = 0
            tmpl[i, 1] = 1
            tmpl[i, 3] = r.params.top_k
            tmpl[i, 4] = np.float32(r.params.temperature).view(np.int32)
            tmpl[i, 5] = np.float32(r.params.top_p).view(np.int32)
            tmpl[i, 6] = r.seed
            tmpl[i, 8] = np.float32(r.params.presence_penalty).view(np.int32)
            tmpl[i, 9] = np.float32(r.params.frequency_penalty).view(np.int32)
            tmpl[i, 10] = r.mrope_delta
            tmpl[i, _ADP_DEC] = r.adapter_slot
            tmpl[i, _FSM_DEC] = fsm_row
            tmpl[i, _STOP_DEC:_STOP_DEC + STOP_SLOTS] = -1
            for j, sid in enumerate(r.params.stop_token_ids):
                tmpl[i, _STOP_DEC + j] = sid
            _pack_bias(tmpl, i, _BIAS_DEC, r.params)
            owners[i] = r
        packed = tmpl.copy()
        packed[:, _DEC_COLS:] = self.allocator.page_tables
        return packed

    def _pack_decode(self, active, plan: dict, infl: dict,
                     merged: dict) -> np.ndarray:
        """The decode step's packed array: the template plus each row's
        dynamic columns. ``plan[i]`` is the row's window budget (0: it
        rides masked), ``infl[i]`` the tokens its slot has in flight (its
        input token is then the newest step's output, on the device) and
        ``merged[i]`` an admission's ``(resumed, host value, prefill
        row)``; a row in neither reads its request's pending token."""
        packed = self._dec_template(active)
        for i, r in active:
            p, prior = plan.get(i, 0), infl.get(i, 0)
            packed[i, 0] = 0 if p <= 0 else int(self.slot_len[i]) + prior + 1
            packed[i, _BUD_DEC] = p
            if r.fsm_row >= 0 and r.pending_fsm_state is not None:
                packed[i, _FSM_DEC + 1] = 1      # resume: force state
                packed[i, _FSM_DEC + 2] = r.pending_fsm_state
                r.pending_fsm_state = None
            if i in merged:
                resumed, host_val, row = merged[i]
                if resumed:              # resumed: host-known pending token
                    packed[i, 1], packed[i, 2] = 1, host_val
                else:                    # fresh: token sampled by the prefill
                    packed[i, 1], packed[i, 7] = 2, row
            elif prior > 0:
                packed[i, 1] = 0         # newest in-flight step's output
            else:
                packed[i, 1], packed[i, 2] = 1, r.pending_token
        return packed

    def _decode_once(self) -> list[StepEvent]:
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return []

        # grow page tables; preempt on exhaustion
        for i, r in list(active):
            while True:
                if self.slots[i] is not r:
                    # r was preempted by an earlier iteration's MemoryError;
                    # allocating would leak a page into the vacated slot
                    break
                try:
                    self.allocator.allocate(i, int(self.slot_len[i]) + 1)
                    break
                except MemoryError:
                    self._preempt_youngest()
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return []

        from llms_on_kubernetes_tpu.engine.multihost import MSG_DECODE

        self.decode_dispatches += 1
        self.decode_tokens += len(active)
        self.steps_obs.append(1)
        # a window of one on every active row, every token host-known
        ones = dict.fromkeys((i for i, _r in active), 1)
        packed = self._pack_decode(active, ones, {}, {})
        self._count_window_rows(
            {i: int(self.slot_len[i]) + 1 for i in ones}, ones)

        use_fsm = self._fsm_any_active()
        self._mh_send(MSG_DECODE, dec_packed=packed, fsm_used=use_fsm)
        with self._dispatch("decode", "_decode_multi_packed_step",
                            f"1x{len(active)}",
                            positions=self._ssm_rows(len(active)),
                            sampler=self._book_sampler(packed)) as dseq:
            (pack, self._unread_toks, self.k_pages, self.v_pages,
             self.token_counts, new_state,
             self.conv_state) = self._decode_multi(
                self.params, self.model_config, 1, jnp.asarray(packed),
                self._unread_toks, self._unread_prefill_toks, self.k_pages,
                self.v_pages, self.token_counts, self._key,
                self._fsm_args() if use_fsm else None, self.conv_state,
            )
        if new_state is not None:
            self._fsm_state = new_state
        t0 = time.perf_counter()
        arr = np.asarray(jax.device_get(pack))
        host = HostSample(arr[0])
        self._device_time_s += time.perf_counter() - t0
        self._book_moe("decode", arr, len(self.slots))
        t_read = self._clock()
        self.timeline.close(dseq, t_read,
                            [(r, "decode", 1) for _i, r in active])
        if self.ledger is not None:
            self._collected.append(("decode", t_read))

        events: list[StepEvent] = []
        for i, r in active:
            self.slot_len[i] += 1  # pending token's KV is now cached
            new = int(host.tokens[i])
            r.pending_token = new
            events += self._emit(r, new, _lp_entry(host, i))
        return events

    # ------------------------------------------------------------------
    # async (pipelined) scheduling
    # ------------------------------------------------------------------

    def _inflight_tokens(self) -> dict:
        """Per-slot in-flight TOKEN counts: what each unharvested window
        planned for the slot's CURRENT request, which is what a launch
        sizes page allocations and window budgets from. A step whose
        entry at a slot refers to a previous (finished/preempted) occupant
        does not count: it writes garbage the harvest skips, and counting
        it would inflate the new request's attention length into
        unwritten positions."""
        counts: dict[int, int] = {}
        for s in self._inflight:
            for j, r in s.active:
                if self.slots[j] is r:
                    counts[j] = counts.get(j, 0) + s.planned.get(j, 0)
        return counts

    def _admit_async(self, events: list[StepEvent]):
        """Admission without host sync: prefill up to admit_batch waiting
        same-bucket requests in ONE padded call; first-token reads are
        deferred to _harvest. A prompt of the chunk path (past the largest
        bucket, or behind a cached prefix) is admitted alone and written
        ONE CHUNK a call (_next_chunk), the bucket path taking every other
        turn while prompts wait for it. Returns None or a dict describing
        the admissions for the decode launch's on-device token merge."""
        from llms_on_kubernetes_tpu import faults

        if faults.is_active("queue_stall"):
            # LLMK_FAULT=queue_stall: admission refuses while the flag is
            # set (see _admit_one)
            return None
        if any(s.spec for s in self._inflight):
            # a speculative verify is in flight: its consumed-vs-planned
            # delta is unknown until harvest, so defer admission one step
            # (an admission's decode launch must run the same iteration,
            # and spec dispatches serialize). step() harvests the verify
            # this iteration and admits first thing next iteration — no
            # starvation, bounded by one dispatch.
            return None
        # clear BEFORE scanning: a submit after this point re-sets the flag
        # (at worst a spurious backpressure wakeup), while anything already
        # queued is handled right here
        self._admit_wake.clear()
        chain = self._chain
        if chain is not None and (chain.req.finished
                                  or self.slots[chain.slot] is not chain.req):
            # aborted or preempted part-way: what is left of it goes
            chain = self._chain = None
        if chain is not None and chain.yielded:
            return self._next_chunk()
        picked: list[tuple[int, "Request", bool, list[int]]] = []
        long_pick = None
        with self._lock:
            while self.waiting and len(picked) < self.config.admit_batch:
                slot = self._free_slot()
                if slot is None:
                    break
                req = self.waiting[0]
                if (req.params.grammar is not None
                        and not self._ensure_grammar(req)):
                    break  # all grammar rows pinned; wait
                if req.adapter is not None and not self._ensure_adapter(req):
                    break  # every adapter slot pinned; wait
                resumed = bool(req.output)
                prefill_tokens = req.prompt + (req.output[:-1] if resumed else [])
                n = len(prefill_tokens)
                if self.allocator.pages_needed(n + 1) > self.allocator.pages_per_slot:
                    self.waiting.popleft()
                    events.append(self._finish(req, "length"))
                    continue
                hit = self._adopt_cached_prefix(slot, req, prefill_tokens)
                if (hit > 0 or req.images is not None
                        or n > max(self.config.prefill_buckets)):
                    # cache-hit / multimodal / out-of-bucket prompt: runs
                    # alone (chunk path or mm prefill), one at a time
                    if (picked or chain is not None
                            or not self.allocator.can_allocate(slot, n + 1)):
                        if hit:
                            self.allocator.rollback_adopt(slot)
                        self._host_adopt.pop(slot, None)
                        break  # runs by itself next iteration / wait
                    self.waiting.popleft()
                    self.allocator.allocate(slot, n + 1)
                    if hit:
                        self.allocator.commit_adopt(slot, hit)
                    self._note_admission(req)
                    self.slots[slot] = req
                    req.slot = slot
                    if resumed and req.fsm_row >= 0:
                        self._fsm_replay(req)
                    long_pick = (slot, req, resumed, prefill_tokens, hit)
                    break
                if picked and self._bucket_for(n) != self._bucket_for(
                        len(picked[0][3])):
                    break  # next request needs a different bucket
                if not self.allocator.can_allocate(slot, n + 1):
                    break  # wait for pages to free up
                self.waiting.popleft()
                self.allocator.allocate(slot, n + 1)
                self._note_admission(req)
                # combined hit was 0, so this is counter-only (host miss)
                self._host_kv_commit(slot, req)
                self.slots[slot] = req
                req.slot = slot
                if resumed and req.fsm_row >= 0:
                    self._fsm_replay(req)
                picked.append((slot, req, resumed, prefill_tokens))
        if long_pick is not None:
            slot, req, resumed, prefill_tokens, hit = long_pick
            # upload host-tier pages (if staged) before the chunk prefill
            # below is dispatched — its history attention reads them.
            # Outside the lock: the np.stack memcpy must not block submit()
            self._host_kv_commit(slot, req)
            if req.images is None or hit > 0:
                # cache-hit remainder (pure text for multimodal hits) or
                # an out-of-bucket text prompt: the chunk path, a chunk a
                # step()
                self._chain = ChunkChain(slot, req, prefill_tokens, hit, hit,
                                         resumed)
                return self._next_chunk()
            led_rows = [(req, "prefill", max(1, len(prefill_tokens)))]
            pack, toks, dseq = self._dispatch_mm_prefill(
                slot, req, prefill_tokens, led_rows)
            return self._lone_admission(slot, req, resumed, pack, toks, dseq)
        if not picked:
            return None if chain is None else self._next_chunk()
        if chain is not None:
            chain.yielded = True

        from llms_on_kubernetes_tpu.engine.multihost import MSG_PREFILL

        bucket = max(self._bucket_for(len(p[3])) for p in picked)
        # pad the batch to 1 or admit_batch rows (two executables per bucket)
        K = 1 if len(picked) == 1 else self.config.admit_batch
        pps = self.allocator.pages_per_slot
        tokens = np.zeros((K, bucket), np.int32)
        packed = np.zeros((K, _PRE_COLS + pps), np.int32)
        packed[:, 3] = np.float32(1.0).view(np.int32)  # top_p disabled
        packed[:, _ADP_PRE] = -1                       # padded rows: base
        packed[:, _FSM_PRE:_FSM_PRE + 2] = -1          # padded rows: none
        for row, (slot, req, _resumed, ptoks) in enumerate(picked):
            n = len(ptoks)
            tokens[row, :n] = ptoks
            self._pack_prefill_row(packed, row, req, n, slot)
            self.slot_len[slot] = n
            self.path_tokens["prefill"] += n

        use_fsm = bool((packed[:, _FSM_PRE] >= 0).any())
        self._mh_send(MSG_PREFILL, pre_tokens=tokens, pre_packed=packed,
                      fsm_used=use_fsm)
        # every picked row rode the dispatch, resumed ones included
        led_rows = [(req, "prefill", max(1, len(ptoks)))
                    for _slot, req, _resumed, ptoks in picked]
        with self._dispatch("prefill", "_prefill_packed_step",
                            f"{K}x{bucket}", led_rows, K * bucket) as dseq:
            (pack, toks, self.k_pages, self.v_pages, self.token_counts,
             new_state, self.conv_state) = self._prefill_packed(
                self.params, self.model_config, jnp.asarray(tokens),
                jnp.asarray(packed), self.k_pages, self.v_pages,
                self.token_counts, self._key,
                self._fsm_args() if use_fsm else None, self.conv_state,
            )
        if new_state is not None:
            self._fsm_state = new_state
        for slot, req, _resumed, _ptoks in picked:
            self.allocator.register_prefix(slot, req.prompt)
        key = None
        if any(not resumed for _, _, resumed, _ in picked):
            # a negative key: its read carries first tokens
            key = -1 - dseq
            self._harvester.push(key, pack)
            self._first_reads[key] = ("prefill", K)
        else:
            self.timeline.close(dseq, None)   # nobody reads a re-prefill
        merge = {"toks": toks, "slots": {}}
        for row, (slot, req, resumed, _ptoks) in enumerate(picked):
            if resumed:
                # pending token is already host-known (the last emitted
                # token); the prefill's sampled token is discarded, as in
                # the sync path
                req.pending_token = req.output[-1]
                merge["slots"][slot] = (True, req.output[-1], row)
            else:
                merge["slots"][slot] = (False, 0, row)
                self._pending_first.append((req, key, row))
        return merge

    def _launch_decode_async(self, admitted, events: list[StepEvent]) -> str:
        """Launch one decode step whose input tokens are assembled ON DEVICE
        from the newest in-flight step's output (continuing slots), host
        values (slots with no step in flight), and this step's prefill
        (just-admitted slots): a speculative verify where a speculator
        has drafts, else a window of decode_steps (1 included). Returns
        "launched", "early" (not due yet: _decode_due; _harvest waits out
        the rest), "paced" (nothing to launch until device work
        completes), or "idle"."""
        K = self.config.decode_steps
        if self._spec is not None:
            st = self._launch_decode_spec(K, admitted, events)
            if st is not None:
                return st
        return self._launch_decode_multi(K, admitted, events)

    def _plan_windows(self, K: int,
                      events: list[StepEvent]) -> tuple[dict, dict]:
        """Per slot, PLAN p <= K tokens for the next window and allocate
        its pages up front. p is clipped by the request's remaining
        max_tokens budget (less the tokens already in flight and a first
        token whose read has not landed: it is emitted before any of this
        window's) and by max_model_len; a row with p == 0 rides masked.
        On exhaustion: drain in-flight work (finishes hiding in
        unharvested steps free pages), then preempt. Returns ``(plan,
        in-flight tokens per slot)``.

        Token-level inflight counts: a planned-but-unharvested window
        already owns its positions. Stale plan entries survive drains —
        slot_len + inflight is invariant under harvest (tokens move from
        in-flight to slot_len one-for-one), and so is the max_tokens
        budget (output grows by exactly the harvested tokens)."""
        max_len = self.config.max_model_len
        infl = self._inflight_tokens()
        first_pending = {id(r) for r, _k, _row in self._pending_first}
        plan: dict[int, int] = {}
        i = 0
        while i < self.config.max_decode_slots:
            r = self.slots[i]
            if r is None:
                i += 1
                continue
            if self._chain is not None and i == self._chain.slot:
                plan[i] = 0     # its prompt is still being written
                i += 1
                continue
            prior = infl.get(i, 0)
            base0 = int(self.slot_len[i]) + prior + 1
            extra = 1 if id(r) in first_pending else 0
            budget = r.params.max_tokens - len(r.output) - prior - extra
            p = max(0, min(K, budget, max_len - base0 + 1))
            if p == 0:
                plan[i] = 0
                i += 1
                continue
            try:
                self.allocator.allocate(i, base0 + p - 1)
                plan[i] = p
                i += 1
            except MemoryError:
                if self._inflight or self._pending_first:
                    events += self._harvest(drain=True)
                    infl = self._inflight_tokens()
                    first_pending = {id(r) for r, _k, _row
                                     in self._pending_first}
                    continue
                self._preempt_youngest()
                infl = self._inflight_tokens()
        return plan, infl

    def _launch_decode_multi(self, K: int, admitted,
                             events: list[StepEvent]) -> str:
        """The window launch: one dispatch runs up to K decode steps per
        slot (_decode_multi_packed_step), each row with the budget
        _plan_windows gave it. The harvest consumes up to that many tokens
        per row; host-side _emit stays authoritative for finishes, so a
        row that stops mid-window simply wastes its tail (early-exit
        accounting)."""
        rule = self._decode_due(admitted)
        if rule[0] is None:
            return "early"
        if admitted is not None:
            # before any return: a follower takes every prefill's tokens
            # as its newest (multihost.follower_loop), launch or none
            self._unread_prefill_toks = admitted["toks"]

        plan, infl = self._plan_windows(K, events)
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return "idle"
        if all(plan.get(i, 0) == 0 for i, _r in active):
            # every row's budget is consumed by in-flight work — a
            # dispatch would be all-masked. The pipeline is non-empty in
            # this state (empty pipeline => budget >= 1), so harvesting
            # makes progress. (Or the one row is a prompt part-way through
            # its chunks: step() comes back to launch the next.)
            return "paced" if self._chain is None else "launched"

        packed = self._pack_decode(
            active, plan, infl, admitted["slots"] if admitted else {})
        self._count_window_rows(
            {i: int(self.slot_len[i]) + infl.get(i, 0) + 1
             for i, _r in active}, plan)
        last_toks = (self._inflight[-1].toks if self._inflight
                     else self._unread_toks)
        prefill_toks = self._unread_prefill_toks

        from llms_on_kubernetes_tpu.engine.multihost import MSG_DECODE

        use_fsm = self._fsm_any_active()
        self._mh_send(MSG_DECODE, dec_packed=packed, fsm_used=use_fsm)
        with self._dispatch("decode", "_decode_multi_packed_step",
                            f"{K}x{len(active)}",
                            positions=K * self._ssm_rows(sum(
                                plan.get(i, 0) > 0 for i, _r in active)),
                            sampler=self._book_sampler(packed)) as dseq:
            (pack, toks, self.k_pages, self.v_pages, self.token_counts,
             new_state, self.conv_state) = self._decode_multi(
                self.params, self.model_config, K, jnp.asarray(packed),
                last_toks, prefill_toks, self.k_pages, self.v_pages,
                self.token_counts, self._key,
                self._fsm_args() if use_fsm else None, self.conv_state,
            )
        if new_state is not None:
            self._fsm_state = new_state
        seq = next(self._seq_counter)
        step = InflightStep(pack, toks, active, seq,
                            planned={i: plan.get(i, 0) for i, _r in active},
                            dseq=dseq)
        self._inflight.append(step)
        self._unread_toks = toks
        self._harvester.push(seq, pack)
        self._note_launch(*rule)
        return "launched"

    def _launch_decode_spec(self, K: int, admitted,
                            events: list[StepEvent]) -> Optional[str]:
        """Try to launch a speculative verify dispatch; returns None to
        fall through to the plain fused window (_launch_decode_multi).

        Spec dispatches SERIALIZE: a window that consumes fewer tokens
        than it planned would break the slot_len + inflight-tokens
        invariant every pipelined launch's position math relies on, so a
        spec step launches only into an empty pipeline (no in-flight
        steps, no pending firsts, no prefill merge) — every slot's last
        token is then host-known (src == 1) and doubles as the drafter's
        context tail. While the verify is in flight the engine reports
        "paced"; admission is deferred by _admit_async for the same
        reason. The accept-ratio policy demotes drafting on adversarial
        traffic, which silently restores the plain fused pipeline."""
        if any(s.spec for s in self._inflight):
            return "paced"          # serialize: wait for the verify
        if admitted is not None:
            return None             # the admission's merge launches NOW
        if not self._spec.policy.should_draft():
            return None
        if self._inflight or self._pending_first:
            # drafting needs every slot's committed tail host-known: pace
            # until the pipeline drains (each spec dispatch then carries
            # up to K tokens, which is what pipelining amortized)
            return "paced"
        B = self.config.max_decode_slots

        # nothing is in flight: the ladder goes straight to preemption
        plan, infl = self._plan_windows(K, events)
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return None
        # draft only rows with window room (p >= 2) and a committed tail
        ctxs: list = [None] * B
        for i, r in active:
            if plan.get(i, 0) >= 2 and r.pending_token >= 0:
                ctxs[i] = np.asarray(r.prompt + r.output, np.int32)
        if not any(c is not None for c in ctxs):
            return None               # no row has draft room: plain window
        proposals = self._spec.propose_batch(ctxs)
        drafted: dict[int, int] = {}
        D = K - 1
        ext = np.full((B, D), -1, np.int32)
        for i, _r in active:
            d = proposals[i][:max(0, plan.get(i, 0) - 1)]
            if d.size:
                ext[i, :d.size] = d
                drafted[i] = int(d.size)
        if not drafted:
            # an attempted-but-empty draft pass is evidence against this
            # traffic: feed the policy so adversarial streams demote to
            # the pipelined plain window instead of serializing forever
            self._spec.policy.note_empty()
            return None               # nothing proposed: plain window

        full = np.concatenate(
            [self._pack_decode(active, plan, infl, {}), ext], axis=1)

        use_fsm = self._fsm_any_active()
        with self._dispatch("spec", "_decode_spec_packed_step",
                            f"{K}x{len(active)}",
                            sampler=self._book_sampler(full)) as dseq:
            (pack, toks, self.k_pages, self.v_pages, self.token_counts,
             new_state) = self._decode_spec(
                self.params, self.model_config, K, jnp.asarray(full),
                self.k_pages, self.v_pages, self.token_counts, self._key,
                self._fsm_args() if use_fsm else None,
            )
        if new_state is not None:
            self._fsm_state = new_state
        seq = next(self._seq_counter)
        step = InflightStep(pack, toks, active, seq,
                            planned={i: plan.get(i, 0) for i, _r in active},
                            spec=True, drafted=drafted, dseq=dseq)
        self._inflight.append(step)
        self._harvester.push(seq, pack)
        return "launched"

    def _decode_due(self, admitted=None) -> tuple[Optional[str], float, float]:
        """When the next decode step is launched: ``(rule, due, window)``
        with one of DECODE_LAUNCH_RULES if that is now, ``(None, due,
        window)`` if not before ``due``.

        A step that just admitted is launched at once ("admission"): it
        merges the prefill's sampled tokens on the device. Otherwise
        the device needs the next step when the work launched so far
        ends, and a request that arrives meanwhile has its prefill
        enqueued behind whatever is there: so the step is launched
        ``_lead`` before the timeline's estimate of that end
        (DispatchTimeline.free_at: the newest completion the harvester
        has stamped plus the measured device times of what was launched
        after it), and at the latest when the step ahead of it completes
        (_harvest's wait ends there too). Where that cannot be timed, it
        is launched as soon as the pipeline has room ("depth"): a shape
        that never ran, a step no longer than two leads (a CPU engine; a
        model with a 5 ms step), or multihost, whose followers mirror the
        coordinator's launches and have not been run under a timed one."""
        if admitted is not None:
            return "admission", 0.0, 0.0
        now = self._clock()
        n = sum(r is not None for r in self.slots)
        window = None if self.config.multihost else self.timeline.estimate(
            "decode", f"{max(1, self.config.decode_steps)}x{n}")
        if window is None or window <= 2.0 * self._lead:
            return "depth", now, 0.0
        hv = self._harvester
        done = {s.dseq: hv.done_time(s.seq)
                for s in self._inflight if hv.is_done(s.seq)}
        for _req, key, _row in self._pending_first:
            if hv.key_done(key):
                done[-1 - key] = hv.done_time(key)
        free = self.timeline.free_at(now, done)
        if free is None:
            return "depth", now, 0.0
        t_free, busy = free
        due = t_free - self._lead
        if now < due and self._inflight:
            return None, due, window
        return ("timed" if busy else "late"), due, window

    def _note_launch(self, rule: str, due: float, window: float) -> None:
        """Count a decode launch under its rule, and move the lead to what
        this launch needed: from the moment it aimed at (``due``, the
        device's free time less the lead used) to now, the enqueue having
        returned. That holds the thread's late wake, the step() around
        it, packing and the jitted call, and for a "late" launch also by
        how much the device was free sooner than estimated. The lead
        rises to a larger need at once and falls to a smaller one slowly,
        never under the floor (the estimates' rule, mirrored); a launch
        that was not timed only lets it fall."""
        self.decode_launches[rule] += 1
        if rule == "admission":
            return
        need = _LEAD_FLOOR_S
        if rule != "depth":
            need = min(max(self._clock() - due, need), window)
        self._lead += (need - self._lead) * (
            1.0 if need > self._lead else _LEAD_DECAY)

    def _book_moe(self, kind: str, arr, rows: int) -> None:
        """Book what the expert layers of one dispatch did, from the host
        copy of its pack (``rows`` sample rows, then the experts' rows):
        no device read of its own. A token step in which no row was live
        routed nothing and is no step."""
        cfg = self.model_config
        # a model that holds a share of its experts reports, behind the
        # rows of the experts held here, the pairs routed elsewhere
        held = cfg.num_held_experts
        width = held + (cfg.experts_held is not None)
        got = moe_rows_of(np.asarray(arr), rows, cfg.num_moe_layers, width)
        if got is None:
            return
        steps = got.reshape(-1, cfg.num_moe_layers, width)
        steps = steps[steps.sum(axis=(1, 2)) > 0]
        if not len(steps):
            return
        st = self.moe_stats[kind]
        st["routed_rows"] += int(steps.sum())
        steps = steps[..., :held]
        st["held_rows"] += int(steps.sum())
        st["experts_touched"] += int((steps > 0).sum())
        st["expert_slots"] += steps.size
        st["fullest_expert_rows"] += int(steps.max(axis=2).sum())
        st["mean_expert_rows"] += float(steps.sum()) / held
        from llms_on_kubernetes_tpu.ops import attention

        # which grouped product the newest traced step took (ops/moe._plan)
        impl, why = attention._chosen.get("experts", ("", ""))
        self.moe_last = {"kind": kind, "token_steps": len(steps),
                         "rows_per_expert": steps[-1].tolist(),
                         "product": f"{impl} ({why})"}

    def launch_view(self) -> dict:
        """What times the next decode step, for ``GET /debug/engine``:
        the lead, the device time each kind and shape last took, and the
        launches so far by rule."""
        return {"lead_ms": round(self._lead * 1000.0, 3),
                "estimates_ms": self.timeline.estimates_view(),
                "launches": dict(self.decode_launches)}

    def _harvest(self, drain: bool, paced: bool = False) -> list[StepEvent]:
        """Consume host copies of completed device work from the harvester
        thread, in dispatch order, WITHOUT blocking on device execution.

        The engine thread blocks in exactly two cases: ``drain`` (state
        inspection / shutdown / memory pressure needs every result), and
        backpressure: the pipeline holds ``async_depth`` unharvested decode
        steps (launching more would speculate unboundedly), or the next
        one is not due yet (``_decode_due``: the wait then ends at that
        moment, or when the step ahead completes), or there is nothing
        to launch until some device work completes (``paced``: the wait
        ends with the oldest result). Everything
        else — including admission of new requests and their prefill
        dispatch — proceeds while the harvester waits out the device and
        the host read: a submission ends either wait at once. This is
        what bounds gateway TTFT: a new request's prefill queues behind
        what is on the device NOW, not behind a blocking batched read of
        the whole pipeline nor behind a step enqueued before the device
        needed it. A first token is handed to its request
        before the thread sleeps and whenever one lands during the sleep;
        every other event leaves at the end of ``step()``, so the thread
        does not sleep on any."""
        events: list[StepEvent] = []
        if not self._inflight and not self._pending_first:
            return events
        depth = max(1, self.config.async_depth)
        budget = self._stall_budget()
        n_steps = 0
        while True:
            n_steps += self._collect_ready(events)
            # what the wait is for: the k oldest steps in flight, and no
            # later than `until`
            until = None
            if drain:
                if not self._inflight and not self._pending_first:
                    break
                k = len(self._inflight)
            elif len(self._inflight) >= depth:
                k = len(self._inflight) - (depth - 1)   # the one that makes room
            elif paced:
                if events or not (self._inflight or self._pending_first):
                    break       # something completed: step() looks again
                k = 1
            else:
                if not self._inflight:
                    break
                rule, until, _window = self._decode_due()
                if rule is not None:
                    break
                # the launch comes no later than the completion of the
                # step ahead of it
                k = len(self._inflight)
            # blocked. A first token collected so far leaves the engine
            # NOW, not a decode window later when the wait is over
            for ev in events:
                if ev.first:
                    self._hand_over(ev, "backpressure")
            if until is not None and not all(ev.handed_over for ev in events):
                # the pipeline has room and the next step is not due: the
                # other events leave at the end of step(), then step()
                # comes back here to wait
                break
            # wait for whatever gates the head. If the oldest
            # step's request still awaits its FIRST token (its read
            # hasn't landed), wait for that key — consuming the step
            # early would let a stale first overwrite pending_token later
            # and feed the model a wrong input token.
            key = self._head_blocking_first()
            if key is not None:
                with _phase("llmk.wait"):
                    self._harvester.wait_key(key, timeout_s=budget)
                continue
            wake = None if drain else self._admit_wake
            with _phase("llmk.wait"):
                if self._inflight:
                    # a first token landing ends the wait too: the loop
                    # collects it, hands it over above and comes back
                    # here. It does NOT leave for another round of step(),
                    # which would launch one more decode window for the
                    # next prefill to queue behind
                    self._harvester.wait_done(
                        self._inflight[k - 1].seq, wake=wake,
                        keys=tuple(key for _, key, _ in self._pending_first),
                        timeout_s=budget, until=until)
                else:       # only firsts left
                    self._harvester.wait_key(self._pending_first[0][1],
                                             timeout_s=budget, wake=wake)
            if wake is not None and wake.is_set():
                # a submission wants admission NOW; collect whatever
                # completed and hand control back (pipeline may sit
                # one step over depth for one iteration)
                n_steps += self._collect_ready(events)
                break
        # pacing calibration: completion spacing per decode step bounds the
        # device step time from ABOVE (reads add latency, never remove it),
        # so track the MINIMUM with slow upward drift. A mean/EMA here is
        # unstable: when reads are the bottleneck the spacing reflects the
        # read path, the estimate inflates, pacing launches slower, spacing
        # confirms the inflated estimate, and the pipeline starves
        # (observed: 4x throughput collapse).
        if n_steps > 0:
            now = time.monotonic()
            if self._last_harvest_t is not None:
                gap = (now - self._last_harvest_t) / n_steps
                if 0.0 < gap < 0.5:
                    if gap < self._est_step:
                        self._est_step = gap
                    else:
                        self._est_step = min(self._est_step * 1.02, gap)
            self._last_harvest_t = now
        elif not self._inflight:
            self._last_harvest_t = None  # idle: next spacing sample invalid
        return events

    def _head_blocking_first(self) -> Optional[int]:
        """The pending-first key gating the OLDEST in-flight step, or None.

        A decode step must not be consumed before its request's first
        token: processing it early advances slot_len/pending_token, and
        the late first would then rewind pending_token to the prompt's
        sampled token — feeding a stale input to the next host-value
        decode launch (observed as diverged generations)."""
        if not self._inflight or not self._harvester.is_done(
                self._inflight[0].seq):
            return None
        waiting = {id(r): k for r, k, _ in self._pending_first}
        for slot, req in self._inflight[0].active:
            if not req.finished and req.slot == slot and id(req) in waiting:
                return waiting[id(req)]
        return None

    def _collect_ready(self, events: list[StepEvent]) -> int:
        """Non-blocking: consume every completed result whose ordering
        constraints are satisfied — firsts in FIFO order, then steps in
        dispatch order while not gated by a pending first. Returns the
        number of decode steps consumed (pacing calibration)."""
        # firsts are per-request-independent results: release every
        # completed entry rather than stopping at the first not-done key
        done_entries, still = [], []
        for entry in self._pending_first:
            (done_entries if self._harvester.key_done(entry[1])
             else still).append(entry)
        for req, key, row in done_entries:
            if req.finished or row < 0:
                continue
            host = HostSample(np.asarray(self._harvester.get(key)))
            tok = int(host.tokens[row])
            req.pending_token = tok
            events += self._emit(req, tok, _lp_entry(host, row), first=True)
        if done_entries:
            self._pending_first = still
            done_keys = {k for _, k, _ in done_entries}
            for k in done_keys - {k for _, k, _ in still}:
                self.timeline.close(-1 - k, self._harvester.done_time(k))
                kind, rows = self._first_reads.pop(k)
                self._book_moe(kind, self._harvester.get(k), rows)
                self._harvester.discard_key(k)

        processed = -1
        n_steps = 0
        while self._inflight and self._harvester.is_done(self._inflight[0].seq):
            if self._head_blocking_first() is not None:
                break  # the step's request still awaits its first token
            step = self._inflight.popleft()
            res = self._harvester.get(step.seq)
            accept = None
            if isinstance(res, (tuple, list)):   # spec: (packs, accept)
                res, accept = res
                accept = np.asarray(accept)
            arr = np.asarray(res)                # [K, B, W]
            hosts = [HostSample(arr[k]) for k in range(arr.shape[0])]
            if not step.spec:
                self._book_moe("decode", arr, len(self.slots))
            processed = step.seq
            n_steps += 1
            consumed_total = wasted = max_consumed = 0
            spec_accepted = 0
            led_rows: list = []
            for slot, req in step.active:
                p = step.planned.get(slot, 0)
                if p <= 0:
                    continue
                waste_phase = ("spec_waste"
                               if step.spec and step.drafted
                               and slot in step.drafted else "early_exit")
                # a spec row consumes only its device-verified prefix: the
                # suffix rows after a draft mismatch hold tokens sampled
                # from logits conditioned on the REJECTED draft — garbage
                # by construction, discarded exactly like an early exit
                # (the rejected tail still counts as wasted window)
                cap = p if accept is None else min(p, int(accept[slot]))
                # skip slots whose request finished/aborted/was preempted
                # after this step launched — their sampled tokens are
                # garbage (and the whole window is wasted speculation)
                if req.finished or req.slot != slot:
                    wasted += p
                    led_rows.append((req, waste_phase, p))
                    continue
                consumed = 0
                for k in range(cap):
                    self.slot_len[slot] += 1
                    tok = int(hosts[k].tokens[slot])
                    req.pending_token = tok
                    events += self._emit(req, tok, _lp_entry(hosts[k], slot))
                    consumed += 1
                    if req.finished:
                        # the device masked this row right here too
                        # (stop id / budget); its tail is wasted window
                        break
                consumed_total += consumed
                wasted += p - consumed
                led_rows.append((req, "decode", consumed))
                if p > consumed:
                    led_rows.append((req, waste_phase, p - consumed))
                max_consumed = max(max_consumed, consumed)
                if step.spec and step.drafted and slot in step.drafted:
                    # accepted drafts = consumed tokens minus the one the
                    # plain path would have produced anyway
                    spec_accepted += max(0, consumed - 1)
            t_done = self._harvester.done_time(step.seq)
            self.timeline.close(step.dseq, t_done, led_rows,
                                window=arr.shape[0])
            if self.ledger is not None:
                self._collected.append(
                    ("spec" if step.spec else "decode", t_done))
            self.decode_dispatches += 1
            self.decode_tokens += consumed_total
            self.early_exit_steps += wasted
            self.steps_obs.append(max_consumed)
            if step.spec:
                drafted_n = sum((step.drafted or {}).values())
                self.spec_dispatches += 1
                self.spec_drafted_tokens += drafted_n
                self.spec_accepted_tokens += spec_accepted
                if self._spec is not None:
                    self._spec.policy.note(drafted_n, spec_accepted)
            elif self._spec is not None:
                self._spec.policy.tick()
        if processed >= 0:
            self._harvester.discard_upto(processed)
        return n_steps

    def _drain_async(self) -> list[StepEvent]:
        """Synchronize: harvest everything in flight (used before state
        inspection / shutdown)."""
        events = self._harvest(drain=True)
        for ev in events:
            self._hand_over(ev)
        self._book_emit()
        return events

    # ------------------------------------------------------------------
    # convenience
    # ------------------------------------------------------------------

    def generate(
        self,
        prompt: list[int],
        params: Optional[SamplingParams] = None,
        adapter: Optional[str] = None,
    ) -> list[int]:
        """Synchronous single-request generation (drives the scheduler)."""
        req = self.submit(prompt, params, adapter=adapter)
        while not req.finished:
            self.step()
        return req.output

    def score_prompt(self, prompt: list[int]):
        """Per-position prompt logprobs (the OpenAI ``echo+logprobs`` /
        vLLM ``prompt_logprobs`` surface): returns
        (token_logprobs [len-1], top_ids [len, K], top_logprobs [len, K])
        where token_logprobs[i] scores prompt[i+1]; K is always
        sampling.LOGPROB_TOPK (a per-request k would compile a separate
        executable per value — callers slice).

        Thread-safe against the engine loop: the scoring forward is
        cache-free (decoder.forward_score — writes go to a private dummy
        trash pool), touches no donated engine state, and the device
        serializes it between scheduler steps. Unsupported on seq-parallel
        meshes (the scoring pool is unsharded). Under multi-host the call
        is announced over the packed broadcast protocol (MSG_SCORE) like
        any other step, so every process enters the same forward_score
        executable and the pod group never deadlocks."""
        from llms_on_kubernetes_tpu.engine.sampling import LOGPROB_TOPK
        from llms_on_kubernetes_tpu.parallel.mesh import AXIS_SEQ

        if self.mesh is not None and int(self.mesh.shape.get(AXIS_SEQ, 1)) > 1:
            raise ValueError("prompt scoring is not supported under "
                             "sequence-parallel serving")
        if len(prompt) > self.config.max_model_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds max_model_len="
                f"{self.config.max_model_len}")
        n = len(prompt)
        # pad to a prefill bucket, or — past the largest bucket — to a
        # multiple of it: unbounded per-length shapes would compile (and
        # cache) one executable per distinct long-prompt length, and odd
        # lengths fall off the flash kernel onto the [T, T]-materializing
        # reference attention
        bucket = next((b for b in self.config.prefill_buckets if n <= b),
                      None)
        if bucket is None:
            big = max(self.config.prefill_buckets)
            bucket = -(-n // big) * big
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n] = prompt
        if self.config.multihost:
            # announce + ship the token row so follower pods enter the
            # same forward_score executable (SPMD — a coordinator-only
            # program over globally sharded params would deadlock)
            from llms_on_kubernetes_tpu.engine import multihost as mh

            self._mh_send(mh.MSG_SCORE, score=(bucket, n))
            mh.send_score_payload(tokens)
        nxt_lp, top_ids, top_lp = self._score_jit(
            self.params, self.model_config, jnp.asarray(tokens),
            jnp.asarray([n], jnp.int32), LOGPROB_TOPK)
        host = jax.device_get((nxt_lp, top_ids, top_lp))
        return (host[0][0, :n - 1].tolist(),
                host[1][0, :n].tolist(), host[2][0, :n].tolist())
