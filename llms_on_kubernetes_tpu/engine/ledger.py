"""Goodput ledger: per-request chip-time attribution + MFU/MBU accounting.

The engine batches many tenants into one device dispatch, fuses K-token
decode windows, speculates, and early-exits masked rows — so wall-clock
and per-stream latency no longer say where chip time actually went.
This module answers that question with an explicit accounting identity:

    attributed (prefill + decode) + wasted (spec_waste + early_exit)
      + idle  ==  ledger window (first launch -> last completion)

Every dispatch the engine launches is ONE record here
(:class:`Dispatch`): opened at the launch site, closed when its host read
lands, and booked in LAUNCH order — the order the device runs them in,
which is not the order the reads are collected in (a prefill's priority
read overtakes the decode step launched ahead of it). The busy interval
of dispatch N is the segment from the previous dispatch's completion (or
N's own launch, whichever is later) to N's completion, and N cannot have
completed after a dispatch launched later that has already been read —
segments never overlap, gaps between them are idle, and the sum conserves
wall time by construction (ci.sh gates this on the smoke run). A
dispatch's completion is what the engine's harvester saw of it: the
moment its result was ready on the device (a watcher thread waits on
every result in launch order) or its read landed, whichever came first;
a record that has neither takes the earliest read of anything launched
after it and says ``end_clamped``. The same
record is the dispatch span: what it waited behind, how long the device
held it, what the host was doing in the gap before it
(``GET /debug/engine`` lists the newest). Each segment is then split across the rows that rode
the dispatch, weighted by planned window tokens: consumed tokens bill
to the stream's ``prefill``/``decode`` phase, speculative rejected
tails to ``spec_waste``, and masked/abandoned rows to ``early_exit`` —
waste is still booked against the request and tenant that caused it,
but never counted as useful stream time.

The same segments answer a second question, latency, not billing: where
a request's time per output token went. A stream waits for the WHOLE of
every window it rides, for the prefills the device runs between its
windows, and for the engine thread to hand its tokens over
(:meth:`GoodputLedger.decode_account`, published on the request's
``decode`` span; the engine thread pays one append a row for it).

FLOPs/bytes ride the same records (2 * active-params per token for
compute; weight + KV-page traffic for memory), giving the ``llm_mfu_
ratio`` / ``llm_mbu_ratio`` gauges (Chowdhery et al., PaLM 2022). A CPU
has no peak in the table and reports neither (see
k8s/tpu-models/README.md "Goodput & chip-time accounting").

:class:`StepAnomalyDetector` watches the same per-dispatch durations
with an EWMA mean/variance + z-score test; the serving loop turns a
sustained anomaly into ONE bounded, rate-limited profiler capture
(``llm_auto_profile_total{reason="step_anomaly"}``) while the slowness
is still live.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import os
import threading
from typing import Any, Optional

PHASES = ("prefill", "decode", "spec_waste", "early_exit")
WASTE_PHASES = ("spec_waste", "early_exit")

# device_kind, exactly as the runtime reports it -> (peak dense bf16
# FLOP/s, peak HBM bytes/s, source). A v5e chip reports "TPU v5 lite"
# (read on the chip, jax 0.9.0 / libtpu 0.0.34); the other spellings are
# the marketing names the same runtimes have used for the same parts.
_V5E = (197e12, 819e9, "cloud.google.com/tpu/docs/v5e")
_V5P = (459e12, 2765e9, "cloud.google.com/tpu/docs/v5p")
_V6E = (918e12, 1640e9, "cloud.google.com/tpu/docs/v6e")
_PEAKS = {
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
    "TPU v4": (275e12, 1228e9, "cloud.google.com/tpu/docs/v4"),
    "TPU v3": (123e12, 900e9, "cloud.google.com/tpu/docs/v3"),
}
KINDS = ("prefill", "chunk", "decode", "spec")
IDLE_HOSTS = ("no_work", "compile", "scheduling")
# the rules a decode step is launched by (Engine.decode_launches,
# llm_decode_launches_total{when}): "timed" = a lead before the device
# was estimated to run dry, work still on it; "late" = the device was
# already free (the estimate overshot, or the host came late: the gap is
# the "scheduling" idle above); "admission" = directly behind the prefill
# whose sampled tokens it merges; "depth" = as soon as the pipeline had
# room, where the launch cannot be timed
DECODE_LAUNCH_RULES = ("timed", "late", "admission", "depth")
# what a decode window's rows ask of the sampler (Engine.decode_windows,
# llm_decode_windows_total{sampler}): "shaped" = a live row carries a
# presence or frequency penalty or a logit_bias entry, so every token step
# of the window keeps the penalty counts, applies the penalties and scatters
# the biases; "plain" = none does, and the window's steps skip all three
SAMPLERS = ("plain", "shaped")
# what the engine books of the expert layers (Engine.moe_stats,
# llm_moe_<stat>_total{kind}), per kind of dispatch and summed over its
# token steps and expert layers: experts HELD here that got at least one
# row / experts held / (token, expert) pairs routed, to any expert / those
# of them that fell on experts held here (all, unless the model holds a
# share: ModelConfig.experts_held) / rows of each layer's fullest held
# expert / of its mean held expert
MOE_STATS = ("experts_touched", "expert_slots", "routed_rows", "held_rows",
             "fullest_expert_rows", "mean_expert_rows")
# launched and not yet booked: a pipeline holds async_depth decode steps
# and the prefills of one admission round, a handful
MAX_OPEN = 64


def detect_peak() -> Optional[tuple[float, float]]:
    """(peak FLOP/s, peak bytes/s) for the local accelerator.

    LLMK_PEAK_TFLOPS + LLMK_PEAK_GBPS win (hardware the table has never
    heard of); else the device kind must be in ``_PEAKS``. An accelerator
    that is not is an error naming the string it reports — a default peak
    would put a wrong MFU/MBU on every dashboard without complaint. The
    CPU platform has no peak: None, and the ledger reports no MFU/MBU."""
    flops = os.environ.get("LLMK_PEAK_TFLOPS")
    gbps = os.environ.get("LLMK_PEAK_GBPS")
    if flops and gbps:
        return float(flops) * 1e12, float(gbps) * 1e9
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in _PEAKS:
        raise RuntimeError(
            f"no peak FLOP/s and HBM bytes/s known for accelerator "
            f"device_kind {dev.device_kind!r}: add it to engine/ledger.py "
            f"_PEAKS, or set LLMK_PEAK_TFLOPS and LLMK_PEAK_GBPS")
    return _PEAKS[dev.device_kind][:2]


def _active_params(cfg: Any) -> int:
    """Parameters touched per token: for MoE, only the routed experts'
    share of the expert MLPs counts (num_params sums all experts)."""
    n = int(cfg.num_params)
    if getattr(cfg, "is_moe", False) and cfg.num_experts > 0:
        d, f, L = cfg.hidden_size, cfg.expert_width, cfg.num_moe_layers
        # of a token's experts, the share that is held here computes here
        held = cfg.num_held_experts
        all_mlp = 3 * d * f * held
        active_mlp = 3 * d * f * cfg.num_experts_per_tok * held \
            // cfg.num_experts
        n -= L * (all_mlp - active_mlp)
    return n


class StepAnomalyDetector:
    """EWMA + z-score detector over per-dispatch device time.

    ``observe(duration_s, now)`` returns True exactly when a trigger
    fires: z-score above ``threshold`` for ``sustain`` consecutive
    samples, after ``warmup`` samples established a baseline, and not
    within ``cooldown_s`` of the previous trigger (the rate limit the
    auto-profiler relies on). Anomalous samples do NOT update the EWMA —
    otherwise a sustained slowdown would teach the baseline to accept
    itself before the sustain count is reached."""

    def __init__(self, threshold: float = 4.0, sustain: int = 3,
                 cooldown_s: float = 600.0, warmup: int = 12,
                 alpha: float = 0.05):
        self.threshold = float(threshold)
        self.sustain = max(1, int(sustain))
        self.cooldown_s = float(cooldown_s)
        self.warmup = max(2, int(warmup))
        self.alpha = float(alpha)
        self._mean = 0.0
        self._var = 0.0
        self._n = 0
        self._streak = 0
        self._cooldown_until: Optional[float] = None
        self.triggers = 0

    def zscore(self, x: float) -> float:
        if self._n < self.warmup:
            return 0.0
        # variance floor: a perfectly steady baseline (tests, mocked
        # clocks) must still register a spike instead of dividing by ~0
        std = math.sqrt(max(self._var, (0.05 * self._mean) ** 2, 1e-12))
        return (x - self._mean) / std

    def observe(self, duration_s: float, now: float) -> bool:
        z = self.zscore(duration_s)
        anomalous = self._n >= self.warmup and z > self.threshold
        if anomalous:
            self._streak += 1
        else:
            self._streak = 0
            d = duration_s - self._mean
            a = self.alpha if self._n >= self.warmup else max(
                self.alpha, 1.0 / (self._n + 1))
            self._mean += a * d
            self._var = (1.0 - a) * (self._var + a * d * d)
            self._n += 1
        if self._streak < self.sustain:
            return False
        if (self._cooldown_until is not None
                and now < self._cooldown_until):
            return False
        self._cooldown_until = now + self.cooldown_s
        self._streak = 0
        self.triggers += 1
        return True


@dataclasses.dataclass
class Dispatch:
    """One device dispatch, from its launch to its booked segment.

    The engine thread opens it at the launch site, marks it launched when
    the jitted call returns (the work is then in the device's queue) and
    closes it when its host read lands; the ledger fills the rest when it
    books the record, in launch order. Each chunk of a chunked prefill
    is a record and is read (the pipelined scheduler launches a decode
    window between two of them); the synchronous scheduler's chain, of
    which only the last dispatch is read, is one record."""
    seq: int
    kind: str                    # one of KINDS
    name: str                    # the jitted step's name
    shape: str                   # rows x bucket, or K x slots active
    t_call: float                # host entered the jitted call
    rows: Optional[list] = None  # [(request, phase, tokens)]; dropped when booked
    window: int = 1
    after_no_work: bool = False  # the engine had run out of work before it
    t_launch: float = 0.0        # the call returned: enqueued on the device
    enqueue_ms: float = 0.0      # host time inside the call; a re-trace shows here
    retraced: bool = False       # the process compiled or hit its cache meanwhile
    closed: bool = False
    t_done: Optional[float] = None   # read landed; None: nobody reads it
    # its end is the read of something launched AFTER it (its own read
    # landed later, or never): device_ms is then an upper bound that holds
    # the time of whatever ran up to that read, booked at 0 in its turn
    end_clamped: bool = False
    # booked:
    seg_start: float = 0.0       # when the device was free for it
    behind_ms: float = 0.0       # seg_start - t_launch: queued behind earlier ones
    device_ms: float = 0.0       # t_done - seg_start
    idle_before_ms: float = 0.0  # the device's gap before seg_start
    idle_host: str = ""          # one of IDLE_HOSTS, when idle_before_ms > 0
    tokens: int = 0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    # positions the Mamba layers' scan or step ran over, padding included,
    # and idle rows where the step visits them (0: the model has no such
    # layer; Engine._dispatch sets it); ``tokens`` is the real ones among
    # them
    ssm_positions: int = 0
    # a decode window's sampler, as the host booked it from the packed rows
    # (Engine._book_sampler): "plain" | "shaped"; "" for every other kind
    sampler: str = ""

    def to_dict(self, t0: float) -> dict:
        """JSON view; times in ms since ``t0`` (the ledger's first launch)."""
        d = {"seq": self.seq, "kind": self.kind, "name": self.name,
             "shape": self.shape, "tokens": self.tokens,
             "launch_ms": round((self.t_launch - t0) * 1000.0, 3),
             "enqueue_ms": round(self.enqueue_ms, 3),
             "retraced": self.retraced,
             "behind_ms": round(self.behind_ms, 3),
             "device_ms": round(self.device_ms, 3),
             "idle_before_ms": round(self.idle_before_ms, 3)}
        if self.idle_host:
            d["idle_host"] = self.idle_host
        if self.end_clamped:
            d["end_clamped"] = True
        if self.ssm_positions:
            d["ssm_tokens"] = self.tokens
            d["ssm_positions"] = self.ssm_positions
        if self.sampler:
            d["sampler"] = self.sampler
        return d


class DispatchTimeline:
    """The device's queue as the engine thread knows it: every dispatch
    launched and not yet booked, in launch order, and what the booked
    ones measured.

    The engine keeps one whether or not chip time is attributed
    (:class:`GoodputLedger` is this plus the accounting): the scheduler's
    clock is read off it. Booking a record segments the device's time as
    the module docstring says and yields ``device_ms``, and the timeline
    keeps, per (kind, shape), the device time such a dispatch LAST took,
    tracked from below: a segment's end is a thread waking from
    ``block_until_ready``, so a sample comes out too long when its own
    stamp is late, and the minimum with a slow drift upwards follows the
    device and errs short, which makes :meth:`free_at` err EARLY, the
    safe side for a launch timed against it. (The shortest of the last
    four samples was tried on the chip: under load every recent stamp is
    late, the estimate rises with them, launches come late, and two of
    seven runs grew a tail.) A late stamp also makes the NEXT segment
    short by as much (seen on the chip: a 67 ms window booked at 1.9
    behind one booked at 130, a 26.5 ms prefill at 2.6): so a segment
    that comes out short and started on the previous one's end is given
    back what that one ran over its own estimate, up to its own; where
    that cannot be known (a shape's first sample, or the previous
    shape's) the short sample stays until the drift has undone it."""

    # an estimate may rise by this factor a sample, and falls at once
    DRIFT = 1.02

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # launched and not yet booked, in launch order
        self._open: "collections.deque[Dispatch]" = collections.deque()
        # (kind, shape) -> seconds; survives reset(), like the detector's
        # baseline: the device does not forget how long a shape takes
        self._est: dict[tuple[str, str], float] = {}
        self._zero()

    def _zero(self) -> None:
        self._last_complete: Optional[float] = None
        # the record booked last ended on a LATER launch's read: the time
        # of whatever ran up to that read is in it, and the next record
        # books at 0, which says nothing about its shape
        self._prev_clamped = False
        # seconds the record booked last ran over its shape's estimate
        self._prev_excess = 0.0
        self.lost = 0       # dropped unbooked: their reads never came
        # the newest launch (t_call) among the records dropped unbooked:
        # a request whose prefill was launched before it may have ridden
        # one, so its decode account is not made up (decode_account)
        self._lost_after = float("-inf")
        self._open.clear()

    # -- recording (engine thread) -------------------------------------

    def open(self, seq: int, kind: str, name: str, shape: str,
             t_call: float, rows: Optional[list] = None,
             after_no_work: bool = False) -> Dispatch:
        """A dispatch is about to be launched. ``rows`` may come now (a
        prefill knows them) or with :meth:`close` (a fused decode window
        knows consumed against wasted only at harvest)."""
        rec = Dispatch(seq, kind, name, shape, t_call, rows=rows,
                       after_no_work=after_no_work)
        with self._lock:
            # a head whose read never came (it raised on the way) must not
            # hold everything launched after it unbooked for ever: with
            # this many behind it the device is long past it. It is
            # dropped; its time falls to the next segment, or to idle
            while (len(self._open) >= MAX_OPEN
                   and not self._open[0].closed):
                self._dropped(self._open.popleft())
                self.lost += 1
                self._book_ready()
            self._open.append(rec)
        return rec

    def launched(self, rec: Dispatch, t_launch: float,
                 retraced: bool = False) -> None:
        """The jitted call returned: ``rec`` is in the device's queue."""
        with self._lock:
            rec.t_launch = t_launch
            rec.enqueue_ms = max(0.0, t_launch - rec.t_call) * 1000.0
            rec.retraced = retraced
            for req, phase, _w in rec.rows or ():
                if (phase == "prefill" and req is not None and getattr(
                        req, "prefill_launched_at", None) is None):
                    req.prefill_launched_at = t_launch

    def close(self, seq: int, t_done: Optional[float],
              rows: Optional[list] = None, window: int = 1) -> None:
        """``seq``'s host read landed at ``t_done``; None for a dispatch
        nobody reads (a resumed request's re-prefill), which the host knows
        to be done only when the next one it does read is. Books every
        record that is now the oldest open one and closed.

        ``rows`` is ``[(request_or_None, phase, weight_tokens), ...]``
        — one entry per (slot, phase) share of the dispatch; a fused
        window row typically contributes a ``decode`` entry for its
        consumed tokens and a waste entry for its planned-minus-consumed
        tail. ``window`` is the fused step count K (weight-streaming
        traffic scales with it, not with batch width)."""
        with self._lock:
            rec = next((r for r in self._open if r.seq == seq), None)
            if rec is None or rec.closed:
                return
            rec.closed = True
            rec.t_done = t_done
            rec.window = window
            if rows is not None:
                rec.rows = rows
            if t_done is not None:
                # the newest read before its first token: a chunked
                # prompt's last chunk (each chunk is a record and is read)
                for req, phase, _w in rec.rows or ():
                    if (phase == "prefill" and req is not None and getattr(
                            req, "first_token_at", None) is None):
                        req.prefill_read_at = t_done
            self._book_ready()

    def _book_ready(self) -> None:
        """Book every record that is the oldest open one and closed."""
        while self._open and self._open[0].closed:
            head = self._open[0]
            # an in-order device cannot finish the head after a later
            # launch that has already been read (a first token's read,
            # collected before the decode step launched ahead of it)
            done = [r.t_done for r in itertools.islice(self._open, 1, None)
                    if r.closed and r.t_done is not None]
            if head.t_done is not None:
                done.append(head.t_done)
            if not done:
                break      # unread, and nothing launched after it read yet
            self._open.popleft()
            head.end_clamped = head.t_done is None or min(done) < head.t_done
            self._book(head, min(done))

    def abandon(self, seq: Optional[int] = None) -> None:
        """Forget open records whose reads will never come: one whose
        launch raised (``seq``), or all of them (a wedged device)."""
        with self._lock:
            if seq is None:
                for rec in self._open:
                    self._dropped(rec)
                self._open.clear()
            else:
                self._open = collections.deque(
                    r for r in self._open if r.seq != seq)
                self._book_ready()

    def _dropped(self, rec: Dispatch) -> None:
        """``rec`` leaves unbooked though it may have run (a launch that
        raised never did: abandon(seq) does not come here)."""
        self._lost_after = max(self._lost_after, rec.t_call)

    def _book(self, rec: Dispatch, t_done: float) -> float:
        """Segment ``rec``: when the device was free for it, how long it
        held it, what gap came before. Returns the segment's seconds."""
        if self._last_complete is None:
            seg_start = rec.t_launch
        else:
            seg_start = max(rec.t_launch, self._last_complete)
            idle = max(0.0, seg_start - self._last_complete) * 1000.0
            if idle > 0.0:
                rec.idle_before_ms = idle
                rec.idle_host = ("no_work" if rec.after_no_work
                                 else "compile" if rec.retraced
                                 else "scheduling")
        dur = max(0.0, t_done - seg_start)
        if self._last_complete is None or t_done > self._last_complete:
            self._last_complete = t_done
        rec.t_done = t_done
        rec.seg_start = seg_start
        rec.behind_ms = (seg_start - rec.t_launch) * 1000.0
        rec.device_ms = dur * 1000.0
        key = (rec.kind, rec.shape)
        est = self._est.get(key)
        if dur > 0.0 and not rec.end_clamped and not self._prev_clamped:
            sample = dur
            if est is not None and dur < est and rec.idle_before_ms == 0.0:
                # short, and its start was the end stamped before it: it
                # is given back what that one ran over, up to the estimate
                sample = min(est, dur + self._prev_excess)
            self._est[key] = (sample if est is None or sample < est
                              else min(est * self.DRIFT, sample))
        self._prev_clamped = rec.end_clamped
        self._prev_excess = (max(0.0, dur - est)
                             if est is not None and not rec.end_clamped
                             else 0.0)
        rec.rows = None     # a record must not keep requests alive
        return dur

    def reset(self) -> None:
        """Zero all accounting (bench measurement windows exclude warmup
        dispatches this way). What was learned of the device survives
        (the per-shape estimates, the detector's baseline) — forgetting
        it would re-open the warmup window."""
        with self._lock:
            self._zero()

    # -- reading --------------------------------------------------------

    def estimate(self, kind: str, shape: str) -> Optional[float]:
        """Seconds the device last held a dispatch of this kind and shape
        (from below); None for one that never ran."""
        return self._est.get((kind, shape))

    def estimates_view(self) -> dict:
        """``"kind shape" -> ms``, for ``GET /debug/engine``."""
        with self._lock:
            return {f"{kind} {shape}": round(s * 1000.0, 3)
                    for (kind, shape), s in sorted(self._est.items())}

    def free_at(self, now: float, done: dict) -> Optional[tuple[float, bool]]:
        """When the device runs out of the work launched so far.

        ``done`` maps the seq of an open record to the time its result
        was complete on the device, for those the harvester has seen and
        the engine has not collected yet. Returns ``(t, busy)``: with
        ``busy`` the newest completion known plus the estimates of
        everything launched after it (the one on the device now cannot
        end before ``now``); without, nothing is ahead and ``t`` is when
        the device went free, or ``now`` where nothing was ever launched.
        None where a dispatch still ahead has a shape that never ran."""
        with self._lock:
            free = self._last_complete
            busy = unknown = False
            for rec in self._open:
                t = rec.t_done if rec.closed else None
                if t is None:
                    t = done.get(rec.seq)
                if t is not None:
                    # in order: whatever was launched before it is done too
                    free, busy, unknown = t, False, False
                    continue
                est = self._est.get((rec.kind, rec.shape))
                if est is None:
                    busy = unknown = True
                    continue
                end = (rec.t_launch if free is None
                       else max(free, rec.t_launch)) + est
                free = end if busy else max(end, now)
                busy = True
        if unknown:
            return None
        return (now if free is None else free), busy


class GoodputLedger(DispatchTimeline):
    """Chip-time attribution for one engine (see module docstring), on
    the engine's :class:`DispatchTimeline`.

    All mutation happens on the engine thread via :meth:`open` /
    :meth:`launched` / :meth:`close`; readers (the serving loop's metrics
    drain, bench, /metrics callbacks, /debug/engine) take the same lock
    through :meth:`snapshot` / :meth:`utilization` /
    :meth:`dispatches_view` / :meth:`decode_account`, so a scrape never
    sees a half-applied record.
    """

    def __init__(self, model_config: Any,
                 detector: Optional[StepAnomalyDetector] = None,
                 peak_flops: Optional[float] = None,
                 peak_bytes_s: Optional[float] = None):
        if peak_flops is None or peak_bytes_s is None:
            detected = detect_peak() or (None, None)
            peak_flops = peak_flops or detected[0]
            peak_bytes_s = peak_bytes_s or detected[1]
        # None (a CPU): no MFU/MBU is reported
        self.peak_flops = peak_flops
        self.peak_bytes_s = peak_bytes_s
        params = _active_params(model_config)
        dtype_bytes = 2 if "16" in str(model_config.dtype) else 4
        # compute: the standard 2*N MAC count per token (PaLM appendix B;
        # attention-score FLOPs are context-dependent and O(few %) at
        # serving batch sizes, so the weight term is the estimate)
        self.flops_per_token = 2.0 * params
        self.param_bytes = float(params * dtype_bytes)
        # KV traffic per token-step: one K+V page-write plus (amortized)
        # the read of its own history — bounded below by the write
        cache_heads, cache_width = model_config.cache_row
        self.kv_bytes_per_token = float(
            (1 if model_config.is_mla else 2) * model_config.num_attn_layers
            * cache_heads * cache_width * dtype_bytes)

        self.detector = detector
        # booked, newest last: the rolling window the MFU/MBU gauges are
        # computed over, and what /debug/engine lists
        self._records: "collections.deque[Dispatch]" = collections.deque(
            maxlen=2048)
        super().__init__()

    def _zero(self) -> None:
        super()._zero()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.dispatches = 0
        self.busy_ms = 0.0
        self.idle_ms = 0.0
        self.phase_ms: dict[str, float] = {p: 0.0 for p in PHASES}
        self.tenant_ms: dict[tuple[str, str], float] = {}
        # per kind: [dispatches, device ms, behind ms, enqueue ms]
        self.kind_stats: dict[str, list] = {k: [0, 0.0, 0.0, 0.0]
                                            for k in KINDS}
        self.idle_host_ms: dict[str, float] = {h: 0.0 for h in IDLE_HOSTS}
        self.flops = 0.0
        self.hbm_bytes = 0.0
        self.decode_tokens = 0
        self.prefill_tokens = 0
        self.anomaly_events = 0
        self._anomaly_pending = False
        self._records.clear()

    def _book(self, rec: Dispatch, t_done: float) -> float:
        rows = [(r, ph, int(w)) for r, ph, w in rec.rows or () if w > 0]
        tok_w = sum(w for _r, _ph, w in rows)
        total_w = tok_w
        if not rows:
            # a dispatch whose every row was dropped (all slots finished
            # mid-flight) still burned chip time — book it as waste so
            # the conservation identity keeps holding
            rows = [(None, "early_exit", 1)]
            total_w = 1
        dur = super()._book(rec, t_done)
        if self.t_first is None:
            self.t_first = rec.t_launch
        if rec.idle_before_ms > 0.0:
            self.idle_ms += rec.idle_before_ms
            self.idle_host_ms[rec.idle_host] += rec.idle_before_ms
        self.t_last = self._last_complete
        self.dispatches += 1
        self.busy_ms += dur * 1000.0
        kind = self.kind_stats.setdefault(rec.kind, [0, 0.0, 0.0, 0.0])
        kind[0] += 1
        kind[1] += rec.device_ms
        kind[2] += rec.behind_ms
        kind[3] += rec.enqueue_ms

        for req, phase, w in rows:
            share_ms = (dur * 1000.0 * w / total_w) if total_w else 0.0
            self.phase_ms[phase] += share_ms
            tenant = getattr(req, "tenant", "") or ""
            key = (tenant, phase)
            self.tenant_ms[key] = self.tenant_ms.get(key, 0.0) + share_ms
            if req is not None:
                req.chip_ms[phase] = req.chip_ms.get(phase, 0.0) + share_ms
            if phase == "decode":
                self.decode_tokens += w
                # it consumed a token of this dispatch: the seq is what
                # decode_account finds the record by (once a dispatch: a
                # row has one "decode" entry)
                rides = getattr(req, "ride_seqs", None)
                if rides is not None:
                    rides.append(rec.seq)
            elif phase == "prefill":
                self.prefill_tokens += w
                if (req is not None and getattr(
                        req, "prefill_started_at", None) is None):
                    req.prefill_started_at = rec.seg_start

        # planned rows are computed whether or not the stream keeps
        # them — wasted FLOPs are the whole point of measuring
        rec.tokens = tok_w
        rec.flops = self.flops_per_token * tok_w
        rec.hbm_bytes = (self.param_bytes * max(1, int(rec.window))
                         + self.kv_bytes_per_token * tok_w)
        self.flops += rec.flops
        self.hbm_bytes += rec.hbm_bytes
        self._records.append(rec)

        if self.detector is not None and dur > 0.0:
            if self.detector.observe(dur, t_done):
                self.anomaly_events += 1
                self._anomaly_pending = True
        return dur

    def take_anomaly(self) -> bool:
        """True once per detector trigger (serving-loop poll)."""
        with self._lock:
            pending, self._anomaly_pending = self._anomaly_pending, False
            return pending

    # -- reading (any thread) ------------------------------------------

    def utilization(self, window_s: float = 60.0,
                    now: Optional[float] = None
                    ) -> Optional[tuple[float, float]]:
        """(MFU, MBU) over the trailing ``window_s`` of dispatches; None
        where the device has no peak (a CPU)."""
        if self.peak_flops is None or self.peak_bytes_s is None:
            return None
        with self._lock:
            if not self._records:
                return 0.0, 0.0
            t_hi = now if now is not None else self._records[-1].t_done
            lo = t_hi - window_s
            ent = [r for r in self._records if r.t_done >= lo]
            if not ent:
                return 0.0, 0.0
            elapsed = max(t_hi - min(r.seg_start for r in ent), 1e-9)
            mfu = sum(r.flops for r in ent) / (self.peak_flops * elapsed)
            mbu = sum(r.hbm_bytes for r in ent) / (
                self.peak_bytes_s * elapsed)
            return min(mfu, 1.0), min(mbu, 1.0)

    def decode_account(self, req: Any, t0: float, t1: float
                       ) -> Optional[dict]:
        """Where a request's token gap went between its first token's
        hand-over ``t0`` and its last one's ``t1``, in seconds, from the
        booked records (any thread; where a trace is finalized, never the
        engine thread: that one pays an append a row in :meth:`_book`).

        ``done`` is the booked completion of the last dispatch in which
        the request consumed a token, clipped into ``[t0, t1]``; what
        follows it is the hand-over of its last window (span
        ``decode.emit``). ``[t0, done]`` is tiled by the device's
        segments and idle gaps, each clipped to it: ``ride`` the WHOLE
        segments of the decode and spec dispatches it consumed a token
        of (once each, not its share by rows), ``prefill`` the segments
        of prefill and chunk dispatches (other requests' first tokens
        and, after a preemption, its own re-prefill), ``other`` the rest
        (decode windows it did not ride, idle gaps), ``idle`` the idle
        part of ``other``. None, and nothing guessed, where the ring no
        longer reaches back to ``t0`` or a record launched since the
        request's prefill was dropped unbooked. A window consumed and
        not booked by now (an older launch still unread) is not in
        ``done``: its time reads as hand-over."""
        rides = getattr(req, "ride_seqs", None)
        launched = getattr(req, "prefill_launched_at", None)
        if rides is None or launched is None:
            return None
        with self._lock:
            if self._lost_after >= launched:
                return None
            recs = list(self._records)
        if not recs or recs[0].seg_start > t0:
            return None
        # newest first, back to the record the device finished before t0
        # (seg_start never falls and no t_done lies past a later one's)
        window = []
        for rec in reversed(recs):
            if max(rec.seg_start, rec.t_done) <= t0:
                break
            window.append(rec)
        riding = set(rides)
        done = max((r.t_done for r in window if r.seq in riding),
                   default=t0)
        done = min(max(done, t0), t1)
        ride = prefill = idle = 0.0
        for rec in window:
            idle += max(0.0, min(rec.seg_start, done) - max(
                rec.seg_start - rec.idle_before_ms / 1000.0, t0))
            dur = min(rec.t_done, done) - max(rec.seg_start, t0)
            if dur <= 0.0:
                continue
            if rec.kind in ("prefill", "chunk"):
                prefill += dur
            elif rec.seq in riding:
                ride += dur
        other = max(0.0, (done - t0) - ride - prefill)
        return {"done": done, "ride": ride, "prefill": prefill,
                "other": other, "idle": min(idle, other)}

    def dispatches_view(self, limit: int = 64) -> list[dict]:
        """The newest ``limit`` booked records, oldest first."""
        with self._lock:
            recs = list(self._records)[-limit:] if limit > 0 else []
            t0 = self.t_first or 0.0
            return [r.to_dict(t0) for r in recs]

    def snapshot(self) -> dict:
        """Cumulative totals (ms / counts), for delta-draining into
        metrics and for bench's conservation check."""
        with self._lock:
            attributed = self.phase_ms["prefill"] + self.phase_ms["decode"]
            wasted = sum(self.phase_ms[p] for p in WASTE_PHASES)
            window_ms = ((self.t_last - self.t_first) * 1000.0
                         if self.t_first is not None else 0.0)
            return {
                "phase_ms": dict(self.phase_ms),
                "attributed_ms": attributed,
                "wasted_ms": wasted,
                "idle_ms": self.idle_ms,
                "idle_host_ms": dict(self.idle_host_ms),
                "busy_ms": self.busy_ms,
                "window_ms": window_ms,
                "dispatches": self.dispatches,
                "lost": self.lost,
                "kinds": {k: {"dispatches": v[0], "device_ms": v[1],
                              "behind_ms": v[2], "enqueue_ms": v[3]}
                          for k, v in self.kind_stats.items()},
                "flops": self.flops,
                "hbm_bytes": self.hbm_bytes,
                "decode_tokens": self.decode_tokens,
                "prefill_tokens": self.prefill_tokens,
                "anomaly_events": self.anomaly_events,
                "tenant_ms": dict(self.tenant_ms),
            }
