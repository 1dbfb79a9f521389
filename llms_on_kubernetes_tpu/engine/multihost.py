"""Multi-host engine stepping: one scheduler, N SPMD participants.

In multi-controller JAX every process must enter the same jitted
computation for its collectives to match (the partitioner's ICI
all-reduces span all hosts). So the coordinator (pod 0) cannot just run
``Engine.step()`` by itself while followers idle — followers would never
enter the program and the slice would deadlock.

Packed protocol (v2 — the TPU-native stand-in for the reference's NCCL
rendezvous, SURVEY §2.4 / §5 "Distributed communication backend"):

- The scheduler (admission, page allocation, sampling-parameter tables)
  runs ONLY on the coordinator; it is plain host Python.
- Every device call is announced by exactly ONE
  ``multihost_utils.broadcast_one_to_all`` of a fixed-shape int32 message
  (control word + the same packed arrays the single-host engine already
  builds for its packed executables). One broadcast = one DCN/ICI round
  per step — the old header+payload protocol paid two.
- ASYNC scheduling works across hosts: the decode input merge happens on
  device from the previous step's sampled tokens, so followers never need
  host values — they mirror the coordinator's call sequence and pass
  every decode step their own newest decode output and newest prefill
  output, as the coordinator does: the same global arrays by SPMD
  determinism. The packed rows' source column says which rows read them.
- A decode message enters the engine's ONE decode step
  (``_decode_multi_packed_step``) with the window K of the control word.
- Followers do no host reads and no allocation: page tables, lengths, and
  sampling parameters all ride inside the packed arrays.

Message layout (all int32; floats ride bitcast, as in the packed steps):

  ctrl[6]    = [op, k (prefill rows | decode window K), bucket, fsm_used,
                score_width, score_len]
  pre_tokens [admit_batch, max_bucket]   prefill/chunk token ids
  pre_packed [admit_batch, _CHK_COLS + pages_per_slot]
  dec_packed [max_decode_slots, _DEC_COLS + pages_per_slot]

Unused fields are zero; the buffers are small (tens of KB) next to a
step's compute, and a single fixed pytree keeps the broadcast one
compiled executable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

MSG_IDLE = 0      # follower receive stub only; the coordinator never sends it
MSG_PREFILL = 1
MSG_CHUNK = 2
MSG_DECODE = 3
MSG_SHUTDOWN = 4
# multimodal prefill: the control word announces it (tokens/packed ride the
# normal buffers), then ONE extra broadcast carries the pixel payload +
# mrope positions (engine._mm_execute runs identically on every process) —
# the common decode/prefill path stays a single broadcast
MSG_MM_PREFILL = 5
# grammar residency change: the control word announces it, then ONE extra
# broadcast ships the updated device tables (engine/grammar.py) — like the
# multimodal pixel payload, the common step path stays a single broadcast.
# Sent only when the resident-grammar SET changes (admission-time).
MSG_GRAMMAR = 6
# prompt scoring (echo+logprobs): the control word carries the padded
# width and true length in ctrl[4:6], then ONE extra broadcast ships the
# [1, width] token row (width can exceed max_bucket — scoring pads to a
# multiple of the largest bucket — so it can't ride pre_tokens). Followers
# enter the same forward_score executable and discard the result.
MSG_SCORE = 7

CTRL_LEN = 6


@dataclasses.dataclass(frozen=True)
class ProtoShapes:
    """Fixed message-buffer shapes, derivable from the engine + model
    configs on every process (both are part of the deployment spec,
    identical per pod)."""
    admit_batch: int
    max_bucket: int
    pre_width: int     # _CHK_COLS + pages_per_slot (covers prefill's too)
    num_slots: int
    dec_width: int     # _DEC_COLS + pages_per_slot
    # multimodal payload (0s when the model has no vision tower): every
    # dynamic-resolution grid holds the same pixel COUNT (fixed patch
    # budget), so images broadcast as flat fixed-size rows + their grids
    n_img_max: int = 0
    img_floats: int = 0   # pixels per image row: S^2 * p^2 * C
    mrope: bool = False
    # frames per pixel-buffer row: a video temporal patch is
    # temporal_patch_size real frames; one row holds exactly one image OR
    # one temporal patch, so total rows <= total blocks <= n_img_max
    mm_row_frames: int = 2
    # grammar device-table shapes (EngineConfig caps + model vocab);
    # only the MSG_GRAMMAR payload broadcast uses them
    g_rows: int = 0
    g_vocab: int = 0
    g_states: int = 0
    g_classes: int = 0
    # the window K a decode message announces: the pipelined scheduler's
    # decode_steps; the synchronous loop enters the step with K = 1
    decode_steps: int = 1

    @classmethod
    def from_engine_config(cls, cfg: Any,
                           model_config: Any = None) -> "ProtoShapes":
        from llms_on_kubernetes_tpu.engine.engine import _CHK_COLS, _DEC_COLS

        n_img = img_floats = 0
        mrope = False
        row_frames = 2
        vocab = 0
        if model_config is not None:
            vocab = model_config.vocab_size
            if model_config.vision is not None:
                v = model_config.vision
                n_img = cfg.max_images_per_request
                img_floats = v.image_size * v.image_size * v.num_channels
                mrope = model_config.mrope_section is not None
                row_frames = max(1, v.temporal_patch_size)
        return cls(
            admit_batch=cfg.admit_batch,
            max_bucket=max(cfg.prefill_buckets),
            pre_width=_CHK_COLS + cfg.pages_per_slot,
            num_slots=cfg.max_decode_slots,
            dec_width=_DEC_COLS + cfg.pages_per_slot,
            n_img_max=n_img, img_floats=img_floats, mrope=mrope,
            mm_row_frames=row_frames,
            g_rows=cfg.max_grammars, g_vocab=vocab,
            g_states=cfg.grammar_states, g_classes=cfg.grammar_classes,
            decode_steps=cfg.decode_steps if cfg.async_scheduling else 1,
        )

    def zeros(self) -> dict:
        return {
            "ctrl": np.zeros((CTRL_LEN,), np.int32),
            "pre_tokens": np.zeros((self.admit_batch, self.max_bucket), np.int32),
            "pre_packed": np.zeros((self.admit_batch, self.pre_width), np.int32),
            "dec_packed": np.zeros((self.num_slots, self.dec_width), np.int32),
        }

    def mm_zeros(self) -> dict:
        """The second (mm-only) broadcast: entry pixels flattened into
        block-aligned rows, per-entry (frames, H, W) shapes (frames=0 =>
        image), and the mrope position block."""
        return {
            "meta": np.zeros((1 + 3 * self.n_img_max,), np.int32),
            "pixels": np.zeros(
                (self.n_img_max, self.mm_row_frames * self.img_floats),
                np.float32),
            "pos3": np.zeros((3, self.max_bucket), np.int32),
        }

    def grammar_zeros(self) -> dict:
        """The second (MSG_GRAMMAR-only) broadcast: the full host-side
        grammar tables (int16 — a few MB at the default caps, sent only
        when the resident set changes)."""
        return {
            "class_of": np.zeros((self.g_rows, self.g_vocab), np.int16),
            "trans": np.zeros((self.g_states, self.g_classes), np.int16),
        }


def _broadcast(value):
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(value)


def send_message(
    shapes: ProtoShapes,
    op: int,
    *,
    pre_tokens: Optional[np.ndarray] = None,
    pre_packed: Optional[np.ndarray] = None,
    dec_packed: Optional[np.ndarray] = None,
    fsm_used: bool = False,
    score: "Optional[tuple[int, int]]" = None,
) -> None:
    """Coordinator: announce one device call in ONE broadcast.
    ``fsm_used`` tells followers to enter the grammar-constrained variant
    of the step executable (same trace decision as the coordinator).
    ``score`` = (padded width, true length) for MSG_SCORE — the payload
    broadcast that follows is shaped from the width."""
    msg = shapes.zeros()
    k = bucket = 0
    if pre_tokens is not None:
        k, bucket = pre_tokens.shape
        msg["pre_tokens"][:k, :bucket] = pre_tokens
        msg["pre_packed"][:k, :pre_packed.shape[1]] = pre_packed
    if dec_packed is not None:
        k = shapes.decode_steps
        msg["dec_packed"][:, :] = dec_packed
    msg["ctrl"][:4] = (op, k, bucket, int(fsm_used))
    if score is not None:
        msg["ctrl"][4:6] = score
    _broadcast(msg)


def receive_message(shapes: ProtoShapes) -> dict:
    """Follower: contribute zeros, receive the coordinator's message."""
    out = _broadcast(shapes.zeros())
    return {k: np.asarray(v) for k, v in out.items()}


def send_mm_payload(shapes: ProtoShapes, images: list,
                    pos3: "Optional[np.ndarray]") -> None:
    """Coordinator: ship a multimodal admission's pixels (+ mrope
    positions) in one broadcast right after its MSG_MM_PREFILL control.
    Entries are images [H, W, C] (meta frames=0) or videos [F, H, W, C];
    a video occupies F / mm_row_frames consecutive block-aligned rows."""
    msg = shapes.mm_zeros()
    msg["meta"][0] = len(images)
    row = 0
    for i, im in enumerate(images):
        video = im.ndim == 4
        f, h, w = (im.shape[0] if video else 0), im.shape[-3], im.shape[-2]
        msg["meta"][1 + 3 * i:4 + 3 * i] = (f, h, w)
        flat = np.asarray(im, np.float32).reshape(-1)
        n_rows = max(1, f // shapes.mm_row_frames)
        msg["pixels"][row:row + n_rows].reshape(-1)[:flat.size] = flat
        row += n_rows
    if pos3 is not None:
        msg["pos3"][:, :pos3.shape[-1]] = pos3
    _broadcast(msg)


def receive_mm_payload(shapes: ProtoShapes, channels: int,
                       bucket: int) -> "tuple[list, Optional[np.ndarray]]":
    """Follower: rebuild the admission's image/video list (per-entry
    dynamic grids) and the [3, bucket] mrope block (None for non-mrope
    models)."""
    out = _broadcast(shapes.mm_zeros())
    meta = np.asarray(out["meta"])
    pixels = np.asarray(out["pixels"])
    images = []
    row = 0
    for i in range(int(meta[0])):
        f, h, w = (int(x) for x in meta[1 + 3 * i:4 + 3 * i])
        if f:  # video
            n_rows = f // shapes.mm_row_frames
            flat = pixels[row:row + n_rows].reshape(-1)[:f * h * w * channels]
            images.append(flat.reshape(f, h, w, channels))
            row += n_rows
        else:
            images.append(
                pixels[row, :h * w * channels].reshape(h, w, channels))
            row += 1
    pos3 = np.asarray(out["pos3"])[:, :bucket] if shapes.mrope else None
    return images, pos3


def send_score_payload(tokens: np.ndarray) -> None:
    """Coordinator: ship the padded [1, width] score-token row right
    after its MSG_SCORE control word."""
    _broadcast(np.asarray(tokens, np.int32))


def receive_score_payload(width: int) -> np.ndarray:
    """Follower: receive the [1, width] token row (width from ctrl[4])."""
    return np.asarray(_broadcast(np.zeros((1, width), np.int32)))


def send_grammar_payload(shapes: ProtoShapes, class_h: np.ndarray,
                         trans_h: np.ndarray) -> None:
    """Coordinator: ship the full grammar tables right after MSG_GRAMMAR."""
    msg = shapes.grammar_zeros()
    msg["class_of"][:, :] = class_h
    msg["trans"][:, :] = trans_h
    _broadcast(msg)


def receive_grammar_payload(shapes: ProtoShapes) -> dict:
    out = _broadcast(shapes.grammar_zeros())
    return {k: np.asarray(v) for k, v in out.items()}


def follower_loop(engine: Any) -> None:
    """Run on pods 1..N-1: mirror the coordinator's call sequence forever.

    The engine instance holds the sharded params/cache (global arrays whose
    addressable shards live on this host's chips) and the same jitted
    packed executables; this loop feeds them the broadcast inputs. By SPMD
    determinism the follower's newest decode and prefill outputs
    (``engine._unread_toks`` / ``_unread_prefill_toks``, kept where the
    coordinator keeps its own) are the same global arrays the coordinator
    passes its decode steps.
    """
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine.engine import _CHK_COLS, _DEC_COLS, _PRE_COLS

    shapes = ProtoShapes.from_engine_config(engine.config,
                                            engine.model_config)
    pps = engine.config.pages_per_slot
    while True:
        m = receive_message(shapes)
        op, k, bucket, fsm_used = (int(x) for x in m["ctrl"][:4])
        if op == MSG_SHUTDOWN:
            return
        if op == MSG_IDLE:
            continue
        if op == MSG_GRAMMAR:
            # mirror the coordinator's residency change: same host tables,
            # same device arrays (engine._ensure_grammar/_upload_grammars)
            payload = receive_grammar_payload(shapes)
            engine._g_class_h = payload["class_of"]
            engine._g_trans_h = payload["trans"]
            if engine._fsm_state is None:
                engine._fsm_state = jnp.full(
                    (engine.config.max_decode_slots,), -1, jnp.int32)
            engine._g_dev = (jnp.asarray(engine._g_class_h),
                             jnp.asarray(engine._g_trans_h))
            continue
        if op == MSG_SCORE:
            # mirror the coordinator's forward_score entry (cache-free,
            # trash-pool writes) and discard the result — SPMD only needs
            # every process inside the same executable
            from llms_on_kubernetes_tpu.engine.sampling import LOGPROB_TOPK

            width, n = int(m["ctrl"][4]), int(m["ctrl"][5])
            toks = receive_score_payload(width)
            engine._score_jit(engine.params, engine.model_config,
                              jnp.asarray(toks), jnp.asarray([n], jnp.int32),
                              LOGPROB_TOPK)
            continue
        if op == MSG_MM_PREFILL:
            images, pos3 = receive_mm_payload(
                shapes, engine.model_config.vision.num_channels, bucket)
            _pack, engine._unread_prefill_toks = engine._mm_execute(
                images, m["pre_tokens"][:k, :bucket],
                m["pre_packed"][:k, :_PRE_COLS + pps],
                None if pos3 is None else pos3[None])
            continue
        fsm = engine._fsm_args() if fsm_used else None
        if op in (MSG_PREFILL, MSG_CHUNK):
            cols = (_PRE_COLS if op == MSG_PREFILL else _CHK_COLS) + pps
            tokens = jnp.asarray(m["pre_tokens"][:k, :bucket])
            packed = jnp.asarray(m["pre_packed"][:k, :cols])
            fn = engine._prefill_packed if op == MSG_PREFILL else engine._chunk_packed
            (_pack, engine._unread_prefill_toks, engine.k_pages,
             engine.v_pages, engine.token_counts, new_state,
             engine.conv_state) = fn(
                engine.params, engine.model_config, tokens, packed,
                engine.k_pages, engine.v_pages, engine.token_counts,
                engine._key, fsm, engine.conv_state,
            )
        elif op == MSG_DECODE:
            (_pack, engine._unread_toks, engine.k_pages, engine.v_pages,
             engine.token_counts, new_state,
             engine.conv_state) = engine._decode_multi(
                engine.params, engine.model_config, k,
                jnp.asarray(m["dec_packed"]), engine._unread_toks,
                engine._unread_prefill_toks, engine.k_pages, engine.v_pages,
                engine.token_counts, engine._key, fsm, engine.conv_state,
            )
        else:
            raise ValueError(f"unknown multihost op {op}")
        if new_state is not None:
            engine._fsm_state = new_state
