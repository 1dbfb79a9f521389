"""On-device batched token sampling.

One fused function handles the whole decode batch with PER-SLOT sampling
parameters (temperature / top-k / top-p as [B] arrays), so heterogeneous
requests share one compiled step — the continuous-batching analogue of what
the reference's vLLM image did per sequence (SURVEY §2.3 row 1).

TPU-first: everything stays on device inside the jitted decode step; only the
sampled token ids ([B] int32) come back to the host each step. Greedy is
expressed as temperature==0 via masking, not Python branching, so one
executable covers all modes.

Penalties and ``logit_bias`` are paid for by the token steps that hold a
row asking for one, and by no other. They are the rare request (an OpenAI
client leaves them at their defaults), and each is work over [slots, vocab]:
the counts' update, the penalty arithmetic reading the counts, the bias
scatter and the copy of the logits it forces (about 0.5 ms of a 10 ms token
step at 128 x 65,536 on a v5e). ``sample(..., shaped=)`` takes a traced
scalar (``engine._window_asks`` of the decode window's own packed rows: a
live row with a nonzero penalty or a bias id) and puts the transforms AND
the candidate extraction in one ``lax.cond``, so only [B, 64] and [B, 1]
arrays leave it: still one executable, no flag, and the same bits for every
row on either branch. The prompt paths, once a prompt, always shape.

Top-k/top-p work on a FIXED top-MAX_CANDIDATES candidate set. On TPU the
set is extracted with ``lax.approx_max_k`` (the hardware-native bucketed
reduction; exact ``lax.top_k`` measured 2.6 ms/step for a 128K vocab on
v5e, approx ~0) — its ~0.95 recall means a true top-i candidate can
occasionally be replaced by the next-best one from its bucket, for BOTH
the top_k filter and the top-p nucleus. Greedy is always exact: the
global argmax is provably rank 0 of approx_max_k's output (it is its own
bucket's maximum, and the cross-bucket top-k is exact). On CPU the
extraction is exact ``lax.top_k``. The candidate-set cap itself is a
hard bound: top_k > MAX_CANDIDATES is REJECTED at Engine.submit() (400
at the API), and top-p loses only the tail mass beyond 64 tokens — the
same tradeoff TPU serving stacks standardly make. The categorical draw
uses the Gumbel trick on the masked, renormalized candidate logits.

Reproducibility caveat: because the TPU path extracts candidates with
``approx_max_k`` and the CPU path with exact ``top_k``, a SEEDED non-greedy
request is reproducible within a backend but not necessarily ACROSS
CPU/TPU. Set ``LLMK_EXACT_SAMPLING=1`` to force exact ``lax.top_k`` on TPU
(costs ~2.6 ms/step at 128K vocab) when cross-backend determinism matters
more than throughput.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38
# Sampling candidate pool per slot. top_k values above this are REJECTED
# at the API layer (400) — silent clamping would change the sampling
# semantics the client asked for; top-p nucleus truncation beyond the pool
# drops ~zero probability mass.
MAX_CANDIDATES = 64
# Top alternatives returned per sampled token (OpenAI `logprobs`/
# `top_logprobs` caps at 5; 8 leaves headroom and rides the same
# device->host read as the token ids).
LOGPROB_TOPK = 8


def _shape_logits(logits, penalties, bias):
    """The optional logit transforms on float32 ``logits`` [B, V]: the
    penalties over ``counts``, then the ``logit_bias`` scatter. For a row
    with zero penalties and no bias entry both are the identity
    (``x - 0*... - 0*...``, ``+ 0.0``)."""
    B = logits.shape[0]
    if penalties is not None:
        presence, frequency, counts = penalties
        c = counts.astype(jnp.float32)
        logits = logits - presence[:, None] * (c > 0) - frequency[:, None] * c
    if bias is not None:
        b_ids, b_vals = bias
        rows = jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.int32)[:, None], b_ids.shape)
        # padding id -1 would WRAP to column V-1 (jax normalizes negative
        # indices before mode="drop" applies — verified), so zero the
        # padded values explicitly; mode="drop" still guards any
        # out-of-range positive id
        b_vals = jnp.where(b_ids >= 0, b_vals.astype(jnp.float32), 0.0)
        logits = logits.at[rows, jnp.maximum(b_ids, 0)].add(
            b_vals, mode="drop")
    return logits


def _candidates(logits, allowed):
    """(cand_logits [B, C], cand_idx [B, C], lse [B, 1]) of float32
    ``logits`` [B, V]: the candidate set, sorted descending, and the
    log-sum-exp over the FULL vocabulary (so probabilities and the top-p
    cut are computed against the true distribution, not the truncated
    one). Everything of the sampler that reads a [B, V] array is in here
    or in ``_shape_logits``; what comes out is [B, 64] and [B, 1]."""
    V = logits.shape[1]
    if allowed is not None:
        logits = jnp.where(allowed, logits, NEG_INF)
    C = min(MAX_CANDIDATES, V)
    # TPU: approx_max_k is the hardware-native bucketed reduction (exact
    # top_k measured 2.6 ms/step at 128K vocab; approx ~free). Recall
    # caveats and the greedy-exactness argument: module docstring.
    exact = os.environ.get("LLMK_EXACT_SAMPLING", "0") == "1"
    if jax.default_backend() == "tpu" and V > 4 * C and not exact:
        cand_logits, cand_idx = jax.lax.approx_max_k(logits, C)
    else:
        cand_logits, cand_idx = jax.lax.top_k(logits, C)     # [B, C] each
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)   # [B, 1]
    # the barrier keeps XLA from hoisting the branches' common tail out of
    # sample()'s conditional: compiled for a v5e without it, the exp-sum
    # and the final top-k ran OUTSIDE, and each branch handed them the
    # logits and the row maxima broadcast to [B, V] (two [B, V] outputs)
    return jax.lax.optimization_barrier((cand_logits, cand_idx, lse))


def sample(
    logits: jnp.ndarray,       # [B, V] float32
    keys: jax.Array,           # [B] PRNG keys (one per slot) or one scalar key
    temperature: jnp.ndarray,  # [B] float32; 0 => greedy
    top_k: jnp.ndarray,        # [B] int32; 0 or >=V => disabled
    top_p: jnp.ndarray,        # [B] float32; 1.0 => disabled
    penalties: "tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray] | None" = None,
    bias: "tuple[jnp.ndarray, jnp.ndarray] | None" = None,
    allowed: "jnp.ndarray | None" = None,
    shaped: "jnp.ndarray | None" = None,
) -> "SampleResult":
    """Returns a SampleResult (tokens, chosen logprobs, top-K alternatives).

    Per-slot keys make a request's sampled stream a function of its own
    (seed, position) only — batch composition can never change what a
    request samples (and the OpenAI ``seed`` parameter works).

    ``penalties`` = (presence [B], frequency [B], counts [B, V] int32):
    OpenAI presence/frequency penalties over the OUTPUT tokens generated
    so far (the engine maintains ``counts``). Applied to the raw logits
    before candidate extraction, so the penalized distribution drives
    top-k/top-p and the reported logprobs — vLLM semantics.

    ``bias`` = (ids [B, N] int32, values [B, N] float32): the OpenAI
    ``logit_bias`` map, added to the raw logits before extraction (so a
    +100 bias forces and a -100 bias bans, vLLM semantics). Padding
    entries carry id -1 and are dropped by the scatter.

    ``shaped`` (a traced scalar bool; the decode window's comes from
    ``engine._window_asks``): whether any row that counts asks for a
    penalty or a bias. False takes the candidates from the logits as they
    came and touches neither ``counts`` nor the scatter: ONE ``lax.cond``
    inside the one executable, whose branches return only the [B, 64]
    candidates and the [B, 1] log-sum-exp. Both branches give a row
    without penalties or bias the same bits. None (the prompt paths, once
    a prompt): always shaped.

    ``allowed`` [B, V] bool: grammar-constrained decoding's per-step
    token mask (engine/grammar.py). Applied AFTER bias — a +100
    logit_bias must not defeat a grammar guarantee — and before
    candidate extraction, so reported logprobs renormalize over the
    allowed set (guided-decoding semantics)."""
    B = logits.shape[0]
    logits = logits.astype(jnp.float32)

    def shaped_candidates():
        return _candidates(_shape_logits(logits, penalties, bias), allowed)

    if shaped is None or (penalties is None and bias is None):
        cand_logits, cand_idx, lse = shaped_candidates()
    else:
        cand_logits, cand_idx, lse = jax.lax.cond(
            shaped, shaped_candidates, lambda: _candidates(logits, allowed))
    C = cand_logits.shape[1]

    rank = jnp.arange(C, dtype=jnp.int32)[None, :]           # [1, C]
    k = jnp.where(top_k <= 0, C, jnp.minimum(top_k, C))[:, None]
    keep_k = rank < k

    cand_probs = jnp.exp(cand_logits - lse)                  # [B, C]
    cumprob = jnp.cumsum(cand_probs, axis=-1)
    # keep tokens whose cumulative prob *before* them is < top_p (always
    # keeps the argmax token)
    keep_p = (cumprob - cand_probs) < top_p[:, None]

    keep = keep_k & keep_p
    masked = jnp.where(keep, cand_logits, NEG_INF)           # [B, C]

    # --- draw ----------------------------------------------------------
    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    if keys.ndim == 0:  # single key: legacy batch-wide draw
        gumbel = jax.random.gumbel(keys, (B, C), jnp.float32)
    else:
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (C,), jnp.float32))(keys)
    perturbed = masked / safe_temp + gumbel
    sampled_rank = jnp.argmax(perturbed, axis=-1)            # [B]

    # rank 0 is the EXACT argmax even under approx_max_k: its algorithm
    # takes per-shard maxima then an exact top-k over them, and the global
    # maximum is always its shard's maximum — recall loss only affects
    # lower ranks. So greedy stays exact on both extraction paths.
    greedy_rank = jnp.zeros((B,), sampled_rank.dtype)        # sorted => rank 0
    chosen_rank = jnp.where(temperature <= 0.0, greedy_rank, sampled_rank)

    tokens = jnp.take_along_axis(cand_idx, chosen_rank[:, None], axis=-1)[:, 0]
    chosen_logit = jnp.take_along_axis(cand_logits, chosen_rank[:, None],
                                       axis=-1)
    logprobs = (chosen_logit - lse)[:, 0]
    K = min(LOGPROB_TOPK, C)
    return SampleResult(
        tokens=tokens.astype(jnp.int32),
        logprobs=logprobs,
        top_ids=cand_idx[:, :K].astype(jnp.int32),
        top_logprobs=cand_logits[:, :K] - lse,
    )


@jax.tree_util.register_pytree_node_class
class SampleResult:
    """Per-step sampling outputs (a pytree, so it flows through jit).

    tokens [B] int32; logprobs [B] f32 (of the sampled token); top_ids /
    top_logprobs [B, LOGPROB_TOPK] — the highest-probability alternatives
    (sorted desc), for the OpenAI ``logprobs`` surface. All four ride one
    device->host transfer at harvest time."""

    def __init__(self, tokens, logprobs, top_ids, top_logprobs):
        self.tokens = tokens
        self.logprobs = logprobs
        self.top_ids = top_ids
        self.top_logprobs = top_logprobs

    def tree_flatten(self):
        return (self.tokens, self.logprobs, self.top_ids, self.top_logprobs), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def host_pack(self) -> jnp.ndarray:
        """All four outputs as ONE [B, 2 + 2K] int32 array (floats ride
        bitcast). A device->host read has a per-ARRAY cost: reading one
        packed array per step instead of four leaves cuts the harvester's
        host work ~4x — which is what bounds throughput on small-core
        hosts."""
        lp = jax.lax.bitcast_convert_type(self.logprobs, jnp.int32)
        tlp = jax.lax.bitcast_convert_type(self.top_logprobs, jnp.int32)
        return jnp.concatenate(
            [self.tokens[:, None], lp[:, None], self.top_ids, tlp], axis=1)


class HostSample:
    """Host-side view of a device_get of SampleResult.host_pack()."""

    __slots__ = ("tokens", "logprobs", "top_ids", "top_logprobs")

    def __init__(self, arr):
        import numpy as np

        K = (arr.shape[1] - 2) // 2
        self.tokens = arr[:, 0]
        self.logprobs = np.ascontiguousarray(arr[:, 1]).view(np.float32)
        self.top_ids = arr[:, 2:2 + K]
        self.top_logprobs = np.ascontiguousarray(
            arr[:, 2 + K:2 + 2 * K]).view(np.float32)


def make_sampling_arrays(requests, num_slots: int):
    """Host helper: build [num_slots] parameter arrays from per-slot request
    objects (None => defaults)."""
    import numpy as np

    temps = np.zeros((num_slots,), np.float32)
    top_ks = np.zeros((num_slots,), np.int32)
    top_ps = np.ones((num_slots,), np.float32)
    for i, r in enumerate(requests):
        if r is None:
            continue
        temps[i] = r.temperature
        top_ks[i] = r.top_k
        top_ps[i] = r.top_p
    return temps, top_ks, top_ps
