"""Ring attention: context parallelism for long sequences.

The long-context capability the reference stack entirely lacked (SURVEY §5
"Long-context / sequence parallelism: entirely absent") and the build plan
reserves as the CP extension (§7.3). Design follows blockwise/ring
attention: the sequence is sharded over the mesh's ``seq`` axis; each
device keeps its query block resident and K/V blocks rotate around the
ring via ``jax.lax.ppermute`` (XLA lowers neighbour permutes to ICI
point-to-point transfers), overlapping each hop with the local blockwise
attention. Online-softmax statistics (running max m, normalizer l) make
the blockwise accumulation exact, not approximate.

Memory: each device holds T/R of the sequence; attention scratch is
[T_local, T_local] per head pair instead of [T, T] — an R× memory saving,
which is what makes 128k+ contexts fit.

Causal masking is positional: block origins are derived from the source
device's ring index, so the rotation order never affects the result.
``ring_prefill_attention`` wraps the shard_map; ``_ring_attention_local``
is the per-shard program (also unit-testable single-device).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llms_on_kubernetes_tpu.ops.attention import NEG_INF, softcap
from llms_on_kubernetes_tpu.parallel.mesh import AXIS_SEQ


def _block_attend(q, k, q_origin, k_origin, lengths, *, scale,
                  attn_softcap, sliding_window):
    """Masked attention logits of a local q block vs one rotating k block.

    q: [B, Tq, n_kv, g, d]; k: [B, Tk, n_kv, d]. Returns scores
    [B, n_kv, g, Tq, Tk] masked causally by GLOBAL position, by
    pad-length, and by the optional sliding window.
    """
    B, Tq = q.shape[0], q.shape[1]
    Tk = k.shape[1]
    logits = jnp.einsum("btkgd,bskd->bkgts", q, k) * scale
    logits = softcap(logits, attn_softcap)

    q_pos = q_origin + jnp.arange(Tq, dtype=jnp.int32)[:, None]   # [Tq, 1]
    k_pos = k_origin + jnp.arange(Tk, dtype=jnp.int32)[None, :]   # [1, Tk]
    mask = k_pos <= q_pos
    if sliding_window is not None:
        mask = mask & (k_pos > q_pos - sliding_window)
    valid = k_pos[None] < lengths[:, None, None]                  # [B, 1, Tk]
    mask = mask[None] & valid
    return jnp.where(mask[:, None, None], logits, NEG_INF)


def _ring_attention_local(q, k, v, lengths, *, axis_name, scale,
                          attn_softcap, sliding_window):
    """Per-shard ring attention body (runs under shard_map).

    q/k/v: [B, T_local, heads, d] — this device's sequence chunk.
    lengths: [B] GLOBAL true lengths.
    """
    B, T, n_q, d = q.shape
    n_kv = k.shape[2]
    g = n_q // n_kv
    R = jax.lax.psum(1, axis_name)           # ring size
    me = jax.lax.axis_index(axis_name)
    q_origin = me * T

    qf = q.reshape(B, T, n_kv, g, d).astype(jnp.float32)

    # online-softmax accumulators
    m = jnp.full((B, n_kv, g, T), NEG_INF, jnp.float32)
    l = jnp.zeros((B, n_kv, g, T), jnp.float32)
    o = jnp.zeros((B, n_kv, g, T, d), jnp.float32)
    perm = [(i, (i + 1) % R) for i in range(R)]

    def body(step, carry):
        m, l, o, kc, vc = carry
        # the block now resident came from device (me - step) mod R
        src = (me - step) % R
        scores = _block_attend(
            qf, kc.astype(jnp.float32), q_origin, src * T, lengths,
            scale=scale, attn_softcap=attn_softcap,
            sliding_window=sliding_window,
        )
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # guard fully-masked rows (NEG_INF - NEG_INF)
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bkgts,bskd->bkgtd", p, vc.astype(jnp.float32))
        m = m_new
        # rotate K/V to the next device; overlap with this block's compute
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return m, l, o, kc, vc

    m, l, o, _, _ = jax.lax.fori_loop(0, R, body, (m, l, o, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]          # [B, n_kv, g, T, d]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, n_q, d).astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,            # [B, T_global, n_q, d] (seq-sharded)
    k: jnp.ndarray,            # [B, T_global, n_kv, d]
    v: jnp.ndarray,
    lengths: jnp.ndarray,      # [B] global lengths
    mesh: Mesh,
    *,
    scale: float,
    attn_softcap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Causal prefill attention with the sequence sharded over mesh axis
    'seq'. Exact (same numerics policy as ops/attention.py); tested against
    the single-device reference on a virtual ring in tests/test_ring.py.

    Composes with tensor parallelism: the head axis stays sharded over
    'model' inside the shard_map (when it divides evenly), so CP×TP runs
    with no head all-gather — each device owns its heads' slice of its
    sequence chunk and only K/V blocks move, around the seq ring."""
    from llms_on_kubernetes_tpu.parallel.mesh import AXIS_MODEL

    n_q, n_kv = q.shape[2], k.shape[2]
    model_size = int(mesh.shape[AXIS_MODEL])
    heads = (AXIS_MODEL if model_size > 1 and n_q % model_size == 0
             and n_kv % model_size == 0 else None)
    spec = P(None, AXIS_SEQ, heads, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local, axis_name=AXIS_SEQ, scale=scale,
            attn_softcap=attn_softcap, sliding_window=sliding_window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, lengths)
