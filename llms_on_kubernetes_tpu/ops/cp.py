"""Context parallelism for the paged KV pool: pool sharding, masked
writes, and distributed decode/chunk attention over the ``seq`` mesh axis.

Round-4 closure of SURVEY §2.4/§5's long-context rows: ring attention
(ops/ring_attention.py) already shards PREFILL compute over ``seq``, but
the page pool itself was replicated per shard — max context stayed
bounded by one device's pool share, and decode attention was
single-device. Here the pool's flat page axis is sharded over ``seq``,
so a slice's total KV capacity scales with the ring size, and decode /
chunk attention run as a partial-softmax reduction across the page
shards (gather-based context-parallel decode: each device attends over
the pages it owns, then one ``psum`` merges the online-softmax partials
— the flash-attention merge identity, over ICI instead of within a
kernel).

Numbering: with CP active the decoder folds layers PAGE-MAJOR
(``flat = page_id * L + layer`` — see decoder._run_layers) instead of
layer-major, so a contiguous 1/R shard of the flat axis holds 1/R of
EVERY layer's pages (layer-major sharding would put each layer's pages
on ~one device and serialize the layer loop's attention over the ring).
Page granularity: ``num_pages % R == 0`` keeps each page's L layer slots
on one device.

All entry points are trace-time dispatched on ``seq_parallelism() > 1``
(parallel/mesh.py active-mesh context), so seq=1 meshes never pay a
shard_map boundary.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from llms_on_kubernetes_tpu.ops.attention import NEG_INF, _gather_pool, softcap
from llms_on_kubernetes_tpu.parallel.mesh import (
    AXIS_MODEL, AXIS_SEQ, get_active_mesh, seq_parallelism,
)

_HALF_NEG = NEG_INF / 2


def _kv_axis(mesh, n_kv: int):
    size = mesh.shape[AXIS_MODEL]
    return AXIS_MODEL if size > 1 and n_kv % size == 0 else None


def _pool_specs(pool, mesh):
    """PartitionSpec pytree for a KVPool (or raw array): kv-head axis over
    ``model``, flat page axis over ``seq``."""
    def spec(x):
        m_kv = _kv_axis(mesh, x.shape[0])
        return P(m_kv, AXIS_SEQ, *([None] * (x.ndim - 2)))
    return jax.tree.map(spec, pool)


def _head_axis(mesh, n: int):
    size = mesh.shape[AXIS_MODEL]
    return AXIS_MODEL if size > 1 and n % size == 0 else None


# ---------------------------------------------------------------------------
# masked pool writes
# ---------------------------------------------------------------------------

def dispatch_write_tokens(k_pages, v_pages, k, v, page_table, positions):
    """write_tokens, CP-aware: with a seq-sharded pool each device applies
    only the updates landing in its flat-slot range (read-merge-write with
    an ownership mask — a blind DUS on a non-owner would corrupt whatever
    page lives at the clamped local slot)."""
    from llms_on_kubernetes_tpu.engine.cache import write_tokens

    if seq_parallelism() <= 1:
        return write_tokens(k_pages, v_pages, k, v, page_table, positions)
    mesh = get_active_mesh()
    pool_spec = _pool_specs(k_pages, mesh)
    m_kv = _head_axis(mesh, k.shape[2])
    kv_spec = P(None, None, m_kv, None)

    def body(kp, vp, kk, vv, pt, pos):
        r = jax.lax.axis_index(AXIS_SEQ)
        W = (kp.data if hasattr(kp, "data") else kp).shape[1]
        return write_tokens(kp, vp, kk, vv, pt, pos, owner=(r * W, W))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pool_spec, pool_spec, kv_spec, kv_spec, P(), P()),
        out_specs=(pool_spec, pool_spec),
        check_vma=False,
    )(k_pages, v_pages, k, v, page_table, positions)


# ---------------------------------------------------------------------------
# decode attention: partial softmax per shard + one psum merge
# ---------------------------------------------------------------------------

def _owned_token_mask(page_table, base, W, page):
    """[B, S] bool: key tokens whose (flat) page this device owns."""
    local = page_table - base                       # [B, pages_per_seq]
    owned = (local >= 0) & (local < W)
    return jnp.repeat(owned, page, axis=1), jnp.where(owned, local, 0)


def _merge_partials(num, den, m, axis_name):
    """Combine per-shard online-softmax partials: the flash merge
    identity, reduced with psum/pmax over the ring."""
    M = jax.lax.pmax(m, axis_name)
    w = jnp.where(m > _HALF_NEG, jnp.exp(m - M), 0.0)
    num = jax.lax.psum(num * w[..., None], axis_name)
    den = jax.lax.psum(den * w, axis_name)
    return num / jnp.maximum(den, 1e-30)[..., None]


def cp_paged_attention(q, k_pages, v_pages, page_table, lengths, *, scale,
                       sliding_window: Optional[int] = None,
                       attn_softcap: Optional[float] = None):
    """Context-parallel single-token decode attention.

    Same contract as attention.paged_attention, but the pool arrives
    sharded over ``seq`` on its flat axis; each device computes masked
    partial attention over its local pages and one psum merges the
    numerators/denominators. Pinned against the single-device reference
    in tests/test_cp.py."""
    mesh = get_active_mesh()
    B, n_q, d = q.shape
    n_kv = (k_pages.data if hasattr(k_pages, "data") else k_pages).shape[0]
    page = (k_pages.data if hasattr(k_pages, "data") else k_pages).shape[2]
    pool_spec = _pool_specs(k_pages, mesh)
    m_h = _head_axis(mesh, n_q)
    if m_h is not None and _kv_axis(mesh, n_kv) is None:
        m_h = None  # pool heads replicated: keep q replicated too
    q_spec = P(None, m_h, None)

    def body(qq, kp, vp, pt, ln):
        r = jax.lax.axis_index(AXIS_SEQ)
        data = kp.data if hasattr(kp, "data") else kp
        W = data.shape[1]
        S = pt.shape[1] * page
        tok_owned, local_pt = _owned_token_mask(pt, r * W, W, page)
        k = _gather_pool(kp, local_pt, B, S, d)      # [n_kv_l, B, S, d]
        v = _gather_pool(vp, local_pt, B, S, d)
        nk = k.shape[0]
        qg = qq.reshape(B, nk, qq.shape[1] // nk, d).astype(jnp.float32)
        logits = jnp.einsum("bkgd,kbsd->bkgs", qg, k) * scale
        logits = softcap(logits, attn_softcap)
        k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        mask = (k_pos < ln[:, None]) & tok_owned
        if sliding_window is not None:
            mask = mask & (k_pos > ln[:, None] - 1 - sliding_window)
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        m = logits.max(axis=-1)                          # [B, nk, g]
        p = jnp.where(logits > _HALF_NEG,
                      jnp.exp(logits - m[..., None]), 0.0)
        den = p.sum(axis=-1)
        num = jnp.einsum("bkgs,kbsd->bkgd", p, v)
        out = _merge_partials(num, den, m, AXIS_SEQ)     # [B, nk, g, d]
        return out.reshape(B, qq.shape[1], d).astype(qq.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(), P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_pages, v_pages, page_table, lengths)


def cp_chunk_attention(q, k_pages, v_pages, page_table, history,
                       chunk_lengths, *, scale,
                       sliding_window: Optional[int] = None,
                       attn_softcap: Optional[float] = None):
    """Context-parallel prefill-with-history attention (same contract as
    attention.chunk_attention; pool sharded over ``seq``)."""
    mesh = get_active_mesh()
    B, T, n_q, d = q.shape
    data0 = k_pages.data if hasattr(k_pages, "data") else k_pages
    n_kv, page = data0.shape[0], data0.shape[2]
    pool_spec = _pool_specs(k_pages, mesh)
    m_h = _head_axis(mesh, n_q)
    if m_h is not None and _kv_axis(mesh, n_kv) is None:
        m_h = None
    q_spec = P(None, None, m_h, None)

    def body(qq, kp, vp, pt, hist, cln):
        r = jax.lax.axis_index(AXIS_SEQ)
        data = kp.data if hasattr(kp, "data") else kp
        W = data.shape[1]
        S = pt.shape[1] * page
        tok_owned, local_pt = _owned_token_mask(pt, r * W, W, page)
        k = _gather_pool(kp, local_pt, B, S, d)
        v = _gather_pool(vp, local_pt, B, S, d)
        nk = k.shape[0]
        qg = qq.reshape(B, T, nk, qq.shape[2] // nk, d).astype(jnp.float32)
        logits = jnp.einsum("btkgd,kbsd->bkgts", qg, k) * scale
        logits = softcap(logits, attn_softcap)
        q_pos = hist[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        k_pos = jnp.arange(S, dtype=jnp.int32)[None, None, :]
        mask = k_pos <= q_pos[:, :, None]
        mask = mask & (k_pos < (hist + cln)[:, None, None])
        if sliding_window is not None:
            mask = mask & (k_pos > q_pos[:, :, None] - sliding_window)
        mask = mask & tok_owned[:, None, :]
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        m = logits.max(axis=-1)                          # [B, nk, g, T]
        p = jnp.where(logits > _HALF_NEG,
                      jnp.exp(logits - m[..., None]), 0.0)
        den = p.sum(axis=-1)
        num = jnp.einsum("bkgts,kbsd->bkgtd", p, v)
        out = _merge_partials(num, den, m, AXIS_SEQ)     # [B, nk, g, T, d]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, qq.shape[2], d)
        return out.astype(qq.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(), P(), P()),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_pages, v_pages, page_table, history, chunk_lengths)
