"""Unit tests for core numeric ops against naive dense references."""

import jax
import jax.numpy as jnp
import numpy as np

from llms_on_kubernetes_tpu.ops.attention import paged_attention, prefill_attention
from llms_on_kubernetes_tpu.ops.moe import moe_block
from llms_on_kubernetes_tpu.ops.norms import rms_norm
from llms_on_kubernetes_tpu.ops.rope import apply_rope, rope_frequencies


def dense_attention_ref(q, k, v, mask, scale):
    """Naive [T, H, d] x [S, KV, d] attention with GQA repeat, f64-ish."""
    T, H, d = q.shape
    S, KV, _ = k.shape
    group = H // KV
    k = np.repeat(k, group, axis=1)
    v = np.repeat(v, group, axis=1)
    logits = np.einsum("thd,shd->hts", q.astype(np.float64), k.astype(np.float64)) * scale
    logits = np.where(mask[None], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, v.astype(np.float64))


def test_rms_norm_matches_manual():
    x = np.random.default_rng(0).normal(size=(4, 32)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(32,)).astype(np.float32)
    got = rms_norm(jnp.asarray(x), jnp.asarray(w), 1e-5)
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5)


def test_rms_norm_gemma_style():
    x = np.ones((2, 8), np.float32)
    w = np.zeros((8,), np.float32)  # gemma stores weight-1 => identity norm
    got = rms_norm(jnp.asarray(x), jnp.asarray(w), 0.0, style="gemma")
    np.testing.assert_allclose(np.asarray(got), x / np.sqrt((x ** 2).mean()), rtol=1e-6)


def test_rope_identity_at_position_zero_and_norm_preserving():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(1, 3, 2, 16)).astype(np.float32)
    k = rng.normal(size=(1, 3, 1, 16)).astype(np.float32)
    inv = jnp.asarray(rope_frequencies(16, 10000.0))
    pos = jnp.asarray([[0, 5, 9]], dtype=jnp.int32)
    qr, kr = apply_rope(jnp.asarray(q), jnp.asarray(k), pos, inv)
    np.testing.assert_allclose(np.asarray(qr)[0, 0], q[0, 0], atol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(qr), axis=-1), np.linalg.norm(q, axis=-1), rtol=1e-5
    )
    # relative property: <rope(q,p) , rope(k,p+delta)> depends only on delta
    q1 = rng.normal(size=(1, 1, 1, 16)).astype(np.float32)
    k1 = rng.normal(size=(1, 1, 1, 16)).astype(np.float32)
    def dot_at(p0, p1):
        qr_, _ = apply_rope(jnp.asarray(q1), jnp.asarray(q1), jnp.asarray([[p0]]), inv)
        kr_, _ = apply_rope(jnp.asarray(k1), jnp.asarray(k1), jnp.asarray([[p1]]), inv)
        return float(jnp.sum(qr_ * kr_))
    assert abs(dot_at(3, 7) - dot_at(13, 17)) < 1e-3


def test_llama3_rope_scaling_changes_low_freqs_only():
    base = rope_frequencies(64, 500000.0)
    scaled = rope_frequencies(64, 500000.0, {
        "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
        "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
    })
    assert np.allclose(scaled[0], base[0])        # highest freq untouched
    assert np.allclose(scaled[-1], base[-1] / 8)  # lowest freq divided by factor


def test_prefill_attention_matches_dense():
    rng = np.random.default_rng(0)
    B, T, H, KV, d = 2, 12, 4, 2, 8
    q = rng.normal(size=(B, T, H, d)).astype(np.float32)
    k = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    v = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    lengths = np.array([12, 7], np.int32)
    scale = d ** -0.5
    got = prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lengths), scale=scale
    )
    for b in range(B):
        Lb = lengths[b]
        tpos = np.arange(T)[:, None]
        spos = np.arange(T)[None, :]
        mask = (spos <= tpos) & (spos < Lb)
        ref = dense_attention_ref(q[b], k[b], v[b], mask, scale)
        np.testing.assert_allclose(np.asarray(got)[b, :Lb], ref[:Lb], rtol=3e-4, atol=3e-4)


def test_prefill_attention_sliding_window():
    rng = np.random.default_rng(1)
    B, T, H, KV, d = 1, 10, 2, 2, 4
    q = rng.normal(size=(B, T, H, d)).astype(np.float32)
    k = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    v = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    lengths = np.array([10], np.int32)
    W = 3
    got = prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lengths),
        scale=1.0, sliding_window=W,
    )
    tpos = np.arange(T)[:, None]
    spos = np.arange(T)[None, :]
    mask = (spos <= tpos) & (spos > tpos - W)
    ref = dense_attention_ref(q[0], k[0], v[0], mask, 1.0)
    np.testing.assert_allclose(np.asarray(got)[0], ref, rtol=3e-4, atol=3e-4)


def _fill_pages(k_seq, page_table_row, page):
    """Scatter a [S, KV, d] sequence into a fresh page pool for testing."""
    S, KV, d = k_seq.shape
    P = int(page_table_row.max()) + 2
    pool = np.zeros((P, page, KV, d), k_seq.dtype)
    for s in range(S):
        pool[page_table_row[s // page], s % page] = k_seq[s]
    return pool


def test_paged_attention_matches_dense():
    rng = np.random.default_rng(2)
    B, H, KV, d, page, pps = 2, 4, 2, 8, 4, 5
    lengths = np.array([13, 6], np.int32)
    S = page * pps
    k_seqs = rng.normal(size=(B, S, KV, d)).astype(np.float32)
    v_seqs = rng.normal(size=(B, S, KV, d)).astype(np.float32)
    q = rng.normal(size=(B, H, d)).astype(np.float32)

    # build a shared pool: give each sequence disjoint physical pages
    page_table = np.zeros((B, pps), np.int32)
    pool_k = np.zeros((KV, 1 + B * pps, page, d), np.float32)  # head-major
    pool_v = np.zeros_like(pool_k)
    nxt = 1
    for b in range(B):
        for i in range(pps):
            page_table[b, i] = nxt
            pool_k[:, nxt] = k_seqs[b, i * page:(i + 1) * page].transpose(1, 0, 2)
            pool_v[:, nxt] = v_seqs[b, i * page:(i + 1) * page].transpose(1, 0, 2)
            nxt += 1

    scale = d ** -0.5
    got = paged_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(page_table), jnp.asarray(lengths), scale=scale,
    )
    for b in range(B):
        Lb = lengths[b]
        mask = np.ones((1, Lb), bool)
        ref = dense_attention_ref(
            q[b][None], k_seqs[b, :Lb], v_seqs[b, :Lb], mask, scale
        )[0]
        np.testing.assert_allclose(np.asarray(got)[b], ref, rtol=3e-4, atol=3e-4)


def test_moe_block_matches_dense_topk():
    rng = np.random.default_rng(3)
    N, D, F, E, k = 16, 8, 12, 4, 2
    x = rng.normal(size=(N, D)).astype(np.float32)
    router = rng.normal(size=(D, E)).astype(np.float32)
    wg = rng.normal(size=(E, D, F)).astype(np.float32) * 0.1
    wu = rng.normal(size=(E, D, F)).astype(np.float32) * 0.1
    wd = rng.normal(size=(E, F, D)).astype(np.float32) * 0.1

    got, rows = moe_block(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(wg), jnp.asarray(wu),
        jnp.asarray(wd), top_k=k,
    )
    assert int(rows.sum()) == N * k

    # dense reference: every expert on every token, combine top-k
    logits = x @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.zeros_like(x)
    for n in range(N):
        top = np.argsort(-p[n])[:k]
        w = p[n][top] / p[n][top].sum()
        for wi, e in zip(w, top):
            h = (x[n] @ wg[e])
            h = h / (1 + np.exp(-h)) * (x[n] @ wu[e])  # silu(gate) * up
            ref[n] += wi * (h @ wd[e])
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-3, atol=2e-3)


def test_moe_padding_does_not_displace_real_tokens():
    """Padding rows reach no expert and count nowhere (valid-mask
    semantics); the expert path is dropless, so there is no capacity for
    them to claim."""
    import jax.numpy as jnp
    N, D, F, E = 8, 4, 4, 2
    rng = np.random.default_rng(7)
    x = rng.normal(size=(N, D)).astype(np.float32)
    # make padding rows identical junk that would otherwise flood expert 0
    x[4:] = 5.0
    router = rng.normal(size=(D, E)).astype(np.float32)
    wg = rng.normal(size=(E, D, F)).astype(np.float32) * 0.1
    wu = rng.normal(size=(E, D, F)).astype(np.float32) * 0.1
    wd = rng.normal(size=(E, F, D)).astype(np.float32) * 0.1
    valid = np.array([True] * 4 + [False] * 4)

    masked, rows = moe_block(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(wg), jnp.asarray(wu),
        jnp.asarray(wd), top_k=1, valid=jnp.asarray(valid),
    )
    only_real, rows_real = moe_block(
        jnp.asarray(x[:4]), jnp.asarray(router), jnp.asarray(wg), jnp.asarray(wu),
        jnp.asarray(wd), top_k=1,
    )
    assert rows.tolist() == rows_real.tolist() and int(rows.sum()) == 4
    np.testing.assert_allclose(np.asarray(masked)[:4], np.asarray(only_real), rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(masked)[4:], 0.0)
