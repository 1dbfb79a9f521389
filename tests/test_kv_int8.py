"""int8 KV cache: quantized pool correctness (SURVEY §7 hard-part 1 perf
lever: halves decode-attention HBM traffic, doubles token capacity).

Accuracy contract: per-token symmetric int8 introduces <= 1/127 relative
error per KV element; attention outputs must stay within a small tolerance
of the bf16-cache path, and the Pallas int8 decode kernel must match the
XLA dequant reference bit-closely.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.cache import (
    CacheConfig, KVPool, init_pages, quantize_kv, write_tokens,
)
from llms_on_kubernetes_tpu.ops.attention import chunk_attention, paged_attention


def _filled_pools(rng, KV, P, page, d, B, T, quantized):
    cc = CacheConfig(num_layers=1, num_kv_heads=KV, head_dim=d, num_pages=P,
                     page_size=page, pages_per_slot=P - 1, dtype="float32",
                     kv_dtype="int8" if quantized else None)
    kp, vp = init_pages(cc)
    k = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    pps = (T + page - 1) // page
    pt = np.zeros((B, P - 1), np.int32)
    for b in range(B):
        pt[b, :pps] = 1 + b * pps + np.arange(pps)
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    kp, vp = write_tokens(kp, vp, k, v, jnp.asarray(pt),
                          jnp.asarray(positions))
    return kp, vp, k, v, jnp.asarray(pt)


def test_quantize_kv_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 7, 2, 16)) * 3.0, jnp.float32)
    data, scale = quantize_kv(x)
    back = data.astype(jnp.float32) * scale[..., None]
    err = np.abs(np.asarray(back - x))
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    assert (err <= amax / 127.0 * 0.51 + 1e-7).all()  # round-to-nearest


def test_write_then_attend_quantized_close_to_exact():
    rng = np.random.default_rng(1)
    KV, P, page, d, B, T = 2, 9, 4, 16, 2, 10
    kp_q, vp_q, k, v, pt = _filled_pools(rng, KV, P, page, d, B, T, True)
    # exact-precision reference pool holding the SAME k/v
    kp_f, vp_f = init_pages(CacheConfig(
        num_layers=1, num_kv_heads=KV, head_dim=d, num_pages=P,
        page_size=page, pages_per_slot=P - 1, dtype="float32"))
    positions = jnp.asarray(np.broadcast_to(np.arange(T, dtype=np.int32),
                                            (B, T)))
    kp_f, vp_f = write_tokens(kp_f, vp_f, k, v, pt, positions)

    q = jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32)
    lengths = jnp.asarray([T, T - 3], jnp.int32)
    out_q = paged_attention(q, kp_q, vp_q, pt, lengths, scale=0.25)
    out_f = paged_attention(q, kp_f, vp_f, pt, lengths, scale=0.25)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               rtol=0.05, atol=0.05)

    # chunk attention reads the same quantized pool
    qc = jnp.asarray(rng.normal(size=(B, 4, 4, d)), jnp.float32)
    hist = jnp.asarray([T - 4, T - 7], jnp.int32)
    cl = jnp.asarray([4, 4], jnp.int32)
    out_cq = chunk_attention(qc, kp_q, vp_q, pt, hist, cl, scale=0.25)
    out_cf = chunk_attention(qc, kp_f, vp_f, pt, hist, cl, scale=0.25)
    np.testing.assert_allclose(np.asarray(out_cq), np.asarray(out_cf),
                               rtol=0.05, atol=0.05)


def test_pallas_int8_kernel_matches_xla_reference():
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_int8,
    )

    rng = np.random.default_rng(2)
    KV, P, page, d, B, T = 2, 9, 4, 128, 2, 12
    kp, vp, _, _, pt = _filled_pools(rng, KV, P, page, d, B, T, True)
    q = jnp.asarray(rng.normal(size=(B, 4, d)), jnp.float32)
    lengths = jnp.asarray([T, T - 5], jnp.int32)
    want = paged_attention(q, kp, vp, pt, lengths, scale=0.3)
    got = pallas_paged_attention_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, pt, lengths,
        scale=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # sliding window variant
    want_w = paged_attention(q, kp, vp, pt, lengths, scale=0.3,
                             sliding_window=6)
    got_w = pallas_paged_attention_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, pt, lengths,
        scale=0.3, sliding_window=6, interpret=True)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=2e-5, atol=2e-5)


# cached tokens a row, page, pages a slot. "three blocks": 1536-token slots
# of three 512-token attention blocks, so the kernel's pipeline (the next
# live row's pages and scales fetched while this row attends) runs inside
# a row and across an idle one; window 9 then skips two leading blocks.
INT8_WRITE_GEOMETRY = {
    "one block": ([13, 16, 1, 0, 31], 8, 4),
    "three blocks": ([1499, 39, 0, 1535, 600], 64, 24),
}


@pytest.mark.parametrize("geometry", sorted(INT8_WRITE_GEOMETRY))
@pytest.mark.parametrize("window,softcap", [(None, None), (9, None), (None, 40.0)])
def test_fused_write_int8_k1_matches_write_tokens(window, softcap, geometry,
                                                  interpret=True):
    """The quantize-at-write twin of the fused decode kernel must match
    write_tokens on an int8 pool: same attention rows, and — outside the
    never-read trash page 0 — the same int8 bytes exactly, scales to
    1 ulp (the kernel quantizes in f32 inside the program; the reference
    quantizes under jit — XLA CPU's eager path rounds differently, so
    the reference MUST be jitted). Lengths cover mid-page, a fresh-page
    boundary, length-1 (prefill of 1 token + first decode), an idle row,
    and the last row of the last page."""
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write_int8,
    )

    rng = np.random.default_rng(3)
    hist, page, pps = INT8_WRITE_GEOMETRY[geometry]
    KV, group, d = 2, 2, 8
    hist = np.asarray(hist, np.int32)
    B, n_q = len(hist), KV * group
    P = B * pps + 1
    cc = CacheConfig(num_layers=1, num_kv_heads=KV, head_dim=d, num_pages=P,
                     page_size=page, pages_per_slot=pps, dtype="float32",
                     kv_dtype="int8")
    kp, vp = init_pages(cc)
    table = np.zeros((B, pps), np.int32)
    for b in range(B):
        table[b] = 1 + b * pps + np.arange(pps)
    table = jnp.asarray(table)

    wt = jax.jit(write_tokens)
    Tmax = int(hist.max())
    k_hist = jnp.asarray(rng.normal(size=(B, Tmax, KV, d)), jnp.float32)
    v_hist = jnp.asarray(rng.normal(size=(B, Tmax, KV, d)), jnp.float32)
    pos = np.broadcast_to(np.arange(Tmax, dtype=np.int32), (B, Tmax)).copy()
    pos[pos >= hist[:, None]] = -1
    kp, vp = wt(kp, vp, k_hist, v_hist, table, jnp.asarray(pos))

    lengths = jnp.asarray(np.where(hist > 0, hist + 1, 0).astype(np.int32))
    k_new = jnp.asarray(rng.normal(size=(B, KV, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, KV, d)), jnp.float32)
    wp = np.where(hist > 0, hist, -1)[:, None].astype(np.int32)
    kp_ref, vp_ref = wt(kp, vp, k_new[:, None], v_new[:, None], table,
                        jnp.asarray(wp))
    q = jnp.asarray(rng.normal(size=(B, n_q, d)), jnp.float32)
    ref = paged_attention(q, kp_ref, vp_ref, table, lengths, scale=d ** -0.5,
                          sliding_window=window, attn_softcap=softcap)

    out, kd2, ks2, vd2, vs2 = pallas_paged_attention_write_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, table, lengths,
        k_new, v_new, scale=d ** -0.5, sliding_window=window,
        attn_softcap=softcap, interpret=interpret)
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(ref)[act],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()  # idle row must not NaN
    np.testing.assert_array_equal(np.asarray(kd2)[:, 1:],
                                  np.asarray(kp_ref.data)[:, 1:])
    np.testing.assert_array_equal(np.asarray(vd2)[:, 1:],
                                  np.asarray(vp_ref.data)[:, 1:])
    np.testing.assert_allclose(np.asarray(ks2)[:, 1:],
                               np.asarray(kp_ref.scale)[:, 1:], rtol=2e-7)
    np.testing.assert_allclose(np.asarray(vs2)[:, 1:],
                               np.asarray(vp_ref.scale)[:, 1:], rtol=2e-7)


def test_int8_kv_teacher_forced_parity_across_decode_windows():
    """int8 KV acceptance gate (PR-4 margin-triage pattern): the fused
    K=1 kernel, the K=4 window, and the K=4 speculative (ngram) path
    must emit IDENTICAL greedy streams with int8 KV on — they quantize
    with the same math, so divergence means a kernel bug, not noise.
    Then teacher-force the stream through the fp32 model: wherever
    fp32's top-1/top-2 logprob margin is decisive (0.05 nats — far above
    the ~0.005 int8-KV perturbation), the int8-KV engine must have
    picked fp32's argmax. Near-ties are excluded by construction, so
    this does not inherit the autoregressive-cascade brittleness the
    PR-4 weight-quant test fixed."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.engine import (
        Engine, EngineConfig, SamplingParams,
    )
    from llms_on_kubernetes_tpu.models.decoder import forward_score, init_params

    def stream(steps, spec):
        eng = Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=2,
            page_size=16, num_pages=64, pages_per_slot=8,
            prefill_buckets=(16,), kv_cache_dtype="int8",
            decode_steps=steps, speculation=spec))
        return eng.generate([1, 2, 3, 4, 5],
                            SamplingParams(temperature=0.0, max_tokens=8))

    k1 = stream(1, None)
    k4 = stream(4, None)
    k4s = stream(4, "ngram")
    assert k1 == k4 == k4s, (k1, k4, k4s)
    assert len(k1) == 8

    cfg = get_config("debug-tiny")
    params = init_params(cfg, jax.random.key(0), dtype="float32")
    seq = [1, 2, 3, 4, 5] + k1
    tokens = jnp.asarray([seq], jnp.int32)
    lengths = jnp.asarray([len(seq)], jnp.int32)
    _, ids, top = forward_score(params, cfg, tokens, lengths, top_k=2)
    margin = np.asarray(top[0, :, 0] - top[0, :, 1])
    decisive = margin > 0.05
    checked = 0
    for t in range(4, len(seq) - 1):  # positions predicting generated tokens
        if decisive[t]:
            assert seq[t + 1] == int(ids[0, t, 0]), (
                f"int8 KV flipped a decisive (margin {margin[t]:.3f}) "
                f"argmax at position {t}: {ids[0, t, 0]} -> {seq[t + 1]}")
            checked += 1
    assert checked >= 4  # test has teeth


def test_mid_window_abort_restores_page_accounting_int8():
    """Aborting mid-flight with a K=4 window in the async pipeline and
    int8 pages must restore the allocator exactly: no leaked refcounts,
    the full free list back (prefix caching off so freed pages return to
    the free list, not the LRU), and a zeroed page table — the PR-8/12
    abort harness extended to the quantized pool."""
    from llms_on_kubernetes_tpu.engine.engine import (
        Engine, EngineConfig, SamplingParams,
    )

    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=2,
        page_size=4, num_pages=32, pages_per_slot=8, prefill_buckets=(16,),
        kv_cache_dtype="int8", decode_steps=4, prefix_caching=False,
        async_scheduling=True, async_depth=2))
    free0 = eng.allocator.num_free_pages
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=200))
    other = eng.submit([4, 5], SamplingParams(temperature=0.0, max_tokens=6))
    for _ in range(3):
        eng.step()
    eng.abort(req, "client_disconnect")
    steps = 0
    while not (req.finished and other.finished):
        eng.step()
        steps += 1
        assert steps < 500
    for _ in range(5):  # drain any in-flight windows
        eng.step()
    assert eng.allocator.refcount == {}
    assert eng.allocator.num_free_pages == free0
    assert all(not p for p in eng.allocator.slot_pages)
    assert (eng.allocator.page_tables == 0).all()


def test_engine_generates_with_int8_kv():
    from llms_on_kubernetes_tpu.engine.engine import (
        Engine, EngineConfig, SamplingParams,
    )

    def mk(kv):
        return Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=2,
            page_size=8, num_pages=32, pages_per_slot=8,
            prefill_buckets=(16,), kv_cache_dtype=kv))

    p = SamplingParams(temperature=0.0, max_tokens=8)
    a = mk("int8").generate([1, 2, 3, 4], p)
    b = mk("int8").generate([1, 2, 3, 4], p)
    assert a == b and len(a) == 8          # deterministic, full length
    ref = mk(None).generate([1, 2, 3, 4], p)
    # tiny random model: logits gaps are wide, int8 KV rarely flips greedy
    same = sum(x == y for x, y in zip(a, ref))
    assert same >= len(ref) - 2, (a, ref)

    with pytest.raises(ValueError, match="kv_dtype"):
        init_pages(CacheConfig(num_layers=1, num_kv_heads=1, head_dim=8,
                               kv_dtype="fp4"))
