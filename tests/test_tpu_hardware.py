"""Real-TPU kernel pinning (skipped off-TPU).

The interpret-mode tests in tests/test_pallas.py / test_kv_int8.py pin
kernel SEMANTICS; these pin the actual Mosaic LOWERING on hardware —
a kernel that regresses only on-device (tiling, DMA alignment, MXU
precision) should fail here before a bench run discovers it
(round-2 review recommendation).

Run on a machine with a TPU attached (LLMK_TEST_TPU=1 stops the
suite-wide conftest from forcing the CPU platform):

    LLMK_TEST_TPU=1 python -m pytest tests/test_tpu_hardware.py -v

The ``smoke_shape`` tests compile every kernel a dispatcher in
ops/attention.py can reach at the geometry chip_smoke.py serves
(mistral-7b heads: 8 KV x 4 x 128; page 64 x 64 pages = 4096-token slots,
window 4096; the int8 pair at page 128) and compare it with the XLA
reference on the same pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

on_tpu = jax.default_backend() == "tpu"
pytestmark = pytest.mark.skipif(not on_tpu, reason="needs a real TPU")


def _fill_pools(rng, KV, page, d, B, pps, kv_dtype):
    from llms_on_kubernetes_tpu.engine.cache import (
        CacheConfig, init_pages, write_tokens,
    )

    P = B * pps + 1
    T = pps * page - 3
    cc = CacheConfig(num_layers=1, num_kv_heads=KV, head_dim=d, num_pages=P,
                     page_size=page, pages_per_slot=pps, dtype="float32",
                     kv_dtype=kv_dtype)
    kp, vp = init_pages(cc)
    k = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, d)), jnp.float32)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T))
    kp, vp = write_tokens(kp, vp, k, v, pt, jnp.asarray(positions))
    lengths = jnp.asarray(rng.integers(T // 2, T + 1, B), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, KV * 4, d)), jnp.float32)
    return kp, vp, pt, lengths, q


def test_paged_decode_kernel_matches_xla_on_tpu():
    from llms_on_kubernetes_tpu.ops.attention import paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import pallas_paged_attention

    rng = np.random.default_rng(0)
    kp, vp, pt, lengths, q = _fill_pools(rng, 8, 32, 128, 4, 8, None)
    want = np.asarray(paged_attention(q, kp, vp, pt, lengths, scale=0.09))
    got = np.asarray(pallas_paged_attention(
        q, kp.data, vp.data, pt, lengths, scale=0.09, interpret=False))
    # MXU f32 matmuls run at bf16-ish precision on TPU
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_paged_decode_int8_kernel_matches_xla_on_tpu():
    from llms_on_kubernetes_tpu.ops.attention import paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_int8,
    )

    rng = np.random.default_rng(1)
    kp, vp, pt, lengths, q = _fill_pools(rng, 8, 128, 128, 4, 3, "int8")
    want = np.asarray(paged_attention(q, kp, vp, pt, lengths, scale=0.09))
    got = np.asarray(pallas_paged_attention_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, pt, lengths,
        scale=0.09, interpret=False))
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_paged_fused_write_kernel_matches_xla_on_tpu():
    """The default-on fused KV-append + attend kernel
    (pallas_paged_attention_write) on the real Mosaic lowering. Cases:
    mid-page, page-boundary writes (last row of a page at 64, first row
    of a fresh page at 65), length-1, idle (length 0) and near-capacity
    rows — the 8-sublane-aligned read-modify-write of the target block is
    the part that can only regress on hardware."""
    from test_pallas import run_fused_write_case

    rng = np.random.default_rng(3)
    run_fused_write_case(
        rng, np.asarray([45, 64, 65, 1, 0, 250], np.int32),
        n_kv=8, group=4, d=128, page=32, pps=8,
        interpret=False,
        # attention rows at MXU f32 (bf16-ish) precision; the pool-byte
        # comparison inside the helper stays EXACT — writes are DMAs
        rtol=2e-2, atol=2e-2)


def test_flash_prefill_kernel_matches_xla_on_tpu():
    from llms_on_kubernetes_tpu.ops.attention import prefill_attention
    from llms_on_kubernetes_tpu.ops.pallas_flash import flash_prefill_attention

    rng = np.random.default_rng(2)
    B, T, n_kv, group, d = 2, 256, 8, 4, 128
    q = jnp.asarray(rng.normal(size=(B, T, n_kv * group, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    lengths = jnp.asarray([T, T - 57], jnp.int32)
    want = np.asarray(prefill_attention(q, k, v, lengths, scale=0.09))
    got = np.asarray(flash_prefill_attention(
        q, k, v, lengths, scale=0.09, interpret=False))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# serving geometry (chip_smoke.py's): every dispatcher-reachable kernel
# ---------------------------------------------------------------------------

N_KV, GROUP, D = 8, 4, 128            # mistral-7b attention heads
LENGTHS = [4096, 4093, 1500, 65, 1]   # full slot .. one token


def _random_pool(rng, P, page, kv_dtype):
    """A pool with every page filled (unwritten pages must be maskable
    garbage, not zeros): bf16 normal, or int8 bytes + positive scales."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool

    if kv_dtype == "int8":
        return KVPool(
            jnp.asarray(rng.integers(-127, 128, size=(N_KV, P, page, D),
                                     dtype=np.int8)),
            jnp.asarray(rng.uniform(0.004, 0.012, size=(N_KV, P, page)),
                        jnp.float32))
    return KVPool(jnp.asarray(rng.normal(size=(N_KV, P, page, D)),
                              jnp.bfloat16))


def _smoke_case(seed, page, pps, kv_dtype, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    B = len(lengths)
    kp = _random_pool(rng, B * pps + 1, page, kv_dtype)
    vp = _random_pool(rng, B * pps + 1, page, kv_dtype)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, N_KV * GROUP, D)), jnp.bfloat16)
    return rng, kp, vp, pt, jnp.asarray(lengths, jnp.int32), q


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("window", [4096, 1024])
def test_smoke_shape_paged_decode(window):
    from llms_on_kubernetes_tpu.ops.attention import paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import pallas_paged_attention

    _, kp, vp, pt, lengths, q = _smoke_case(10, 64, 64, None)
    want = paged_attention(q, kp, vp, pt, lengths, scale=D ** -0.5,
                           sliding_window=window)
    got = pallas_paged_attention(q, kp.data, vp.data, pt, lengths,
                                 scale=D ** -0.5, sliding_window=window)
    # bf16 outputs of f32 softmaxes whose matmuls run at MXU precision
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("window", [4096, 1024])
def test_smoke_shape_paged_decode_int8(window):
    from llms_on_kubernetes_tpu.ops.attention import paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_int8,
    )

    _, kp, vp, pt, lengths, q = _smoke_case(11, 128, 32, "int8")
    want = paged_attention(q, kp, vp, pt, lengths, scale=D ** -0.5,
                           sliding_window=window)
    got = pallas_paged_attention_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, pt, lengths,
        scale=D ** -0.5, sliding_window=window)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=3e-2, atol=3e-2)


# fused write+attend: mid-page, last row of a page, first row of a fresh
# page, one token, idle, and the last row of the slot
WRITE_LENGTHS = [1500, 64, 65, 1, 0, 4096]


def _write_case(seed, page, pps, kv_dtype):
    """Pools + one new token per slot, and what the XLA path makes of them:
    write_tokens into the pool, then paged_attention over the result."""
    from llms_on_kubernetes_tpu.engine.cache import write_tokens
    from llms_on_kubernetes_tpu.ops.attention import paged_attention

    rng, kp, vp, pt, lengths, q = _smoke_case(seed, page, pps, kv_dtype,
                                              WRITE_LENGTHS)
    B = len(WRITE_LENGTHS)
    k_new = jnp.asarray(rng.normal(size=(B, N_KV, D)), jnp.bfloat16)
    v_new = jnp.asarray(rng.normal(size=(B, N_KV, D)), jnp.bfloat16)
    wp = jnp.where(lengths > 0, lengths - 1, -1)[:, None]
    kp_ref, vp_ref = jax.jit(write_tokens)(
        kp, vp, k_new[:, None], v_new[:, None], pt, wp)
    want = paged_attention(q, kp_ref, vp_ref, pt, lengths, scale=D ** -0.5,
                           sliding_window=4096)
    return kp, vp, pt, lengths, q, k_new, v_new, kp_ref, vp_ref, want


def _check_rows(got, want, lengths, tol):
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(_f32(got)[act], _f32(want)[act],
                               rtol=tol, atol=tol)
    assert np.isfinite(_f32(got)).all()      # the idle row must not NaN


def test_smoke_shape_fused_write():
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write,
    )

    (kp, vp, pt, lengths, q, k_new, v_new,
     kp_ref, vp_ref, want) = _write_case(12, 64, 64, None)
    got, kd, vd = pallas_paged_attention_write(
        q, kp.data, vp.data, pt, lengths, k_new, v_new, scale=D ** -0.5,
        sliding_window=4096)
    _check_rows(got, want, lengths, 2e-2)
    # pool bytes are DMA'd, not computed: exact outside trash page 0
    np.testing.assert_array_equal(_f32(kd)[:, 1:], _f32(kp_ref.data)[:, 1:])
    np.testing.assert_array_equal(_f32(vd)[:, 1:], _f32(vp_ref.data)[:, 1:])


def test_smoke_shape_fused_write_int8():
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write_int8,
    )

    (kp, vp, pt, lengths, q, k_new, v_new,
     kp_ref, vp_ref, want) = _write_case(13, 128, 32, "int8")
    got, kd, ks, vd, vs = pallas_paged_attention_write_int8(
        q, kp.data, kp.scale, vp.data, vp.scale, pt, lengths, k_new, v_new,
        scale=D ** -0.5, sliding_window=4096)
    _check_rows(got, want, lengths, 3e-2)
    # the in-kernel quantizer follows cache.quantize_kv's arithmetic; the
    # two compilers may still round x/s differently in the last place, so
    # bytes may differ by one step and scales by an ulp
    for a, b in ((kd, kp_ref.data), (vd, vp_ref.data)):
        assert np.abs(np.asarray(a, np.int32)[:, 1:]
                      - np.asarray(b, np.int32)[:, 1:]).max() <= 1
    np.testing.assert_allclose(np.asarray(ks)[:, 1:],
                               np.asarray(kp_ref.scale)[:, 1:], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vs)[:, 1:],
                               np.asarray(vp_ref.scale)[:, 1:], rtol=1e-6)


@pytest.mark.parametrize("T", [256, 1024, 4096])
def test_smoke_shape_flash_prefill(T):
    """chip_smoke.py's buckets (256, 1024) and ``serve``'s largest default
    bucket (4096), under mistral's 4096 window."""
    from llms_on_kubernetes_tpu.ops.attention import prefill_attention
    from llms_on_kubernetes_tpu.ops.pallas_flash import flash_prefill_attention

    rng = np.random.default_rng(14)
    B = 2 if T <= 1024 else 1
    q = jnp.asarray(rng.normal(size=(B, T, N_KV * GROUP, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, N_KV, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, N_KV, D)), jnp.bfloat16)
    lens = [T, T - 57][:B]
    lengths = jnp.asarray(lens, jnp.int32)
    want = prefill_attention(q, k, v, lengths, scale=D ** -0.5,
                             sliding_window=4096)
    got = flash_prefill_attention(q, k, v, lengths, scale=D ** -0.5,
                                  sliding_window=4096)
    for b, n in enumerate(lens):   # padding rows are don't-care
        np.testing.assert_allclose(_f32(got)[b, :n], _f32(want)[b, :n],
                                   rtol=2e-2, atol=2e-2)


def test_smoke_shape_dispatch_picks_compiled_kernels():
    """On the chip the dispatchers must take the compiled kernels at the
    serving geometry — and say so."""
    from llms_on_kubernetes_tpu.ops import attention

    _, kp, vp, pt, lengths, q = _smoke_case(15, 64, 64, None)
    attention.dispatch_paged_attention(
        q, kp, vp, pt, lengths, scale=D ** -0.5, sliding_window=4096)
    x = jnp.zeros((1, 1024, N_KV * GROUP, D), jnp.bfloat16)
    attention.dispatch_prefill_attention(
        x, x[:, :, :N_KV], x[:, :, :N_KV], jnp.asarray([1024], jnp.int32),
        scale=D ** -0.5, sliding_window=4096)
    assert attention._chosen["decode"][0] == "pallas-compiled"
    assert attention._chosen["prefill"][0] == "pallas-compiled"


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four chips")
def test_smoke_shape_tensor_parallel_decode_keeps_pool_sharded():
    """--tp 4: each chip runs the decode kernel on its own two KV heads
    (shard_map over ``model``); the result matches the one-device XLA
    reference and the compiled step holds no all-gather — XLA gathering a
    sharded pool for an unpartitionable custom call is the failure this
    pins."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import make_mesh, set_active_mesh
    from llms_on_kubernetes_tpu.parallel.sharding import shard_pool

    _, kp, vp, pt, lengths, q = _smoke_case(16, 64, 64, None)
    want = attention.paged_attention(q, kp, vp, pt, lengths,
                                     scale=D ** -0.5, sliding_window=4096)
    cfg = get_config("mistral-7b")
    mesh = make_mesh(model=4, devices=jax.devices()[:4])
    set_active_mesh(mesh)
    try:
        fn = jax.jit(lambda q, kp, vp: attention.dispatch_paged_attention(
            q, kp, vp, pt, lengths, scale=D ** -0.5, sliding_window=4096))
        args = (q, shard_pool(kp, cfg, mesh), shard_pool(vp, cfg, mesh))
        got = fn(*args)
        hlo = fn.lower(*args).compile().as_text()
    finally:
        set_active_mesh(None)
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-2, atol=2e-2)
    assert "all-gather" not in hlo


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four chips")
def test_smoke_shape_tensor_parallel_decode_step_gathers_no_pool():
    """The engine's fused K=4 decode step, compiled for --tp 4 at
    mistral-7b's widths (depth cut to 2 layers): the optimized HLO holds
    TP's all-reduces and no all-gather of a page-shaped operand."""
    import dataclasses

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine import engine as E
    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, init_pages
    from llms_on_kubernetes_tpu.ops.quant import random_quantized_params
    from llms_on_kubernetes_tpu.parallel.mesh import make_mesh, set_active_mesh
    from llms_on_kubernetes_tpu.parallel.sharding import (
        pool_sharding, shard_params,
    )

    cfg = dataclasses.replace(get_config("mistral-7b"), num_layers=2)
    mesh = make_mesh(model=4, devices=jax.devices()[:4])
    B, page, pps = 4, 64, 64
    params = shard_params(
        random_quantized_params(cfg, 0, dtype="bfloat16"), cfg, mesh)
    kp, vp = init_pages(
        CacheConfig(num_layers=2, num_kv_heads=N_KV, head_dim=D,
                    num_pages=B * pps + 1, page_size=page,
                    pages_per_slot=pps),
        pool_sharding(cfg, mesh))
    step = jax.jit(E._decode_multi_packed_step, static_argnums=(1, 2),
                   donate_argnums=(6, 7, 8))
    set_active_mesh(mesh)
    try:
        hlo = step.lower(
            params, cfg, 4, jnp.zeros((B, E._DEC_COLS + pps), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((1,), jnp.int32), kp, vp,
            jnp.zeros((B, cfg.vocab_size), jnp.int32), jax.random.key(0),
        ).compile().as_text()
    finally:
        set_active_mesh(None)
    assert "all-reduce" in hlo          # it really is partitioned
    gathers = [ln.strip()[:160] for ln in hlo.splitlines()
               if "all-gather" in ln and f",{page},{D}]" in ln]
    assert not gathers, gathers
