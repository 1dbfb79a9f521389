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


def _report(name, obj):
    """Numbers for PERF.md: on stdout, and under chiprun_out/ where the
    chip tool keeps files."""
    import json
    import os

    print(f"[{name}] {json.dumps(obj)}", flush=True)
    if os.path.isdir("chiprun_out"):
        with open(f"chiprun_out/{name}.json", "w") as f:
            json.dump(obj, f, indent=1)


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


def _write_case(seed, page, pps, kv_dtype, lengths=WRITE_LENGTHS):
    """Pools + one new token per slot, and what the XLA path makes of them:
    write_tokens into the pool, then paged_attention over the result."""
    from llms_on_kubernetes_tpu.engine.cache import write_tokens
    from llms_on_kubernetes_tpu.ops.attention import paged_attention

    rng, kp, vp, pt, lengths, q = _smoke_case(seed, page, pps, kv_dtype,
                                              lengths)
    B = len(lengths)
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


# the one cell's decode batch (mistral-7b.chat: page 64, 32 pages a slot,
# 32 rows of which 13 live at the median): mid-page, a page's last row
# (64, 640), a fresh page's first row (65, 1025), one token, the slot's
# last row, and idle rows between them
CELL_LENGTHS = [1500, 0, 64, 65, 0, 0, 1, 2048, 0, 640, 129, 0, 0, 700, 0,
                1023, 0, 1025, 0, 0, 333, 0, 1919, 0, 0, 8, 0, 0, 0, 0, 0, 0]


@pytest.mark.parametrize("page,pps,lengths", [
    (64, 64, WRITE_LENGTHS), (64, 32, CELL_LENGTHS)],
    ids=["smoke", "cell"])
def test_smoke_shape_fused_write(page, pps, lengths):
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention, pallas_paged_attention_write,
    )

    (kp, vp, pt, lengths, q, k_new, v_new,
     kp_ref, vp_ref, want) = _write_case(12, page, pps, None, lengths)
    got, kd, vd = pallas_paged_attention_write(
        q, kp.data, vp.data, pt, lengths, k_new, v_new, scale=D ** -0.5,
        sliding_window=4096)
    _check_rows(got, want, lengths, 2e-2)
    # pool bytes are DMA'd, not computed: exact outside trash page 0
    np.testing.assert_array_equal(_f32(kd)[:, 1:], _f32(kp_ref.data)[:, 1:])
    np.testing.assert_array_equal(_f32(vd)[:, 1:], _f32(vp_ref.data)[:, 1:])
    # ... and the append really landed (the reference is not the input)
    assert (_f32(kp_ref.data)[:, 1:] != _f32(kp.data)[:, 1:]).any()
    # kernel against kernel (the two-op path's: the DUS loop, then the
    # paged kernel over the written pool): only the last softmax merge
    # differs, so the bf16 rows differ by a rounding at most
    two_op = pallas_paged_attention(q, kp_ref.data, vp_ref.data, pt, lengths,
                                    scale=D ** -0.5, sliding_window=4096)
    act = np.asarray(lengths) > 0
    d = np.abs(_f32(got)[act] - _f32(two_op)[act])
    _report(f"pr34_kernel_vs_kernel_{pps}", {
        "max_abs_diff": float(d.max()), "differing_share": float((d > 0).mean()),
        "max_abs_value": float(np.abs(_f32(two_op)[act]).max())})
    # (p is rounded to bf16 on its way into the MXU: a bf16 step of the
    # largest value, not of each element)
    np.testing.assert_allclose(_f32(got)[act], _f32(two_op)[act],
                               rtol=2 ** -7, atol=2 ** -7)


class _NoDMA:
    """``pltpu`` with DMAs that move nothing (a kernel's compute alone)."""

    class _Copy:
        def start(self):
            pass

        wait = start

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)

    def make_async_copy(self, *a, **kw):
        return self._Copy()


def _parent_module(rel, name):
    """The parent commit's module ``rel`` of the package, under another
    name, where the chip call brought the commit (.scratch/parent: the
    verify skill's recipe); None where it did not."""
    import importlib.util
    import os
    import sys

    path = ".scratch/parent/llms_on_kubernetes_tpu/" + rel
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # a module's dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod


def _kernel_sides():
    """(name, module) of this tree's ops/pallas_paged.py and, where the
    chip call brought it, the parent commit's."""
    from llms_on_kubernetes_tpu.ops import pallas_paged

    parent = _parent_module("ops/pallas_paged.py", "parent_paged")
    return ([("parent", parent)] if parent else []) + [
        ("change", pallas_paged)]


def test_cell_kernel_time_fetch_attend_both(monkeypatch):
    """The fused decode kernel at the cell's shape (13 of 32 rows live),
    timed whole, with its DMAs alone (the block arithmetic removed) and
    with its arithmetic alone (DMAs that move nothing): what the pipeline
    across rows has to hide, and how much of it it hides. 256 launches
    chained through q inside one executable, pools in place. (What the
    kernel answers at this shape is test_smoke_shape_fused_write[cell]'s.)"""
    import time

    kp, vp, pt, lengths, q, k_new, v_new, *_ = _write_case(
        12, 64, 32, None, CELL_LENGTHS)
    live = int((np.asarray(lengths) > 0).sum())
    n_calls = 256

    def time_us(mod):
        fn = mod.pallas_paged_attention_write.__wrapped__

        @jax.jit
        def chain(q, kd, vd):
            def step(_, c):
                o, kd, vd = fn(c[0], c[1], c[2], pt, lengths, k_new, v_new,
                               scale=D ** -0.5, sliding_window=4096)
                return o.astype(q.dtype), kd, vd
            return jax.lax.fori_loop(0, n_calls, step, (q, kd, vd))

        args = (q, kp.data + 0, vp.data + 0)
        best = float("inf")
        for _ in range(4):                      # the first run compiles
            t0 = time.perf_counter()
            jax.block_until_ready(chain(*args))
            best = min(best, time.perf_counter() - t0)
        return best / n_calls * 1e6

    said = {"live_rows": live, "rows": len(CELL_LENGTHS)}
    for name, mod in _kernel_sides():
        both = time_us(mod)
        with monkeypatch.context() as m:        # DMAs, no arithmetic
            if hasattr(mod, "_attend_block"):
                m.setattr(mod, "_attend_block", lambda q, carry, *a, **kw: carry)
            else:
                m.setattr(mod, "_attend_staged", lambda q, *a, **kw: (
                    jnp.full(q.shape[:2] + (1,), -1e30, jnp.float32),
                    jnp.zeros(q.shape[:2] + (1,), jnp.float32),
                    jnp.zeros(q.shape, jnp.float32)))
            fetch = time_us(mod)
        with monkeypatch.context() as m:        # arithmetic, no DMAs
            m.setattr(mod, "pltpu", _NoDMA(mod.pltpu))
            attend = time_us(mod)
        said[name] = {"both_us": round(both, 2), "fetch_only_us": round(fetch, 2),
                      "attend_only_us": round(attend, 2),
                      "both_us_a_live_row": round(both / live, 3)}
    _report("pr37_kernel_split", said)


def _sampler_sides():
    """(name, engine module, sampling module) of this tree and, where the
    chip call brought it, of the parent commit."""
    from llms_on_kubernetes_tpu.engine import engine, sampling

    parent = [_parent_module(f"engine/{name}.py", "parent_" + name)
              for name in ("engine", "sampling")]
    return ([("parent", *parent)] if all(parent) else []) + [
        ("change", engine, sampling)]


# slots, vocabulary, rows live: the jamba2-3b.long-answers and the
# mellum2-12b.long-prompts cells' token steps
SAMPLER_SHAPES = [(128, 65536, 56), (48, 98304, 25)]


@pytest.mark.parametrize("slots,vocab,live", SAMPLER_SHAPES)
def test_cell_sampler_time_plain_against_shaped(slots, vocab, live):
    """What a token step does OUTSIDE the model at a cell's [slots, vocab]:
    the penalty counts' update and ``sample()`` over float32 logits, 64
    steps chained in one executable (the counts and the logits in place; a
    step's logits are new ones, as a head's product is), on rows that ask
    for nothing and on the same rows with one penalty and one logit_bias
    among them. us a step; the parent's code beside it where the call
    brought .scratch/parent (it treats the two alike)."""
    import inspect
    import time

    n_steps = 64
    rng = np.random.default_rng(vocab)
    logits = jnp.asarray(rng.normal(size=(slots, vocab)), jnp.float32)
    toks = jnp.asarray(rng.integers(0, vocab, slots), jnp.int32)

    def rows(E, asking):
        packed = np.zeros((slots, E._DEC_COLS + 1), np.int32)
        packed[:, 5] = np.float32(1.0).view(np.int32)
        packed[:, E._BIAS_DEC:E._BIAS_DEC + E.LOGIT_BIAS_SLOTS] = -1
        packed[:live, 0] = 100
        if asking:
            packed[0, 8:10] = np.float32(0.5).view(np.int32)
            packed[1, E._BIAS_DEC] = 17
            packed[1, E._BIAS_DEC + E.LOGIT_BIAS_SLOTS] = \
                np.float32(2.0).view(np.int32)
        return jnp.asarray(packed)

    def time_us(E, S, packed):
        looks = "shaped" in inspect.signature(S.sample).parameters

        @jax.jit
        def chain(logits, counts, toks, packed):
            def f32(col):
                return jax.lax.bitcast_convert_type(col, jnp.float32)

            lengths0 = packed[:, 0]
            penalties = f32(packed[:, 8]), f32(packed[:, 9])
            bias = E._unpack_bias(packed, E._BIAS_DEC)
            active, kw = lengths0 > 0, {}
            if looks:
                penalised, shaped = E._window_asks(packed)
                active, kw = active & penalised, {"shaped": shaped}

            def step(j, c):
                logits, counts, cur = c
                counts = E._count_decode_tokens(counts, cur, active)
                res = S.sample(
                    logits, E._slot_keys(jax.random.key(0), packed[:, 6],
                                         lengths0 + j),
                    f32(packed[:, 4]), packed[:, 3], f32(packed[:, 5]),
                    penalties=(*penalties, counts), bias=bias, **kw)
                logits = jax.lax.dynamic_update_slice(
                    logits, res.logprobs[:1, None], (0, 0))
                return logits, counts, res.tokens

            return jax.lax.fori_loop(0, n_steps, step, (logits, counts, toks))

        best = float("inf")
        for _ in range(4):                      # the first run compiles
            args = (logits + 0, jnp.zeros((slots, vocab), jnp.int32), toks,
                    packed)
            jax.block_until_ready(args)
            t0 = time.perf_counter()
            jax.block_until_ready(chain(*args))
            best = min(best, time.perf_counter() - t0)
        return round(best / n_steps * 1e6, 1)

    said = {"slots": slots, "vocab": vocab, "live_rows": live}
    for name, E, S in _sampler_sides():
        said[name] = {"plain_rows_us": time_us(E, S, rows(E, False)),
                      "asking_rows_us": time_us(E, S, rows(E, True))}
    _report(f"pr53_sampler_{slots}x{vocab}", said)
    plain, asking = said["change"].values()
    assert plain < asking, said


@pytest.mark.parametrize("slots,vocab", [s[:2] for s in SAMPLER_SHAPES])
def test_cell_sampler_branches_give_the_same_bits(slots, vocab):
    """``sample()`` at a cell's [slots, vocab] on the chip's own candidate
    extraction (``approx_max_k``, which no CPU test runs): rows that ask
    for nothing get the same tokens, log-probabilities and alternatives,
    bit for bit, from the plain branch, from the shaped branch and from the
    unconditioned call; rows among which one carries a penalty and one a
    bias get from the shaped branch what the unconditioned call gives. (The
    benchmark's output check probes with ``max_tokens`` 1, which the
    PREFILL samples: it never enters a decode window's conditional.)"""
    from llms_on_kubernetes_tpu.engine import sampling

    rng = np.random.default_rng(slots)
    logits = jnp.asarray(rng.normal(size=(slots, vocab)) * 3, jnp.float32)
    counts = jnp.asarray(rng.integers(0, 3, (slots, vocab)), jnp.int32)
    keys = jax.random.split(jax.random.key(1), slots)
    temps = jnp.where(jnp.arange(slots) % 2 == 0, 0.0, 0.8)
    top_k = jnp.full((slots,), 40, jnp.int32)
    top_p = jnp.full((slots,), 0.95, jnp.float32)
    zeros = jnp.zeros((slots,), jnp.float32)
    no_ids = jnp.full((slots, 32), -1, jnp.int32)
    vals = jnp.zeros((slots, 32), jnp.float32)

    # (the logits and the counts go in as ARGUMENTS: closed over they are
    # constants, and XLA folds the branch that reads nothing else on the
    # host, in another order of summation)
    @jax.jit
    def draw(logits, counts, penalty, ids, vals, shaped):
        return sampling.sample(
            logits, keys, temps, top_k, top_p,
            penalties=(penalty, penalty, counts), bias=(ids, vals),
            shaped=shaped).host_pack()

    def packs(penalty, ids, vals):
        got = [np.asarray(draw(logits, counts, penalty, ids, vals, shaped))
               for shaped in (jnp.bool_(False), jnp.bool_(True), None)]
        return got

    plain, shaped, always = packs(zeros, no_ids, vals)
    _report(f"pr53_sampler_bits_{slots}x{vocab}", {
        "rows": slots, "differing_ints_plain_vs_shaped": int(
            (plain != shaped).sum()), "of": int(plain.size),
        "rows_with_another_token": int((plain[:, 0] != shaped[:, 0]).sum())})
    np.testing.assert_array_equal(plain, shaped)
    np.testing.assert_array_equal(plain, always)
    penalty = zeros.at[0].set(0.5)
    ids, vals = no_ids.at[1, 0].set(17), vals.at[1, 0].set(50.0)
    _, asked, always = packs(penalty, ids, vals)
    np.testing.assert_array_equal(asked, always)
    assert asked[1, 0] == 17                # the bias made it the argmax
    assert (asked[2:] == plain[2:]).all() and (asked[:2] != plain[:2]).any()


# pairs a layer sorts at 64 experts top-4: the lfm2-24b-a2b.long-answers
# cell's decode step (42 of 64 slots live) and its prefill shapes (1 x 128,
# 1 x 512 / 4 x 128, 4 x 512), and two larger, around the rule's threshold
GROUPED_SHAPES = [(64, 42), (128, 128), (512, 512), (2048, 2048),
                  (4096, 4096), (8192, 8192)]


def _expert_layer_case(tokens, live, E, k, D, dtype=jnp.bfloat16):
    rng = np.random.default_rng(tokens)
    sel = np.stack([rng.permutation(E)[:k] for _ in range(tokens)])
    valid = np.zeros(tokens, bool)
    valid[rng.permutation(tokens)[:live]] = True
    x = jax.random.normal(jax.random.key(tokens), (tokens, D), dtype)
    return (x, jnp.asarray(sel, jnp.int32),
            jnp.full((tokens, k), 1.0 / k, jnp.float32), jnp.asarray(valid))


def _expert_stack(seed, n, E, K, N, int8=False):
    """[n, E, K, N] drawn a layer at a time, as the server's are."""
    from llms_on_kubernetes_tpu.ops.quant import quantize

    w = jax.jit(lambda keys: jax.lax.map(
        lambda key: (jax.random.normal(key, (E, K, N), jnp.bfloat16)
                     * K ** -0.5).astype(jnp.bfloat16), keys))(
        jax.random.split(jax.random.key(seed), n))
    return quantize(w, reduce_axes=(2,)) if int8 else w


@pytest.mark.parametrize("int8", [False, True], ids=["bfloat16", "int8"])
def test_cell_grouped_product_against_ragged_dot(monkeypatch, int8):
    """An expert layer of the lfm2-24b-a2b cell (64 experts of 2048 x 1536,
    top-4, layers of an 8-layer stack by a scanned index) through
    ops/moe.py with the Pallas grouped kernel and with ``ragged_dot``
    under it, shape by shape: the time of a layer either way (what
    ``moe.KERNEL_MAX_MEAN_ROWS`` was set from: PERF.md, section 6, PR 39),
    and the largest difference between the two results of one layer in
    units of the result's bfloat16 spacing."""
    import time

    from llms_on_kubernetes_tpu.ops import attention, moe

    n, E, D, F, k = (2 if int8 else 8), 64, 2048, 1536, 4
    stacks = (_expert_stack(1, n, E, D, F, int8),
              _expert_stack(2, n, E, D, F, int8),
              _expert_stack(3, n, E, F, D, int8))
    monkeypatch.setattr(moe, "KERNEL_MAX_MEAN_ROWS", 1 << 20)  # time both

    def layers(x, sel, weight, valid, stacks):
        def body(c, i):
            out, _ = moe.grouped_experts(c, sel, weight, *stacks,
                                         valid=valid, layer=i)
            return (c + out * 0.01).astype(c.dtype), out
        return jax.lax.scan(body, x, jnp.arange(n))[1]

    said = {}
    for tokens, live in GROUPED_SHAPES[:3 if int8 else None]:
        args = _expert_layer_case(tokens, live, E, k, D)
        got = {}
        for impl, mode in (("ragged_dot", None), ("kernel", "compiled")):
            monkeypatch.setattr(attention, "pallas_mode", lambda m=mode: m)
            run = jax.jit(lambda *a: layers(*a))        # traced anew
            best = float("inf")
            for _ in range(6):                          # the first compiles
                t0 = time.perf_counter()
                out = jax.block_until_ready(run(*args, stacks))
                best = min(best, time.perf_counter() - t0)
            got[impl] = (best / n * 1e6,
                         np.asarray(out[0].astype(jnp.float32)),
                         attention._chosen["experts"])
        a, b = got["kernel"][1], got["ragged_dot"][1]
        spacing = 2.0 ** (np.floor(np.log2(np.maximum(
            np.maximum(np.abs(a), np.abs(b)), 1e-30))) - 7)
        assert got["kernel"][2][0] == "pallas-compiled"
        assert got["ragged_dot"][2][0] == "xla"
        assert not a[~np.asarray(args[3])].any() and np.abs(a).max() > 0
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)
        said[f"{tokens * k} pairs, {live} of {tokens} rows live"] = {
            "ragged_dot_layer_us": round(got["ragged_dot"][0], 1),
            "kernel_layer_us": round(got["kernel"][0], 1),
            "kernel": got["kernel"][2][1],
            "largest_difference_bf16_spacings": float(
                (np.abs(a - b) / spacing).max()),
            "elements_that_differ": float((a != b).mean())}
    _report(f"pr39_grouped_{'int8' if int8 else 'bfloat16'}", said)


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


# ---------------------------------------------------------------------------
# the one cell's decode step (mistral-7b.chat), whole: full depth, int8
# weights, the bf16 pool of 769 x 32 pages, K = 4 (PR 34)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mistral():
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.ops.quant import random_quantized_params

    cfg = get_config("mistral-7b")
    return cfg, random_quantized_params(cfg, 0, dtype="bfloat16")


def _bf16(bits):
    return bits.view(jnp.bfloat16).astype(np.float32)


def test_cell_decode_window_fused_in_place(mistral):
    """The engine's K = 4 decode window with the 32-layer unroll, the
    append inside the kernel against the per-slot DUS loop (the parent
    commit's path). The pool is updated IN PLACE (a copy of either pool,
    3.2 GB, would show in the executable's temporaries); every layer has
    the same rows written; and rows written while both sides had sampled
    the same tokens are the same bytes in layer 0 and close in the layers
    above."""
    import time

    from test_fused_decode_step import (
        check_same_pool, decode_window, top_logprobs, window_args,
        window_rows,
    )

    from llms_on_kubernetes_tpu.engine.cache import KVPool

    cfg, params = mistral
    page, pps, num_pages, K = 64, 32, 769, 4
    lengths0 = np.asarray(CELL_LENGTHS)
    lengths0[lengths0 == 2048] = 2045          # room for the window
    lengths0[2] = 63                           # crosses a page inside it
    budgets = np.where(lengths0 > 0, K, 0)
    budgets[10] = 2                            # budget ends inside the window
    budgets[25] = 0                            # live, riding masked
    rng = np.random.default_rng(34)
    packed = window_rows(lengths0, budgets, rng.integers(1, 32000, 32), page,
                         pps, num_pages)
    # one random layer block, repeated down the stack (history is garbage
    # to attend over either way; 3.2 GB of host randoms is not needed)
    shape = (N_KV, num_pages, page, D)
    k0 = np.tile(rng.normal(size=shape).astype(jnp.bfloat16),
                 (1, cfg.num_layers, 1, 1))
    v0 = np.tile(rng.normal(size=shape).astype(jnp.bfloat16),
                 (1, cfg.num_layers, 1, 1))

    def timed(compiled, kp, vp, n=12):
        args = window_args(cfg, params, packed, kp, vp)
        for i in range(n + 2):
            if i == 2:
                jax.block_until_ready(args[4].data)
                t0 = time.perf_counter()
            packs, _t, args[4], args[5], args[6], _, _ = compiled(*args)
        jax.block_until_ready(packs)
        return (time.perf_counter() - t0) / n * 1e3

    out, said = {}, {}
    pool_shape = f"bf16[{N_KV},{cfg.num_layers * num_pages},{page},{D}]"
    for name in ("fused", "two_op"):
        t0 = time.perf_counter()
        packs, kp, vp, compiled = decode_window(
            cfg, params, KVPool(jnp.asarray(k0)), KVPool(jnp.asarray(v0)),
            packed, K, two_op=name == "two_op")
        took = time.perf_counter() - t0
        mem, hlo = compiled.memory_analysis(), compiled.as_text()
        said[name] = {
            "lower_compile_run_s": round(took, 1),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "pool_shaped_copies": sum(
                1 for ln in hlo.splitlines()
                if (" copy(" in ln or " copy-start(" in ln)
                and pool_shape in ln.split("=", 1)[1].split("copy")[0]),
            "dynamic_update_slices": hlo.count(" dynamic-update-slice("),
        }
        out[name] = (packs, np.asarray(kp.data), np.asarray(vp.data))
        said[name]["window_ms"] = round(timed(compiled, kp, vp), 3)
        del kp, vp, compiled
    _report("pr34_decode_window", said)
    for name in said:
        # a pool copied even once would be 3.2 GB of temporaries
        assert said[name]["temp_bytes"] < 1 << 30, said
        assert said[name]["pool_shaped_copies"] == 0, said

    live = lengths0 > 0
    alive = (np.arange(K)[:, None] < budgets[None]) & live[None]
    got, want = out["fused"][0], out["two_op"][0]
    d = np.abs(top_logprobs(got) - top_logprobs(want)).max(-1)
    same = got[..., 0] == want[..., 0]
    close = {"alive": int(alive.sum()), "same_token": int(same[alive].sum()),
             "same_token_step0": int(same[0][alive[0]].sum()),
             "of_step0": int(alive[0].sum()),
             "max_abs_top_logprob_diff_step0": float(d[0][alive[0]].max())}
    for side, init in ((1, k0), (2, v0)):
        n, worst = check_same_pool(
            KVPool(out["fused"][side]), KVPool(out["two_op"][side]),
            KVPool(init), cfg.num_layers, packed, (got, want), page, _bf16,
            0.5)    # an unrelated row reads 1.41; roundings carried up the
                    # stack, a tenth of that
        close["kv"[side - 1] + "_rows_compared"] = n
        close["kv"[side - 1] + "_rows_max_rel_rms"] = worst
    _report("pr34_decode_window_outputs", close)
    # the first step reads the same tokens on both sides; how far bf16
    # roundings carry through 32 random layers is the greedy test's to
    # hold against a yardstick, this only catches a wrong row
    assert close["max_abs_top_logprob_diff_step0"] < 0.5, close


def test_cell_greedy_streams_fused_against_two_op(mistral, monkeypatch):
    """64 greedy tokens on eight prompts (the golden file's three, each
    also reversed, and the two shorter ones' first halves), decoded with
    the append inside the kernel and with the two-op path that the parent
    commit takes: where the ids first part, and by how small a margin.
    The yardstick for "close" is a third stream, the two-op path under the
    XLA reference attention: while both sides read the same tokens, the
    fused stream's log-probabilities are no further from the two-op
    path's than the reference's are."""
    import json

    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, init_pages
    from llms_on_kubernetes_tpu.models.decoder import (
        forward_decode, forward_prefill,
    )
    from llms_on_kubernetes_tpu.ops import attention

    cfg, params = mistral
    with open("benchmark/golden/mistral-7b.json") as f:
        texts = list(dict.fromkeys(
            p["content"] for p in json.load(f)["prompts"]))
    texts = (texts + [t[::-1] for t in texts]
             + [t[:len(t) // 2] for t in texts[:2]])
    # the byte tokenizer's chat template (benchmark/reference/make_golden.py)
    prompts = [[256] + list(f"<user>{t}</user>".encode()) for t in texts]
    B, T, page, pps, N = len(prompts), 1280, 64, 32, 64
    assert B == 8 and max(map(len, prompts)) <= T
    cc = CacheConfig(num_layers=cfg.num_layers, num_kv_heads=N_KV, head_dim=D,
                     num_pages=B * pps + 1, page_size=page, pages_per_slot=pps)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    toks = np.zeros((B, T), np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p
    plen = np.asarray([len(p) for p in prompts], np.int32)
    prefill = jax.jit(lambda p, *a: forward_prefill(p, cfg, *a),
                      donate_argnums=(3, 4))

    def stream(two_op, impl="auto"):
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", impl)
        with monkeypatch.context() as m:
            if two_op:    # the reference path in the dispatcher's place
                m.setattr(attention, "dispatch_paged_attention_write",
                          attention.write_then_attend)
            decode = jax.jit(lambda p, *a: forward_decode(p, cfg, *a),
                             donate_argnums=(3, 4))
            kp, vp = init_pages(cc)
            first = []
            for b in range(B):
                logits, kp, vp = prefill(
                    params, jnp.asarray(toks[b:b + 1]),
                    jnp.asarray(plen[b:b + 1]), kp, vp, pt[b:b + 1])
                first.append(np.asarray(jax.nn.log_softmax(logits))[0])
            lps = [np.stack(first)]
            for n in range(1, N):
                cur = jnp.asarray(lps[-1].argmax(-1), jnp.int32)
                logits, kp, vp = decode(params, cur, jnp.asarray(plen + n),
                                        kp, vp, pt)
                lps.append(np.asarray(jax.nn.log_softmax(logits)))
        return np.stack(lps, 1)                       # [B, N, V]

    two_op = stream(True)

    def apart(two_op, other):
        rows = []
        for b in range(B):
            ids_a, ids_b = two_op[b].argmax(-1), other[b].argmax(-1)
            parted = np.nonzero(ids_a != ids_b)[0]
            n = int(parted[0]) if parted.size else N - 1
            # up to and including n both paths read the same tokens
            d = np.abs(two_op[b, :n + 1] - other[b, :n + 1])
            top2 = np.sort(two_op[b, n])[-2:]
            rows.append({
                "prompt_tokens": int(plen[b]),
                "first_parted_at": int(parted[0]) if parted.size else None,
                "two_op_margin_there_nats": float(top2[1] - top2[0]),
                "max_abs_logprob_diff_before": float(d.max()),
                "rms_logprob_diff_first_decode_step": float(
                    np.sqrt((d[min(1, n)] ** 2).mean()))})
        return rows

    fused, xla = stream(False), stream(True, "xla")
    said = {"tokens": N, "fused": apart(two_op, fused),
            "two_op_xla": apart(two_op, xla),
            "fused_against_two_op_xla": apart(xla, fused)}
    _report("pr34_greedy_streams", said)
    # position 0 is prefill's, the same executable on every side
    assert all(r["first_parted_at"] != 0 for r in said["fused"])
    worst = {k: max(r["max_abs_logprob_diff_before"] for r in said[k])
             for k in ("fused", "two_op_xla")}
    assert worst["fused"] <= max(2 * worst["two_op_xla"], 0.05), worst


# ---------------------------------------------------------------------------
# the lfm2-24b-a2b.long-answers cell (PR 41): 32/8 heads of 64, two to a
# 128-lane page row; page 64, 32 pages a slot, 64 slots, 2 x 2049 pages
# ---------------------------------------------------------------------------

N_KV64, D64, ROWS64, PAGES64 = 8, 64, 64, 2 * 2049


def _cell64_case(seed=41):
    """About 30 of 64 rows live at about 400 tokens (the cell's decode
    batch), a page's last row, a fresh page's first, one token; a pool of
    the cell's size, random, in the logical layout [8, P, 64, 64]."""
    rng = np.random.default_rng(seed)
    lengths = np.zeros(ROWS64, np.int32)
    live = rng.permutation(ROWS64)[:30]
    lengths[live] = rng.integers(150, 900, 30)
    lengths[live[:4]] = [64, 65, 1, 2048]
    pool = [jnp.asarray(rng.standard_normal((N_KV64, PAGES64, 64, D64),
                                            np.float32), jnp.bfloat16)
            for _ in range(2)]
    pt = jnp.asarray(2049 + 1 + np.arange(ROWS64 * 32).reshape(ROWS64, 32)
                     % 2048, jnp.int32)          # the second layer's block
    q = jnp.asarray(rng.normal(size=(ROWS64, N_KV64 * 4, D64)), jnp.bfloat16)
    k_new = jnp.asarray(rng.normal(size=(ROWS64, N_KV64, D64)), jnp.bfloat16)
    v_new = jnp.asarray(rng.normal(size=(ROWS64, N_KV64, D64)), jnp.bfloat16)
    return pool, pt, jnp.asarray(lengths), q, k_new, v_new


def test_cell64_paired_kernel_beside_the_xla_two_op_path():
    """The fused write+attend kernel on the pool of paired 64-wide heads,
    at the routed cell's shape: the rows of the XLA two-op path on the
    logical pool (``write_tokens`` + the gather attention: what the cell
    ran before), the same pool bytes after the append, and the time of
    each, chained through q inside one executable with the pools in
    place."""
    import time

    from test_pallas import pair_heads as _pair_heads

    from llms_on_kubernetes_tpu.engine.cache import KVPool, write_tokens
    from llms_on_kubernetes_tpu.ops.attention import paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write,
    )

    (kl, vl), pt, lengths, q, k_new, v_new = _cell64_case()
    wp = jnp.where(lengths > 0, lengths - 1, -1)[:, None]
    scale = D64 ** -0.5

    @jax.jit
    def two_op(q, kd, vd):
        kp, vp = write_tokens(KVPool(kd), KVPool(vd), k_new[:, None],
                              v_new[:, None], pt, wp)
        return (paged_attention(q, kp, vp, pt, lengths, scale=scale),
                kp.data, vp.data)

    want, kr, vr = two_op(q, kl, vl)
    kp, vp = _pair_heads(kl), _pair_heads(vl)
    got, kd, vd = pallas_paged_attention_write(
        q, kp, vp, pt, lengths, k_new, v_new, scale=scale)
    _check_rows(got, want, lengths, 2e-2)
    # (page 0 is the trash page the two-op path sends idle rows to)
    np.testing.assert_array_equal(_f32(kd)[:, 1:],
                                  _f32(_pair_heads(kr))[:, 1:])
    np.testing.assert_array_equal(_f32(vd)[:, 1:],
                                  _f32(_pair_heads(vr))[:, 1:])
    assert (_f32(kd) != _f32(kp)).any()
    del kr, vr, kd, vd

    def time_us(step, args, n_calls):
        @jax.jit
        def chain(q, kd, vd):
            def body(_, c):
                o, kd, vd = step(*c)
                return o.astype(q.dtype), kd, vd
            return jax.lax.fori_loop(0, n_calls, body, (q, kd, vd))

        best = float("inf")
        for _ in range(4):                      # the first run compiles
            t0 = time.perf_counter()
            jax.block_until_ready(chain(*args))
            best = min(best, time.perf_counter() - t0)
        return best / n_calls * 1e6

    fused = pallas_paged_attention_write.__wrapped__
    live = int((np.asarray(lengths) > 0).sum())
    tokens = int(np.asarray(lengths).sum())
    said = {
        "rows": ROWS64, "live_rows": live, "cached_tokens": tokens,
        "paired_kernel_us": round(time_us(
            lambda q, kd, vd: fused(q, kd, vd, pt, lengths, k_new, v_new,
                                    scale=scale), (q, kp, vp), 256), 2),
        "xla_two_op_us": round(time_us(
            two_op.__wrapped__, (q, kl, vl), 32), 2),
        # K and V of every cached token, once
        "live_kv_bytes": tokens * N_KV64 * D64 * 2 * 2,
    }
    said["paired_kernel_hbm_share"] = round(
        said["live_kv_bytes"] / 819e9 / (said["paired_kernel_us"] * 1e-6), 4)
    _report("pr41_kernel_beside_two_op", said)
    assert said["paired_kernel_us"] < said["xla_two_op_us"], said


def test_cell64_greedy_streams_against_float32_reference(monkeypatch):
    """64 greedy tokens on eight prompts (the golden file's three, each
    also reversed, and the two shorter ones' first halves) at the cell's
    nine layers: decoded with the paired kernel, and with the dispatcher's
    gate shut (``write_tokens`` + the XLA gather attention, everything else
    the same: the path the cell ran before). The yardstick is the float32
    reference (benchmark/reference/lfm2_moe.py on the same bf16 weights),
    fed each stream's own tokens: per step, the largest difference of the
    log-probabilities of the reference's eight best ids, which is what the
    benchmark's check compares at the first position."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmark"))
    from reference import lfm2_moe as ref

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, init_pages
    from llms_on_kubernetes_tpu.models import decoder as dec
    from llms_on_kubernetes_tpu.ops import attention

    cfg = get_config("lfm2-24b-a2b@0,3-10")
    with open("benchmark/configs/lfm2-24b-a2b.json") as f:
        ref_cfg = json.load(f)
    with open("benchmark/golden/lfm2-24b-a2b.json") as f:
        golden = json.load(f)
    params = dec.init_params(cfg, jax.random.key(0), dtype="bfloat16")
    texts = list(dict.fromkeys(p["content"] for p in golden["prompts"]))
    texts = (texts + [t[::-1] for t in texts]
             + [t[:len(t) // 2] for t in texts[:2]])
    prompts = [[256] + list(f"<user>{t}</user>".encode()) for t in texts]
    B, T, page, pps, N = len(prompts), 1024, 64, 32, 64
    assert B == 8 and max(map(len, prompts)) + N <= T
    cc = CacheConfig(num_layers=cfg.num_attn_layers,
                     num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                     num_pages=B * pps + 1, page_size=page, pages_per_slot=pps)
    assert cc.pool_row == (4, 128)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    toks = np.zeros((B, T), np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p
    plen = np.asarray([len(p) for p in prompts], np.int32)
    prefill = jax.jit(dec.forward_prefill, static_argnums=(1,),
                      donate_argnums=(4, 5))
    gate = attention._paged_kernel_mode

    def stream(shut):
        monkeypatch.setattr(
            attention, "_paged_kernel_mode",
            (lambda *a: (None, "shut by the test")) if shut else gate)
        decode = jax.jit(
            lambda p, *a, **kw: dec.forward_decode(p, cfg, *a, **kw),
            donate_argnums=(3, 4))
        kp, vp = init_pages(cc)
        conv = dec.init_conv_state(cfg, B, "bfloat16")
        first = []
        for b in range(B):
            logits, kp, vp, aux = prefill(
                params, cfg, jnp.asarray(toks[b:b + 1]),
                jnp.asarray(plen[b:b + 1]), kp, vp, pt[b:b + 1],
                aux=dec.LayerAux(conv=conv,
                                 slots=jnp.asarray([b], jnp.int32)))
            conv = aux.conv
            first.append(np.asarray(jax.nn.log_softmax(logits))[0])
        lps = [np.stack(first)]
        for n in range(1, N):
            cur = jnp.asarray(lps[-1].argmax(-1), jnp.int32)
            logits, kp, vp, aux = decode(
                params, cur, jnp.asarray(plen + n), kp, vp, pt,
                aux=dec.LayerAux(conv=conv))
            conv = aux.conv
            lps.append(np.asarray(jax.nn.log_softmax(logits)))
        said = attention._chosen["decode"]
        return np.stack(lps, 1), said                   # [B, N, V]

    paired, said_paired = stream(False)
    two_op, said_two_op = stream(True)
    assert said_paired == (
        "pallas-compiled",
        "fused write+attend kernel, 2 heads of 64 to a 128-lane page row")
    assert said_two_op == ("xla", "shut by the test")

    def against_reference(lps):
        rows = []
        for b in range(B):
            ids = lps[b].argmax(-1)                     # the stream's tokens
            seq = np.zeros(T, np.int32)
            seq[:plen[b]] = prompts[b]
            seq[plen[b]:plen[b] + N] = ids
            want = np.asarray(jax.nn.log_softmax(ref.logits_at(
                ref_cfg, params, seq.tolist(),
                list(range(plen[b] - 1, plen[b] - 1 + N)))))
            best = np.argsort(want, -1)[:, -8:]
            d = np.abs(np.take_along_axis(lps[b], best, -1)
                       - np.take_along_axis(want, best, -1)).max(-1)   # [N]
            rows.append({"prompt_tokens": int(plen[b]),
                         "prefill_nats": float(d[0]),
                         "decode_median_nats": float(np.median(d[1:])),
                         "decode_max_nats": float(d[1:].max()),
                         "same_top_id": int((ids == want.argmax(-1)).sum())})
        return rows

    said = {"tokens": N, "paired_kernel": against_reference(paired),
            "xla_two_op": against_reference(two_op)}
    parted = [np.nonzero(paired[b].argmax(-1) != two_op[b].argmax(-1))[0]
              for b in range(B)]
    said["first_parted_at"] = [int(p[0]) if p.size else None for p in parted]
    _report("pr41_greedy_streams", said)
    # position 0 is prefill's, the same executable on both sides
    assert all(p != 0 for p in said["first_parted_at"])
    tol = golden["tolerance"]["nats"]
    for a, b in zip(said["paired_kernel"], said["xla_two_op"]):
        assert a["prefill_nats"] < tol, said
        # no further from the reference than the path it replaces
        assert a["decode_median_nats"] <= max(
            1.5 * b["decode_median_nats"], 0.1), said


# deepseek-v3.long-prompts' decode step: 32 slots of 144 pages of 64 tokens,
# six layers' pages in one latent pool of 640-lane rows, 128 heads, 14 slots
# live (decode_occupancy 43-44 %) at lengths drawn as the mix draws them: a
# prompt log-normal around 2,048 (sigma 0.8, 256-8,192) and part of an answer
LATENT = dict(slots=32, live=14, page=64, pps=144, pages=4609, layers=6,
              heads=128, lat=512, rope=64, width=640)


def _latent_cell_case(seed=43):
    g = LATENT
    rng = np.random.default_rng(seed)
    prompts = np.clip(np.exp(rng.normal(np.log(2048), 0.8, g["live"])),
                      256, 8192)
    lengths = np.zeros(g["slots"], np.int32)
    rows = np.sort(rng.choice(g["slots"], g["live"], replace=False))
    lengths[rows] = prompts + rng.integers(1, 384, g["live"])
    pool = jax.random.normal(
        jax.random.key(seed),
        (1, g["layers"] * g["pages"], g["page"], g["width"]), jnp.bfloat16)
    pool = pool.at[..., g["lat"] + g["rope"]:].set(0)
    pt = jnp.asarray(1 + np.arange(g["slots"] * g["pps"]).reshape(
        g["slots"], g["pps"]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(g["slots"], g["heads"],
                                     g["lat"] + g["rope"])), jnp.bfloat16)
    return pool, pt, jnp.asarray(lengths), q


def test_deepseek_cell_latent_kernel_beside_the_xla_loop(monkeypatch):
    """The absorbed decode step's attention at the cell's shape, alone: the
    latent kernel and the XLA loop it replaces (a block of 8 pages of all
    32 slots gathered at a time, as far as the longest slot), six layers'
    tables in turn, 60 calls chained through q inside one executable; the
    kernel again with its DMAs alone and with its arithmetic alone
    (test_cell_kernel_time_fetch_attend_both's split); and what the two
    answer on the same rows."""
    import time

    from llms_on_kubernetes_tpu.ops import attention, pallas_paged

    g = LATENT
    pool, pt, lengths, q = _latent_cell_case()
    scale, n_calls = 192 ** -0.5 * 1.36889 ** 2, 60
    kernel = pallas_paged.pallas_latent_attention.__wrapped__

    def time_us(fn):
        @jax.jit
        def chain(q, pool):
            def step(i, q):
                o = fn(q, pool, pt + (i % g["layers"]) * g["pages"], lengths,
                       scale=scale, lat=g["lat"])
                return jnp.concatenate([o, q[..., g["lat"]:]], axis=-1)
            return jax.lax.fori_loop(0, n_calls, step, q)

        best = float("inf")
        for _ in range(4):                      # the first run compiles
            t0 = time.perf_counter()
            jax.block_until_ready(chain(q, pool))
            best = min(best, time.perf_counter() - t0)
        return best / n_calls * 1e6

    live = np.asarray(lengths)
    live_bytes = int(live.sum()) * g["width"] * 2
    said = {"lengths": live[live > 0].tolist(), "live_rows_bytes": live_bytes}
    for name, fn in (("xla_loop", attention.latent_paged_attention),
                     ("kernel", kernel)):
        us = time_us(fn)
        said[name] = {"us_a_layer": round(us, 1),
                      "live_rows_GB_s": round(live_bytes / us / 1e3, 1)}
    with monkeypatch.context() as m:            # DMAs, no arithmetic
        m.setattr(pallas_paged, "_attend_latent_block",
                  lambda q, carry, *a, **kw: carry)
        said["kernel"]["fetch_only_us"] = round(time_us(kernel), 1)
    with monkeypatch.context() as m:            # arithmetic, no DMAs
        m.setattr(pallas_paged, "pltpu", _NoDMA(pallas_paged.pltpu))
        said["kernel"]["attend_only_us"] = round(time_us(kernel), 1)
    want = _f32(attention.latent_paged_attention(
        q, pool, pt, lengths, scale=scale, lat=g["lat"]))[live > 0]
    got = _f32(pallas_paged.pallas_latent_attention(
        q, pool, pt, lengths, scale=scale, lat=g["lat"]))[live > 0]
    said["max_abs_diff"] = float(np.abs(got - want).max())
    said["max_abs_value"] = float(np.abs(want).max())
    _report("pr43_latent_kernel", said)
    # both round p to bfloat16 on its way into the MXU, after a running
    # maximum over the same 512-token blocks
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)


# the jamba2-3b.long-answers cell's Mamba state and the rows it decodes
SSM_CELL = dict(layers=26, slots=128, N=16, Di=5120, live=(56, 128))


def test_jamba_cell_ssm_step_kernel_beside_the_xla_step(monkeypatch):
    """The Mamba layers' token step on the state at the
    ``jamba2-3b.long-answers`` cell's shape (``f32[26,129,16,5120]``, 128
    rows), alone: all 26 layers' updates in one executable on the donated
    array, the kernel over the live slots (ops/pallas_ssm.py) and the XLA
    step it replaces (every row computed, old selected against new, all
    128 written back), with 56 rows live, as the cell decodes, and with all
    128; and what the two leave in the state and in ``y``."""
    import time

    from llms_on_kubernetes_tpu.ops import attention

    L, S, N, Di = (SSM_CELL[k] for k in ("layers", "slots", "N", "Di"))
    ks = jax.random.split(jax.random.key(48), 6)
    ops = (jax.nn.softplus(jax.random.normal(ks[1], (L, S, 1, Di)) - 3.0),
           -jnp.exp(jax.random.normal(ks[5], (L, N, Di))),
           jax.random.normal(ks[2], (L, S, 1, Di)),
           jax.random.normal(ks[3], (L, S, 1, N)),
           jax.random.normal(ks[4], (L, S, 1, N)))
    rng = np.random.default_rng(48)
    lives = {}
    for n_live in SSM_CELL["live"]:
        lives[n_live] = np.zeros(S, bool)
        lives[n_live][rng.permutation(S)[:n_live]] = True

    def fresh():
        return jax.random.normal(ks[0], (L, S + 1, N, Di), jnp.float32)

    def layers(ssm, live, ops):
        first = attention.live_first(live)
        ys = []
        for l in range(L):
            y, ssm = attention.dispatch_ssm_step(
                *(a[l] for a in ops), ssm, jnp.int32(l), live, first)
            ys.append(y)
        return jnp.stack(ys), ssm

    def run(fn):
        """{rows live: (y, the state after one step, ms a step)}"""
        out = {}
        for n_live, mask in lives.items():
            live = jnp.asarray(mask)
            y, ssm = fn(fresh(), live, ops)         # the first call compiles
            got = np.asarray(y), np.asarray(ssm)
            t0 = time.perf_counter()
            for _ in range(20):
                y, ssm = fn(ssm, live, ops)
            jax.block_until_ready((y, ssm))
            out[n_live] = (*got, (time.perf_counter() - t0) / 20 * 1e3)
        return out

    with monkeypatch.context() as m:    # the choice is made at the trace
        m.setattr(attention, "ssm_step_mode", lambda *a: (None, "shut"))
        xla = run(jax.jit(lambda *a: layers(*a), donate_argnums=(0,)))
        assert attention._chosen["ssm_step"][0] == "xla"
    kernel = run(jax.jit(lambda *a: layers(*a), donate_argnums=(0,)))
    assert attention._chosen["ssm_step"][0].startswith("pallas")
    before, said = np.asarray(fresh()), {}
    for n_live, mask in lives.items():
        (y0, h0, ms0), (y1, h1, ms1) = xla[n_live], kernel[n_live]
        # idle slots and the trash row: what they were, bit for bit
        np.testing.assert_array_equal(h1[:, :S][:, ~mask],
                                      before[:, :S][:, ~mask])
        np.testing.assert_array_equal(h1[:, S], before[:, S])
        np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-5)
        # (an idle row's y: zeros from the kernel, a step nobody keeps from
        # the XLA step)
        np.testing.assert_allclose(y1[:, mask], y0[:, mask],
                                   rtol=1e-4, atol=1e-4)
        assert not y1[:, ~mask].any()
        said[f"{n_live} live"] = {
            "xla_step_ms": round(ms0, 3), "kernel_step_ms": round(ms1, 3),
            "kernel_live_rows_GB_s": round(
                n_live * L * 2 * N * Di * 4 / ms1 / 1e6, 1),
            "max_abs_diff_h": float(np.abs(h1 - h0).max()),
            "max_abs_diff_y": float(np.abs(y1 - y0)[:, mask].max())}
    _report("pr48_ssm_step", said)
    full, part = (said[f"{n} live"] for n in (S, SSM_CELL["live"][0]))
    assert full["kernel_step_ms"] <= full["xla_step_ms"] * 1.02
    assert part["kernel_step_ms"] * 1.8 <= part["xla_step_ms"]


def test_jamba_cell_conv_step_kernel_beside_the_xla_form(monkeypatch):
    """The Mamba layers' convolution window in a token step at the
    ``jamba2-3b.long-answers`` cell's shape (``bf16[26,129,15360]``, 128
    rows), alone: all 26 layers' steps in one executable on the donated
    array, the kernel over the tiles that hold a live row
    (ops/pallas_conv.py) and the XLA one-pass form it stands beside, with
    56 rows live in the lowest slots (as the engine fills them), 56
    scattered, and all 128; and what the two leave in the windows and in
    ``xc``."""
    import time

    from llms_on_kubernetes_tpu.ops import attention

    L, S, Di = (SSM_CELL[k] for k in ("layers", "slots", "Di"))
    taps = 4
    ks = jax.random.split(jax.random.key(52), 4)
    xz = jax.random.normal(ks[1], (L, S, 2 * Di), jnp.bfloat16)
    w = jax.random.normal(ks[2], (L, taps, Di), jnp.float32)
    b = jax.random.normal(ks[3], (L, Di), jnp.float32)
    rng = np.random.default_rng(52)
    lives = {"56 lowest": np.arange(S) < 56, "56 scattered": np.zeros(S, bool),
             "128": np.ones(S, bool)}
    lives["56 scattered"][rng.permutation(S)[:56]] = True

    def fresh():
        return jax.random.normal(ks[0], (L, S + 1, (taps - 1) * Di),
                                 jnp.bfloat16)

    def layers(conv, live, xz, w, b):
        tiles = attention.live_tiles_first(live)
        xcs = []
        for l in range(L):
            xc, xs, conv = attention.dispatch_conv_step(
                xz[l], w[l], b[l], conv, jnp.int32(l), live, tiles)
            xcs.append(xc.astype(jnp.float32) + xs)
        return jnp.stack(xcs), conv

    def run(fn):
        out = {}
        for name, mask in lives.items():
            live = jnp.asarray(mask)
            xc, conv = fn(fresh(), live, xz, w, b)  # the first call compiles
            got = np.asarray(xc), np.asarray(conv.astype(jnp.float32))
            t0 = time.perf_counter()
            for _ in range(20):
                xc, conv = fn(conv, live, xz, w, b)
            jax.block_until_ready((xc, conv))
            out[name] = (*got, (time.perf_counter() - t0) / 20 * 1e3)
        return out

    with monkeypatch.context() as m:    # the choice is made at the trace
        m.setattr(attention, "conv_step_mode", lambda *a: (None, "shut"))
        xla = run(jax.jit(lambda *a: layers(*a), donate_argnums=(0,)))
        assert attention._chosen["conv_step"][0] == "xla"
    kernel = run(jax.jit(lambda *a: layers(*a), donate_argnums=(0,)))
    assert attention._chosen["conv_step"][0] == "pallas-compiled"
    before, said = np.asarray(fresh().astype(jnp.float32)), {}
    for name, mask in lives.items():
        (x0, c0, ms0), (x1, c1, ms1) = xla[name], kernel[name]
        # the windows are moved, not computed: bit for bit, and an idle
        # slot's and the trash row's what they were
        np.testing.assert_array_equal(c1, c0)
        np.testing.assert_array_equal(c1[:, :S][:, ~mask],
                                      before[:, :S][:, ~mask])
        np.testing.assert_array_equal(c1[:, S], before[:, S])
        # xc: a bfloat16 ulp where the two round silu's last bit apart
        np.testing.assert_allclose(x1[:, mask], x0[:, mask], rtol=2 ** -7,
                                   atol=2 ** -7)
        tiles = int(mask.reshape(-1, 16).any(axis=1).sum())
        said[name] = {
            "xla_form_ms": round(ms0, 3), "kernel_ms": round(ms1, 3),
            "kernel_us_a_layer": round(ms1 / L * 1e3, 2),
            "tiles_visited": tiles,
            "kernel_GB_s": round(tiles * 16 * L * (6 * Di * 2 + Di * 8)
                                 / ms1 / 1e6, 1),
            "xc_rows_that_differ": int(
                (x1[:, mask] != x0[:, mask]).any(axis=-1).sum())}
    _report("pr52_conv_step", said)
    assert said["128"]["kernel_ms"] <= said["128"]["xla_form_ms"]
    assert said["56 lowest"]["kernel_ms"] * 1.5 \
        <= said["56 lowest"]["xla_form_ms"]


# name -> (bucket, rows attended, history, calls chained in one executable):
# the cell's two buckets over their own rows, and a 2,048-token chunk over
# the slot's gathered 144 pages behind 2,048 and 6,144 tokens of history
FLASH_SHAPES = {"bucket 512": (512, 512, 0, 16),
                "bucket 2048": (2048, 2048, 0, 8),
                "chunk 2048 over 2048": (2048, 9216, 2048, 6),
                "chunk 2048 over 6144": (2048, 9216, 6144, 4)}


def test_deepseek_cell_latent_flash_kernel_beside_the_xla_loop():
    """The prompts' attention at the cell's shapes, alone (128 heads, a
    latent of 512, one layer): the latent flash kernel and the XLA loop it
    replaces (``latent_expanded_attention``: every [128, 256, 256] score
    tile through HBM), calls chained through the queries inside one
    executable; and what the two answer on the same rows."""
    import time

    from llms_on_kubernetes_tpu.ops import attention, pallas_flash

    g = LATENT
    H, lat, rope = g["heads"], g["lat"], g["rope"]
    scale = 192 ** -0.5 * 1.36889 ** 2

    def xla_loop(qn, qr, rows, w_uk, w_uv, history, kv_len):
        q_pos = history[:, None] + jnp.arange(qn.shape[1],
                                              dtype=jnp.int32)[None]
        return attention.latent_expanded_attention(
            qn, qr, rows, w_uk, w_uv, q_pos, kv_len, scale=scale)

    def kernel(*args):
        return pallas_flash.flash_latent_attention.__wrapped__(
            *args, scale=scale)

    def time_ms(fn, args, n_calls):
        @jax.jit
        def chain(qn, *rest):
            return jax.lax.fori_loop(0, n_calls, lambda i, q: fn(q, *rest),
                                     qn)

        best = float("inf")
        for _ in range(4):                      # the first run compiles
            t0 = time.perf_counter()
            jax.block_until_ready(chain(*args))
            best = min(best, time.perf_counter() - t0)
        return best / n_calls * 1e3

    said = {"blocks": dict(zip(("queries", "keys", "heads"),
                               pallas_flash.latent_flash_blocks(2048, 9216,
                                                                H)))}
    for name, (T, S, history, n_calls) in FLASH_SHAPES.items():
        k = jax.random.split(jax.random.key(47), 5)
        rows = jax.random.normal(k[2], (1, S, g["width"]), jnp.bfloat16)
        rows = rows.at[..., lat + rope:].set(0)
        args = (jax.random.normal(k[0], (1, T, H, 128), jnp.bfloat16),
                jax.random.normal(k[1], (1, T, H, rope), jnp.bfloat16), rows,
                jax.random.normal(k[3], (H, lat, 128), jnp.bfloat16)
                * lat ** -0.5,
                jax.random.normal(k[4], (H, lat, 128), jnp.bfloat16)
                * lat ** -0.5,
                jnp.asarray([history], jnp.int32),
                jnp.asarray([history + T], jnp.int32))
        # score and value products a head: the tiles the causal mask and
        # kv_len leave, at the XLA loop's 256 x 256
        pairs = sum(1 for i in range((history + T) // 256)
                    for j in range(T // 256)
                    if i * 256 <= history + j * 256 + 255)
        flop = pairs * H * 256 * 256 * 2 * (128 + rope + 128)
        want, got = _f32(xla_loop(*args)), _f32(kernel(*args))
        # both against the same rows in float32 at the highest precision:
        # the kernel may not be further from it than the loop it replaces
        with jax.default_matmul_precision("highest"):
            exact = _f32(xla_loop(*(a.astype(jnp.float32) if a.dtype
                                    == jnp.bfloat16 else a for a in args)))
        said[name] = {
            "xla_loop_off_float32": float(np.abs(want - exact).max()),
            "kernel_off_float32": float(np.abs(got - exact).max()),
            "xla_loop_ms": round(time_ms(xla_loop, args, n_calls), 3),
            "kernel_ms": round(time_ms(kernel, args, 2 * n_calls), 3),
            "tile_pairs": pairs, "tiles_GFLOP": round(flop / 1e9, 1),
            "max_abs_diff": float(np.abs(got - want).max()),
            "max_abs_value": float(np.abs(want).max())}
        said[name]["kernel_share_of_197_TFLOP_s"] = round(
            flop / said[name]["kernel_ms"] / 197e9, 3)
        # both round p to bfloat16 on its way into the MXU, after a running
        # maximum over blocks of keys
        np.testing.assert_allclose(got, want, rtol=2 ** -6, atol=2 ** -6)
    _report("pr47_latent_flash", said)
    for name in FLASH_SHAPES:
        assert said[name]["kernel_ms"] < said[name]["xla_loop_ms"], said
        assert (said[name]["kernel_off_float32"]
                <= 1.5 * said[name]["xla_loop_off_float32"]), said


# ---------------------------------------------------------------------------
# deepseek-v3's cell: the served functions, teacher-forced, against the
# float32 reference's full forward pass
# ---------------------------------------------------------------------------

# largest difference, in nats, of the log-probabilities of the reference's
# eight best ids at a position (what the benchmark's check compares at the
# first generated position only), over the last prompt position and 16
# decode positions of two sequences. PERF.md section 6, PR 42, has the
# readings this lies between: the served path's, and the same reference
# over layer matrices cut to float8_e4m3fn
DEEPSEEK_TOL_NATS = 0.35


def test_deepseek_cell_teacher_forced_prefill_chunk_and_decode():
    """At the cell's six layers and published widths, the functions the
    engine's steps call: a 300-token prompt through the 512 bucket, a
    2,560-token prompt through a 2,048-token chunk and a 512-token chunk
    over its cached latent rows, then 16 absorbed decode steps of both
    through the latent cache, every token given (teacher-forced). Logits
    against benchmark/reference/deepseek_v3.py's full expanded forward pass
    of each whole sequence on the same bfloat16 weights. Last, the control:
    the same reference over layer matrices cut to float8_e4m3fn reads over
    the tolerance at those positions."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmark"))
    from reference import deepseek_v3 as ref

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, init_pages
    from llms_on_kubernetes_tpu.models import decoder as dec
    from llms_on_kubernetes_tpu.ops import attention

    with open("benchmark/configs/deepseek-v3.json") as f:
        ref_cfg = json.load(f)
    cfg = get_config(ref_cfg["registry_name"])
    params = dec.init_params(cfg, jax.random.key(0), dtype="bfloat16")
    rng = np.random.default_rng(42)
    N, page, pps, B = 16, 64, 48, 4
    plen = {0: 300, 2: 2560}
    seqs = {s: rng.integers(0, cfg.vocab_size, n + N).astype(np.int32)
            for s, n in plen.items()}
    heads, width = cfg.cache_row
    cc = CacheConfig(num_layers=cfg.num_attn_layers, num_kv_heads=heads,
                     head_dim=width, num_pages=B * pps + 1, page_size=page,
                     pages_per_slot=pps, latent=True)
    kp, vp = init_pages(cc)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    chunk = jax.jit(dec.forward_chunk, static_argnums=(1,),
                    donate_argnums=(5, 6))
    decode = jax.jit(dec.forward_decode, static_argnums=(1,),
                     donate_argnums=(4, 5))

    def padded(tokens, bucket):
        out = np.zeros((1, bucket), np.int32)
        out[0, :len(tokens)] = tokens
        return jnp.asarray(out)

    # the experts the served path chooses, layer by layer, for the first
    # prompt's tokens: counted against the reference's choices below
    from llms_on_kubernetes_tpu.ops import moe

    chosen, route = [], moe.route

    def recording_route(*a, **kw):
        sel, weight = route(*a, **kw)
        jax.debug.callback(lambda s: chosen.append(np.asarray(s)), sel,
                           ordered=True)
        return sel, weight

    got = {s: [] for s in seqs}
    moe.route = recording_route
    try:
        logits, kp, vp, _ = jax.jit(
            dec.forward_prefill, static_argnums=(1,), donate_argnums=(4, 5))(
            params, cfg, padded(seqs[0][:300], 512), jnp.asarray([300]), kp,
            vp, pt[0:1], aux=dec.LayerAux())
        jax.effects_barrier()
    finally:
        moe.route = route
    got[0].append(np.asarray(logits[0]))
    for at, n, bucket in ((0, 2048, 2048), (2048, 512, 512)):
        logits, kp, vp, _ = chunk(
            params, cfg, padded(seqs[2][at:at + n], bucket),
            jnp.asarray([at]), jnp.asarray([n]), kp, vp, pt[2:3],
            aux=dec.LayerAux())
    got[2].append(np.asarray(logits[0]))
    for step in range(N - 1):
        toks, lens = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for s in seqs:
            toks[s] = seqs[s][plen[s] + step]
            lens[s] = plen[s] + step + 1
        logits, kp, vp, aux = decode(params, cfg, jnp.asarray(toks),
                                     jnp.asarray(lens), kp, vp, pt,
                                     aux=dec.LayerAux())
        for s in seqs:
            got[s].append(np.asarray(logits[s]))
    said = {op: attention._chosen[op] for op in ("prefill", "chunk", "decode")}
    # the prompts' paths ran the latent flash kernel (PR 47)
    assert said["prefill"][0] == said["chunk"][0] == "pallas-compiled"
    assert said["prefill"][1].startswith("latent flash kernel")
    assert said["chunk"][1].startswith("latent flash kernel")
    # the absorbed steps ran the latent kernel (PR 43)
    assert said["decode"][0] == "pallas-compiled"
    assert said["decode"][1].startswith("latent")
    del kp, vp

    def reference(p):
        return {s: np.asarray(jax.nn.log_softmax(ref.logits_at(
            ref_cfg, p, seqs[s].tolist(),
            list(range(plen[s] - 1, plen[s] - 1 + N))))) for s in seqs}

    def worst_of_best8(lps, want):
        """Per sequence and position: the largest difference over the
        reference's eight best ids."""
        out = {}
        for s in want:
            best = np.argsort(want[s], -1)[:, -8:]
            out[s] = np.abs(np.take_along_axis(lps[s], best, -1)
                            - np.take_along_axis(want[s], best, -1)).max(-1)
        return out

    want = reference(params)
    # routings the served bfloat16 path decides differently from the
    # float32 reference: (token, layer) pairs of the 300-token prompt whose
    # sets of 8 chosen experts differ, and those among them where the
    # difference reaches an expert HELD here (only that changes a result)
    theirs = []
    with jax.default_matmul_precision("highest"):
        ref._hidden(ref_cfg, params, seqs[0][:300].tolist(),
                    lambda _i, g, lp: theirs.append(np.asarray(ref.route(
                        g, lp["router"].astype(jnp.float32),
                        lp["router_bias"].astype(jnp.float32), top_k=8,
                        n_group=8, topk_group=4, renorm=True, scale=2.5)) > 0))
    routed = differ = differ_held = 0
    for sel, want_sel in zip(chosen, theirs):
        mine = np.zeros_like(want_sel)
        np.put_along_axis(mine, sel[:300], True, axis=1)
        wrong = mine != want_sel
        routed += 300
        differ += int(wrong.any(axis=1).sum())
        differ_held += int(wrong[:, :16].any(axis=1).sum())
    assert len(chosen) == len(theirs) == cfg.num_moe_layers
    served = worst_of_best8(
        {s: np.asarray(jax.nn.log_softmax(jnp.asarray(np.stack(got[s]))))
         for s in seqs}, want)
    logit_diff = {s: float(np.abs(
        (np.stack(got[s]) - np.stack(got[s]).mean(-1, keepdims=True))
        - (want[s] - want[s].mean(-1, keepdims=True))).max()) for s in seqs}
    # the control: every layer matrix cut to the nearest type below, in
    # place (a second copy of the weights does not fit beside them)
    for run in params["layers"]:
        for name, w in list(run.items()):
            if w.ndim >= 3:
                run[name] = w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                w.delete()
    control = worst_of_best8(reference(params), want)
    report = {
        "said": {op: why for op, (_, why) in said.items()},
        "served_nats": {
            "prefill_512": float(served[0][0]),
            "chunks_2048_512": float(served[2][0]),
            "decode_after_prefill_max": float(served[0][1:].max()),
            "decode_after_chunks_max": float(served[2][1:].max()),
            "per_position": {s: [round(float(x), 4) for x in served[s]]
                             for s in served}},
        "served_centered_logit_diff_max": logit_diff,
        "routings": {"compared": routed, "decided_differently": differ,
                     "of_them_over_a_held_expert": differ_held},
        "control_float8_nats": {
            "min": float(min(control[s].min() for s in control)),
            "max": float(max(control[s].max() for s in control)),
            "per_sequence_max": {s: float(control[s].max())
                                 for s in control}},
        "tolerance_nats": DEEPSEEK_TOL_NATS}
    print("[pr43_teacher_forced]", json.dumps(report), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pr43_teacher_forced.json", "w") as f:
        json.dump(report, f, indent=1)
    assert max(served[s].max() for s in served) < DEEPSEEK_TOL_NATS
    assert all(control[s].max() > DEEPSEEK_TOL_NATS for s in control)
