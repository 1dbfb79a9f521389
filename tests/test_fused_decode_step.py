"""The decode step's KV append inside the paged decode kernel (PR 34).

``dispatch_paged_attention_write`` takes the fused write+attend kernel
wherever ``_paged_kernel_mode`` admits the paged decode kernel, and is
``attention.write_then_attend`` (``write_tokens`` +
``dispatch_paged_attention``) everywhere else; no setting reaches that
choice (PR 49). Here, on
the CPU, the kernel runs through the Pallas interpreter inside the engine's
own K-step decode window (``_decode_multi_packed_step``); the hardware
suite (tests/test_tpu_hardware.py) drives the same window, through
``decode_window`` below, on the Mosaic lowering at mistral-7b's shapes.

What "the same pool" can mean for a whole step: a layer's K/V rows are
computed from the layer below, whose attention output differs between the
two paths by the order of the last softmax merge (on the chip by a bf16
rounding, which 32 layers of random weights amplify until near-ties
flip). So every layer is held to the same WRITTEN ROWS, layer 0's rows are
held byte for byte (they depend on the input tokens alone) and the deeper
layers' to a tolerance, both for as long as the two sides sampled the same
tokens.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine import cache as C
from llms_on_kubernetes_tpu.engine import engine as E
from llms_on_kubernetes_tpu.ops import attention


def window_rows(lengths0, budgets, tokens, page_size, pps, num_pages):
    """The packed rows of one decode window (greedy, no stops, no bias) and
    the pages it needs: row b gets the next free pages for the positions it
    may write, trash page 0 elsewhere (as the allocator leaves them)."""
    B = len(lengths0)
    packed = np.zeros((B, E._DEC_COLS + pps), np.int32)
    packed[:, 0] = lengths0
    packed[:, 1] = 1                                     # src: host value
    packed[:, 2] = tokens
    packed[:, 5] = np.float32(1.0).view(np.int32)        # top_p off
    packed[:, E._ADP_DEC] = -1
    packed[:, E._FSM_DEC] = -1
    packed[:, E._BUD_DEC] = budgets
    packed[:, E._STOP_DEC:E._STOP_DEC + E.STOP_SLOTS] = -1
    packed[:, E._BIAS_DEC:E._BIAS_DEC + E.LOGIT_BIAS_SLOTS] = -1
    free = 1
    for b in range(B):
        if lengths0[b] > 0:
            # lengths0 counts the first step's token; each step adds one
            last = int(lengths0[b]) + max(int(budgets[b]) - 1, 0)
            n = -(-last // page_size)
            packed[b, E._DEC_COLS:E._DEC_COLS + n] = np.arange(free, free + n)
            free += n
    assert free <= num_pages, (free, num_pages)
    return packed


def window_args(cfg, params, packed, k_pages, v_pages):
    """The decode step's arguments after (cfg, K): nothing in flight, fresh
    counts; the pools and the counts are the donated ones (4, 5, 6)."""
    B = packed.shape[0]
    return [params, jnp.asarray(packed), jnp.zeros((B,), jnp.int32),
            jnp.zeros((1,), jnp.int32), k_pages, v_pages,
            jnp.zeros((B, cfg.vocab_size), jnp.int32), jax.random.key(0)]


def decode_window(cfg, params, k_pages, v_pages, packed, K, two_op=False):
    """One K-step window of the engine's decode step, traced afresh: as
    the dispatcher chooses, or with ``two_op`` on the reference path
    (``attention.write_then_attend`` in the dispatcher's place while the
    step is traced). Returns (packs [K, B, W] on the host, k_pages,
    v_pages, compiled)."""
    dispatcher = attention.dispatch_paged_attention_write
    if two_op:
        attention.dispatch_paged_attention_write = attention.write_then_attend
    try:
        # a function of its own: jit's trace cache is keyed by the function
        step = jax.jit(
            lambda p, *a: E._decode_multi_packed_step(p, cfg, K, *a),
            donate_argnums=(4, 5, 6))
        args = window_args(cfg, params, packed, k_pages, v_pages)
        compiled = step.lower(*args).compile()
        packs, _toks, k_pages, v_pages, _counts, _, _ = compiled(*args)
    finally:
        attention.dispatch_paged_attention_write = dispatcher
    return np.asarray(packs), k_pages, v_pages, compiled


def layer_blocks(pool, num_layers):
    """[L, n_kv, P, page, d] view of a flat pool's data, on the host, as
    raw bits (NaN-safe, dtype-blind equality)."""
    a = np.asarray(pool.data)
    a = a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])
    n_kv, LP, page, d = a.shape
    return np.moveaxis(a.reshape(n_kv, num_layers, LP // num_layers, page, d),
                       1, 0)


def check_same_pool(got, want, init, num_layers, packed, packs, page_size,
                    to_float, tol):
    """``got`` against ``want`` (both written from ``init``; ``packs`` the
    two sides' sampled rows). Outside each layer's trash page 0 the same
    rows are written in every layer, whatever was sampled. A row written at
    a step up to which both sides fed the slot the same tokens is, in
    layer 0, the same bytes, and in every layer within ``tol`` of the
    other's (rms over the row, relative). Returns (rows compared, the
    largest relative rms)."""
    g, w, i = (layer_blocks(p, num_layers) for p in (got, want, init))
    np.testing.assert_array_equal((g != i).any(-1)[:, :, 1:],
                                  (w != i).any(-1)[:, :, 1:])
    agree = packs[0][..., 0] == packs[1][..., 0]             # [K, B]
    n, worst = 0, 0.0
    for b in np.nonzero(packed[:, 0] > 0)[0]:
        for j in range(packed[b, E._BUD_DEC]):
            if not agree[:j, b].all():
                break
            pos = packed[b, 0] - 1 + j
            pid = packed[b, E._DEC_COLS + pos // page_size]
            gr, wr = g[:, :, pid, pos % page_size], w[:, :, pid, pos % page_size]
            np.testing.assert_array_equal(gr[0], wr[0])
            assert (wr != i[:, :, pid, pos % page_size]).any(-1).all()
            a, c = to_float(gr), to_float(wr)
            worst = max(worst, float(np.sqrt(((a - c) ** 2).mean()
                                             / (c ** 2).mean())))
            n += 1
    assert n and worst <= tol, (n, worst)
    return n, worst


def top_logprobs(packs):
    K, B, W = packs.shape
    n = (W - 2) // 2
    return np.ascontiguousarray(packs[..., 2 + n:]).view(np.float32)


# page 8, 4 pages a slot. "mixed" rows: the window crosses a page boundary
# (writes positions 6, 7 | 8, 9); the first write is a page's last row;
# idle; the budget ends inside the window; one token; live but riding
# masked; idle. "idle runs" is what the kernel's pipeline across rows can
# get wrong (PR 37: a live row's pages are fetched while the live row
# before it attends): the first live row is not row 0, live rows lie
# between runs of idle ones, a one-token row (nothing cached to fetch)
# sits between two longer ones, and the last row is live.
LAYOUTS = {
    "mixed": ([7, 8, 0, 13, 1, 5, 0], [4, 4, 0, 2, 4, 0, 0]),
    "idle runs": ([0, 0, 9, 0, 0, 0, 14, 1, 20, 0, 6], [0, 0, 4, 0, 0, 0, 4, 4, 3, 0, 2]),
}
PAGE, PPS, K = 8, 4, 4


# head widths the compiled kernel takes: 128 as it is, 64 two heads to a
# 128-lane page row (cache.heads_per_row); what the record adds for each
WIDTHS = {128: "", 64: ", 2 heads of 64 to a 128-lane page row"}


@pytest.fixture(scope="module", params=[
    (layout, d) for d in sorted(WIDTHS) for layout in sorted(LAYOUTS)],
    ids=lambda p: f"{p[0]}, {p[1]} wide")
def tiny(request):
    """debug-tiny widened to a head_dim the compiled kernel would take, a
    random pool in the layout the engine would give it (stale rows must be
    maskable garbage, not zeros), and one layout of rows for a decode
    window."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.models.decoder import init_params

    layout, d = request.param
    lengths0, budgets = LAYOUTS[layout]
    cfg = dataclasses.replace(get_config("debug-tiny"), head_dim=d)
    params = init_params(cfg, jax.random.key(1), dtype="float32")
    B = len(lengths0)
    num_pages = B * PPS + 1
    rng = np.random.default_rng(5)
    heads, lanes = C.CacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=d).pool_row
    assert lanes == 128
    shape = (heads, cfg.num_layers * num_pages, PAGE, lanes)
    k0 = rng.normal(size=shape).astype(np.float32)
    v0 = rng.normal(size=shape).astype(np.float32)
    packed = window_rows(lengths0, budgets, rng.integers(1, 200, B), PAGE,
                         PPS, num_pages)

    def pools():
        return C.KVPool(jnp.asarray(k0)), C.KVPool(jnp.asarray(v0))

    return cfg, params, packed, pools, lengths0, budgets


def _f32(bits):
    return bits.view(np.float32)


@pytest.mark.parametrize("two_op,kernel", [
    (False, "fused write+attend kernel"),
    (True, "paged kernel"),
], ids=["fused", "two-op"])
def test_decode_window_fused_and_two_op_match_xla(tiny, monkeypatch,
                                                  two_op, kernel):
    cfg, params, packed, pools, lengths0, budgets = tiny
    kernel += WIDTHS[cfg.head_dim]
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "xla")
    want, wk, wv, _ = decode_window(cfg, params, *pools(), packed, K,
                                    two_op=True)
    assert attention._chosen["decode"][0] == "xla"
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    got, gk, gv, _ = decode_window(cfg, params, *pools(), packed, K, two_op)
    assert attention._chosen["decode"] == ("pallas-interpret", kernel)

    live = np.asarray(lengths0) > 0
    alive = (np.arange(K)[:, None] < np.asarray(budgets)[None]) & live[None]
    written = int(sum(budgets))              # the case is real
    assert alive.sum() == written >= 14
    np.testing.assert_array_equal(got[..., 0][alive], want[..., 0][alive])
    np.testing.assert_allclose(top_logprobs(got)[alive],
                               top_logprobs(want)[alive],
                               rtol=2e-5, atol=2e-5)   # run_fused_write_case's
    k0, v0 = pools()
    for g, w, i in ((gk, wk, k0), (gv, wv, v0)):
        n, _ = check_same_pool(g, w, i, cfg.num_layers, packed, (got, want),
                               PAGE, _f32, 2e-5)
        assert n == written


def _operands(rng, n_kv, d, page, kv_dtype, unpaired=False):
    B, pps, group = 3, 2, 2
    cc = C.CacheConfig(num_layers=1, num_kv_heads=n_kv, head_dim=d,
                       num_pages=B * pps + 1, page_size=page,
                       pages_per_slot=pps, dtype="float32", kv_dtype=kv_dtype)
    kp, vp = C.init_pages(cc)
    if unpaired:     # a logical 64-wide pool, as a caller by hand may build
        kp, vp = (C.KVPool(jnp.zeros((n_kv, *p.shape[1:3], d), p.dtype))
                  for p in (kp, vp))
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    hist = jnp.asarray(rng.normal(size=(B, page, n_kv, d)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(page, dtype=jnp.int32), (B, page))
    kp, vp = C.write_tokens(kp, vp, hist, hist[::-1], pt, pos)
    lengths = jnp.asarray([page + 1, 5, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, n_kv * group, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    wp = jnp.where(lengths > 0, lengths - 1, -1)[:, None]
    return q, kp, vp, pt, lengths, k_new, v_new, wp


# what the dispatcher sees -> why it must take the two-op path by itself,
# on a backend where Pallas compiles (pallas_mode() forced to "compiled")
OBSERVED_OUT = {
    "head_dim 96": (dict(n_kv=2, d=96, page=8, kv_dtype=None), 4,
                    "head_dim 96 is not a multiple of 128, pairs into no "
                    "128-lane row, is not padded"),
    "head_dim 64, odd kv heads": (
        dict(n_kv=3, d=64, page=8, kv_dtype=None), 4,
        "head_dim 64 is not a multiple of 128 and 3 kv heads do not pair"),
    "head_dim 64, int8 pool": (
        dict(n_kv=2, d=64, page=128, kv_dtype="int8"), 4,
        "head_dim 64 is not a multiple of 128 and an int8 pool keeps one "
        "scale a head, so heads stay apart"),
    "head_dim 64, a pool nobody paired": (
        dict(n_kv=2, d=64, page=8, kv_dtype=None, unpaired=True), 4,
        "head_dim 64 is not a multiple of 128 and the pool holds one head "
        "a row"),
    "traced window": (dict(n_kv=2, d=128, page=8, kv_dtype=None), "traced",
                      "traced (per-layer) sliding window"),
    "int8 pool at page 64": (dict(n_kv=2, d=128, page=64, kv_dtype="int8"), 4,
                             "int8 KV needs page_size % 128 == 0, got 64"),
}


@pytest.mark.parametrize("case", sorted(OBSERVED_OUT))
def test_dispatcher_observes_itself_out(rng, monkeypatch, case):
    """Nothing asks for it. The result is the two-op path's bit for bit,
    and the record says why."""
    geometry, window, why = OBSERVED_OUT[case]
    q, kp, vp, pt, lengths, k_new, v_new, wp = _operands(rng, **geometry)
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")

    def both(window):
        got = attention.dispatch_paged_attention_write(
            q, kp, vp, pt, lengths, k_new, v_new, wp, scale=0.1,
            sliding_window=window)
        k2, v2 = C.write_tokens(kp, vp, k_new[:, None], v_new[:, None], pt, wp)
        want = attention.paged_attention(q, k2, v2, pt, lengths, scale=0.1,
                                         sliding_window=window)
        return got, (want, k2, v2)

    if window == "traced":
        got, want = jax.jit(both)(jnp.int32(4))
    else:
        got, want = both(window)
    assert attention._chosen["decode"] == ("xla", why)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("how", ["environment", "field"])
def test_no_setting_reaches_the_decode_write(monkeypatch, how):
    """The choice is the code's alone (PR 49). The environment name that
    used to take an engine off the kernel is read nowhere: an engine built
    under it runs its K = 4 decode windows on the write-and-attend kernel.
    The config field that carried it is not a field."""
    if how == "field":
        with pytest.raises(TypeError):
            E.EngineConfig(model="debug-tiny", kv_write="dus")
        return
    monkeypatch.setenv("LLMK_KV_WRITE", "dus")
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    # the engine's step traces are shared between Engine objects
    jax.clear_caches()
    try:
        eng = E.Engine(E.EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=2,
            page_size=16, num_pages=64, pages_per_slot=8,
            prefill_buckets=(16,), decode_steps=4))
        out = eng.generate(list(range(1, 12)),
                           E.SamplingParams(temperature=0.0, max_tokens=8))
    finally:
        jax.clear_caches()
    assert len(out) == 8
    assert attention._chosen["decode"] == ("pallas-interpret",
                                           "fused write+attend kernel")
