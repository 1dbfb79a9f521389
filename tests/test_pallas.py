"""Pallas kernels vs the XLA reference attention ops.

ops/attention.py is the semantically-authoritative implementation
(its own tests pin it against brute-force numpy); these tests pin the
Pallas kernels to it in interpreter mode so they run in CI without TPU
hardware — the compiled path is exercised by tests/test_tpu_hardware.py
and chip_smoke.py on the real chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.cache import CacheConfig, PageAllocator, init_pages, write_tokens
from llms_on_kubernetes_tpu.ops.attention import paged_attention, prefill_attention
from llms_on_kubernetes_tpu.ops.pallas_flash import flash_prefill_attention
from llms_on_kubernetes_tpu.ops.pallas_paged import pallas_paged_attention


def _qkv(rng, B, T, n_q, n_kv, d):
    q = jnp.asarray(rng.normal(size=(B, T, n_q, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window,softcap", [(None, None), (5, None), (None, 30.0)])
def test_flash_prefill_matches_reference(rng, window, softcap):
    B, T, n_q, n_kv, d = 2, 16, 4, 2, 8
    q, k, v = _qkv(rng, B, T, n_q, n_kv, d)
    lengths = jnp.asarray([16, 9], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, scale=d ** -0.5,
                            sliding_window=window, attn_softcap=softcap)
    out = flash_prefill_attention(q, k, v, lengths, scale=d ** -0.5,
                                  sliding_window=window, attn_softcap=softcap,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # rows past a sequence's length are padding whose values are unused;
    # only compare valid rows (done above: reference zeros them identically
    # because both softmax over NEG_INF-masked logits)


def test_flash_prefill_multiblock(rng):
    """T spanning several 128-wide q blocks, uneven lengths."""
    B, T, n_q, n_kv, d = 2, 256, 2, 1, 16
    q, k, v = _qkv(rng, B, T, n_q, n_kv, d)
    lengths = jnp.asarray([256, 130], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, scale=d ** -0.5)
    out = flash_prefill_attention(q, k, v, lengths, scale=d ** -0.5,
                                  interpret=True)
    # compare only valid rows; padding rows are don't-care
    for b, n in enumerate([256, 130]):
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n],
                                   rtol=2e-5, atol=2e-5)


def _paged_setup(rng, B, n_kv, d, page, pages_per_seq, lengths):
    P = B * pages_per_seq + 1
    k_pages = jnp.asarray(rng.normal(size=(n_kv, P, page, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(n_kv, P, page, d)), jnp.float32)
    # distinct page tables with some shared structure
    table = np.zeros((B, pages_per_seq), np.int32)
    perm = rng.permutation(P - 1) + 1
    for b in range(B):
        used = -(-lengths[b] // page)
        table[b, :used] = perm[b * pages_per_seq:b * pages_per_seq + used]
    return k_pages, v_pages, jnp.asarray(table)


# The paged decode kernels run ONE pipeline across their grid steps (the
# next live row's pages are fetched while this row attends; ops/
# pallas_paged.py). What that can get wrong, at a geometry with several
# attention blocks a slot (page 64 x 24 pages: three blocks of 512 tokens):
# name -> (lengths incl. the current token, sliding window, rows whose first
# page is ONE adopted prefix page)
PIPE = dict(n_kv=2, group=2, d=8, page=64, pps=24)
PIPELINE_CASES = {
    "all rows idle": ([0, 0, 0, 0], None, ()),
    "first live row is not row 0 and the last row is live":
        ([0, 0, 70, 0, 0, 0, 9, 0, 130], None, ()),
    "one token between two long rows": ([600, 1, 1100], None, ()),
    "three blocks next to one page": ([1500, 40, 0, 1536, 33], None, ()),
    "window skips leading blocks": ([1500, 1030, 0, 520, 1], 600, ()),
    "two rows share a prefix page": ([200, 0, 300], None, (0, 2)),
}
# the parent commit's kernels on these inputs, bit for bit (block size and
# order of arithmetic did not change with the pipeline)
GOLDEN = {"one token between two long rows", "window skips leading blocks"}


def _golden(kernel, case):
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "paged_decode_golden.npz")
    return np.load(path)[f"{kernel}: {case}"]


def _share_first_page(table, share):
    """Rows ``share[1:]`` adopt row ``share[0]``'s first page."""
    table = np.asarray(table).copy()
    for b in share[1:]:
        table[b, 0] = table[share[0], 0]
    return jnp.asarray(table)


# name -> (geometry, lengths, window, softcap, rows sharing a first page)
_SMALL = dict(n_kv=2, group=2, d=8, page=4, pps=4)
DECODE_CASES = {
    "small": (_SMALL, [13, 16, 5], None, None, ()),
    "small, window 7": (_SMALL, [13, 16, 5], 7, None, ()),
    "small, softcap 50": (_SMALL, [13, 16, 5], None, 50.0, ()),
    **{name: (PIPE, lengths, window, None, share)
       for name, (lengths, window, share) in PIPELINE_CASES.items()},
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_paged_decode_matches_reference(rng, case):
    geo, lengths_np, window, softcap, share = DECODE_CASES[case]
    n_kv, d, page, pps = geo["n_kv"], geo["d"], geo["page"], geo["pps"]
    lengths_np = np.asarray(lengths_np, np.int32)
    B, n_q = len(lengths_np), n_kv * geo["group"]
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps, lengths_np)
    table = _share_first_page(table, share)
    q = jnp.asarray(rng.normal(size=(B, n_q, d)), jnp.float32)
    lengths = jnp.asarray(lengths_np)
    ref = paged_attention(q, k_pages, v_pages, table, lengths,
                          scale=d ** -0.5, sliding_window=window,
                          attn_softcap=softcap)
    out = pallas_paged_attention(q, k_pages, v_pages, table, lengths,
                                 scale=d ** -0.5, sliding_window=window,
                                 attn_softcap=softcap, interpret=True)
    act = lengths_np > 0     # an idle row reads 0 here, an average there
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(ref)[act],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()
    if case in GOLDEN:
        np.testing.assert_array_equal(np.asarray(out),
                                      _golden("plain", case))


def test_paged_decode_idle_slot(rng):
    """length 0 rows (idle decode slots) must not NaN."""
    B, n_q, n_kv, d, page, pps = 2, 2, 1, 8, 4, 2
    lengths_np = np.asarray([6, 0], np.int32)
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps, lengths_np)
    q = jnp.asarray(rng.normal(size=(B, n_q, d)), jnp.float32)
    out = pallas_paged_attention(q, k_pages, v_pages, table,
                                 jnp.asarray(lengths_np),
                                 scale=d ** -0.5, interpret=True)
    assert np.isfinite(np.asarray(out)).all()  # incl. idle row 1


def test_paged_decode_through_cache_write_path(rng):
    """End-to-end with the real cache plumbing: write tokens via
    write_tokens, then decode-attend with both implementations."""
    cfg = CacheConfig(num_layers=1, num_kv_heads=2, head_dim=8,
                      num_pages=32, page_size=4, pages_per_slot=4,
                      dtype="float32")
    k_pages, v_pages = init_pages(cfg)
    alloc = PageAllocator(cfg.num_pages, cfg.page_size, 2, cfg.pages_per_slot)
    T = 7
    alloc.allocate(0, T)
    alloc.allocate(1, 5)
    table = jnp.asarray(alloc.page_tables)

    k_new = jnp.asarray(rng.normal(size=(2, T, 2, 8)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(2, T, 2, 8)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (2, T))
    lengths = jnp.asarray([T, 5], jnp.int32)
    write_positions = jnp.where(positions < lengths[:, None], positions, -1)
    # num_layers=1: the flat pool [KV, 1*P, page, d] IS the single layer
    kp, vp = write_tokens(k_pages, v_pages, k_new, v_new, table,
                          write_positions)

    q = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
    ref = paged_attention(q, kp, vp, table, lengths, scale=8 ** -0.5)
    out = pallas_paged_attention(q, kp.data, vp.data, table, lengths,
                                 scale=8 ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("head_dim", [16, 64])
def test_engine_greedy_identical_under_pallas(monkeypatch, head_dim):
    """Full engine decode with LLMK_ATTENTION_IMPL=pallas (interpreted on
    CPU) must emit the same greedy tokens as the XLA path: at debug-tiny's
    own 16-wide heads, and widened to 64, where the engine's pool holds
    two heads to a row and prefill, chunk and decode all read it."""
    import dataclasses

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams
    from llms_on_kubernetes_tpu.ops import attention

    cfg = dataclasses.replace(get_config("debug-tiny"), head_dim=head_dim)

    def run():
        eng = Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=2,
            page_size=16, num_pages=64, pages_per_slot=8,
            prefill_buckets=(16,),
        ), model_config=cfg)
        assert eng.k_pages.shape[3] == (128 if head_dim == 64 else head_dim)
        # 21 tokens: a prefill bucket and a chunk; 8 more by decode
        return eng.generate(list(range(1, 22)),
                            SamplingParams(temperature=0.0, max_tokens=8))

    # the engine's step traces are shared between Engine objects, and the
    # variable is read at trace time: without this the second engine would
    # run the first one's executables
    jax.clear_caches()
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "xla")
    ref = run()
    assert attention._chosen["decode"][0] == "xla"
    jax.clear_caches()
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    try:
        out = run()
    finally:
        jax.clear_caches()
    assert out == ref, f"pallas diverged: {out} vs {ref}"
    assert attention._chosen["decode"] == (
        "pallas-interpret", "fused write+attend kernel" + (
            ", 2 heads of 64 to a 128-lane page row" if head_dim == 64
            else ""))


def run_fused_write_case(rng, lengths_np, *, n_kv, group, d, page, pps,
                         interpret, rtol=2e-5, atol=2e-5, window=None,
                         share=()):
    """One fused write+attend case against the DUS reference: same
    attention rows (active slots), finite output everywhere (idle rows
    must not NaN), and byte-identical pools outside the never-read trash
    page 0. ``share``: rows whose first page is one adopted prefix page
    (read by each, written by none). Shared with the hardware suite
    (test_tpu_hardware.py) so the interpret-mode and Mosaic-lowered paths
    pin the SAME cases. Returns the attention rows."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool, write_tokens
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write,
    )

    lengths_np = np.asarray(lengths_np, np.int32)
    B, n_q = len(lengths_np), n_kv * group
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps,
                                           lengths_np)
    table = _share_first_page(table, share)
    q = jnp.asarray(rng.normal(size=(B, n_q, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    lengths = jnp.asarray(lengths_np)

    wp = np.where(lengths_np > 0, lengths_np - 1, -1)[:, None].astype(np.int32)
    kp_ref, vp_ref = write_tokens(
        KVPool(k_pages), KVPool(v_pages), k_new[:, None], v_new[:, None],
        table, jnp.asarray(wp))
    ref = paged_attention(q, kp_ref.data, vp_ref.data, table, lengths,
                          scale=d ** -0.5, sliding_window=window)

    out, kp2, vp2 = pallas_paged_attention_write(
        q, k_pages, v_pages, table, lengths, k_new, v_new,
        scale=d ** -0.5, sliding_window=window, interpret=interpret)
    act = lengths_np > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(ref)[act],
                               rtol=rtol, atol=atol)
    assert np.isfinite(np.asarray(out)).all()
    # pool bytes are DMA'd, not computed — exact equality holds on
    # hardware too (the DUS reference writes idle rows to the trash page;
    # the fused kernel skips them entirely, hence [:, 1:])
    np.testing.assert_array_equal(np.asarray(kp2)[:, 1:],
                                  np.asarray(kp_ref.data)[:, 1:])
    np.testing.assert_array_equal(np.asarray(vp2)[:, 1:],
                                  np.asarray(vp_ref.data)[:, 1:])
    return np.asarray(out)


def test_paged_fused_write_page_boundary(rng):
    """Writes landing on the LAST row of a page (length % page == 0) and
    the FIRST row of a freshly-allocated page (length % page == 1) — both
    edges of the kernel's 8-row aligned read-modify-write block."""
    page, pps = 8, 4
    run_fused_write_case(
        rng, [page, page + 1, 3 * page, 3 * page + 1],
        n_kv=2, group=2, d=8, page=page, pps=pps, interpret=True)


# page >= 8: the kernel's read-modify-write block is 8 rows deep
WRITE_CASES = {
    "every row idle": (dict(n_kv=1, group=2, d=8, page=8, pps=2),
                       [0, 0, 0], None, ()),
    "idle rows between live ones": (dict(n_kv=2, group=2, d=8, page=8, pps=2),
                                    [0, 5, 0, 8, 1], None, ()),
    **{name: (PIPE, *case) for name, case in PIPELINE_CASES.items()},
}


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_paged_fused_write_idle_rows(rng, case):
    """Idle rows (length 0): no NaN, no pool write, no DMA started for or
    by them. The all-idle batch (every program skips its write), idle rows
    interleaved with active ones, and what the pipeline across rows can
    get wrong (PIPELINE_CASES)."""
    geo, lengths, window, share = WRITE_CASES[case]
    out = run_fused_write_case(rng, lengths, **geo, interpret=True,
                               window=window, share=share)
    if case in GOLDEN:
        np.testing.assert_array_equal(out, _golden("write", case))


_DEFERRED = """
import numpy as np
from jax.experimental.pallas import tpu as pltpu
import test_kv_int8, test_pallas as T
deferred = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                 detect_races=True,
                                 uninitialized_memory="nan")
for case in sorted(T.PIPELINE_CASES):
    lengths, window, share = T.PIPELINE_CASES[case]
    T.run_fused_write_case(np.random.default_rng(0), lengths, **T.PIPE,
                           interpret=deferred, window=window, share=share)
test_kv_int8.test_fused_write_int8_k1_matches_write_tokens(
    9, None, "three blocks", interpret=deferred)
for case in ("idle rows among live ones", "page 64, three blocks",
             "a stale staging half holds NaN beyond n_valid"):
    T.check_latent_case(np.random.default_rng(0), case, interpret=deferred)
from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu
print("races", tpu.races.races_found)
"""


def test_paged_pipeline_with_dmas_that_land_at_their_wait():
    """The plain interpreter copies at ``start``, so it cannot see a block
    attended before its fetch was waited for, or a wait on the wrong half.
    Pallas' TPU interpreter copies at the ``wait`` (uninitialised VMEM
    reads NaN) and looks for races: the pipeline's cases, and the int8
    twin, hold there too. In a process of its own, with a time limit: a
    wait for a DMA nobody started does not fail, it hangs."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [here, os.path.dirname(here), os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run([sys.executable, "-c", _DEFERRED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    assert "races False" in run.stdout, run.stdout[-1000:]


@pytest.mark.parametrize("window,softcap", [(None, None), (9, None), (None, 40.0)])
def test_paged_decode_fused_write_matches_reference(rng, window, softcap):
    """The fused write+attend kernel (decode KV append folded into the
    attention program — the round-5 replacement for the per-slot DUS
    loop) must match write_tokens + paged_attention exactly: same
    attention output and, outside the never-read trash page 0, the same
    pool bytes. Covers mid-page, page-boundary, length-1, and idle rows."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool, write_tokens
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write,
    )

    B, n_q, n_kv, d, page, pps = 5, 4, 2, 8, 8, 4
    lengths_np = np.asarray([13, 16, 1, 0, 32], np.int32)  # 16, 32: new page
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps, lengths_np)
    q = jnp.asarray(rng.normal(size=(B, n_q, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    lengths = jnp.asarray(lengths_np)

    wp = np.where(lengths_np > 0, lengths_np - 1, -1)[:, None].astype(np.int32)
    kp_ref, vp_ref = write_tokens(
        KVPool(k_pages), KVPool(v_pages), k_new[:, None], v_new[:, None],
        table, jnp.asarray(wp))
    ref = paged_attention(q, kp_ref.data, vp_ref.data, table, lengths,
                          scale=d ** -0.5, sliding_window=window,
                          attn_softcap=softcap)

    out, kp2, vp2 = pallas_paged_attention_write(
        q, k_pages, v_pages, table, lengths, k_new, v_new,
        scale=d ** -0.5, sliding_window=window, attn_softcap=softcap,
        interpret=True)
    act = lengths_np > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(ref)[act],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()  # idle row must not NaN
    # pools identical outside the trash page (the DUS reference writes
    # idle rows there; the fused kernel skips them entirely)
    np.testing.assert_array_equal(np.asarray(kp2)[:, 1:],
                                  np.asarray(kp_ref.data)[:, 1:])
    np.testing.assert_array_equal(np.asarray(vp2)[:, 1:],
                                  np.asarray(vp_ref.data)[:, 1:])


# ---------------------------------------------------------------------------
# two 64-wide heads to a 128-lane page row (engine/cache.py heads_per_row)
# ---------------------------------------------------------------------------

def pair_heads(pool):
    """A logical pool [n_kv, P, page, d] as the paired one
    [n_kv/2, P, page, 2d]: stored[h, p, t, j*d + c] = logical[2h + j, p, t, c]."""
    n_kv, P, page, d = pool.shape
    x = jnp.moveaxis(jnp.reshape(pool, (n_kv // 2, 2, P, page, d)), 1, 3)
    return x.reshape(n_kv // 2, P, page, 2 * d)


# name -> (kv heads, GQA group, lengths incl. the current token, window);
# page 8, 4 pages a slot
PAIRED_CASES = {
    "page boundary, group 4": (2, 4, [8, 9, 24, 25], None),
    "idle rows and a length of one, group 1": (4, 1, [0, 5, 0, 8, 1], None),
    "static window, group 4": (4, 4, [32, 13, 0, 7], 9),
    "every row idle": (2, 1, [0, 0], None),
}


def _paired_case(rng, case):
    from llms_on_kubernetes_tpu.engine.cache import KVPool

    n_kv, group, lengths_np, window = PAIRED_CASES[case]
    lengths_np = np.asarray(lengths_np, np.int32)
    B, d, page, pps = len(lengths_np), 64, 8, 4
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps,
                                           lengths_np)
    q = jnp.asarray(rng.normal(size=(B, n_kv * group, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, n_kv, d)), jnp.float32)
    wp = jnp.asarray(np.where(lengths_np > 0, lengths_np - 1, -1)[:, None])
    # the two-op path on the LOGICAL 64-wide pool is the yardstick
    kp_ref, vp_ref = write_tokens(
        KVPool(k_pages), KVPool(v_pages), k_new[:, None], v_new[:, None],
        table, wp)
    ref = paged_attention(q, kp_ref, vp_ref, table, jnp.asarray(lengths_np),
                          scale=d ** -0.5, sliding_window=window)
    return (q, k_pages, v_pages, table, jnp.asarray(lengths_np), k_new, v_new,
            wp, window, kp_ref.data, vp_ref.data, np.asarray(ref))


@pytest.mark.parametrize("case", sorted(PAIRED_CASES))
def test_paired_fused_write_matches_two_op_on_logical_pool(rng, case):
    """The fused write+attend kernel on a pool of paired 64-wide heads:
    the attention rows of the two-op path on the logical pool, and after
    the append the SAME pool bytes (paired), outside trash page 0."""
    from llms_on_kubernetes_tpu.ops.pallas_paged import (
        pallas_paged_attention_write,
    )

    (q, k_pages, v_pages, table, lengths, k_new, v_new, _wp, window,
     kp_ref, vp_ref, ref) = _paired_case(rng, case)
    out, kp2, vp2 = pallas_paged_attention_write(
        q, pair_heads(k_pages), pair_heads(v_pages), table, lengths, k_new,
        v_new, scale=64 ** -0.5, sliding_window=window, interpret=True)
    assert out.shape == q.shape and kp2.shape[3] == 128
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[act], ref[act],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(kp2)[:, 1:],
                                  np.asarray(pair_heads(kp_ref))[:, 1:])
    np.testing.assert_array_equal(np.asarray(vp2)[:, 1:],
                                  np.asarray(pair_heads(vp_ref))[:, 1:])


@pytest.mark.parametrize("case", sorted(PAIRED_CASES))
def test_paired_plain_kernel_and_reference_ops_read_the_pairs(rng, case):
    """What else reads a paired pool: ``write_tokens`` (the token's row is
    the pool's row by a reshape), the plain paged kernel, and the XLA
    reference ops, which un-pair after their gather. All against the
    logical pool's answers."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool
    from llms_on_kubernetes_tpu.ops.attention import chunk_attention

    (q, k_pages, v_pages, table, lengths, k_new, v_new, wp, window,
     kp_ref, vp_ref, ref) = _paired_case(rng, case)
    kp2, vp2 = write_tokens(
        KVPool(pair_heads(k_pages)), KVPool(pair_heads(v_pages)),
        k_new[:, None], v_new[:, None], table, wp)
    np.testing.assert_array_equal(np.asarray(kp2.data),
                                  np.asarray(pair_heads(kp_ref)))
    np.testing.assert_array_equal(np.asarray(vp2.data),
                                  np.asarray(pair_heads(vp_ref)))
    act = np.asarray(lengths) > 0
    out = pallas_paged_attention(q, kp2.data, vp2.data, table, lengths,
                                 scale=64 ** -0.5, sliding_window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out)[act], ref[act],
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(out)).all()
    xla = paged_attention(q, kp2, vp2, table, lengths, scale=64 ** -0.5,
                          sliding_window=window)
    np.testing.assert_allclose(np.asarray(xla)[act], ref[act],
                               rtol=1e-6, atol=1e-6)
    # the chunk path: two queries a row ending at the row's last token
    T = 2
    qc = jnp.stack([q, q], 1)
    hist = jnp.maximum(lengths - T, 0)
    n = jnp.minimum(lengths, T)
    want = chunk_attention(qc, kp_ref, vp_ref, table, hist, n,
                           scale=64 ** -0.5, sliding_window=window)
    got = chunk_attention(qc, kp2, vp2, table, hist, n, scale=64 ** -0.5,
                          sliding_window=window)
    np.testing.assert_allclose(np.asarray(got)[act], np.asarray(want)[act],
                               rtol=1e-6, atol=1e-6)


def test_paired_prefill_page_merge(rng):
    """A prefill's page merges into a paired pool (a chunk that starts
    mid-page and crosses into fresh pages) leave the paired bytes of what
    they leave in a logical pool."""
    from llms_on_kubernetes_tpu.engine.cache import KVPool

    n_kv, d, page, pps, T = 4, 64, 8, 4, 13
    start = np.asarray([0, 5, 8], np.int32)
    n_tok = np.asarray([13, 9, 0], np.int32)
    B = len(start)
    k_pages, v_pages, table = _paged_setup(rng, B, n_kv, d, page, pps,
                                           start + T)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    pos = start[:, None] + np.arange(T, dtype=np.int32)[None]
    pos = jnp.asarray(np.where(np.arange(T)[None] < n_tok[:, None], pos, -1))
    want = write_tokens(KVPool(k_pages), KVPool(v_pages), k, v, table, pos)
    got = write_tokens(KVPool(pair_heads(k_pages)),
                       KVPool(pair_heads(v_pages)), k, v, table, pos)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.data)[:, 1:],
                                      np.asarray(pair_heads(w.data))[:, 1:])


# kv heads, head_dim, kv type, model axis -> (heads a row, the reason's gist)
ROW_CASES = {
    "64 wide, even heads": ((8, 64, None, 1), 2, ""),
    "64 wide, model axis divides the pairs": ((8, 64, None, 4), 2, ""),
    "64 wide, model axis splits a pair": ((4, 64, None, 4), 1, "model axis"),
    "64 wide, odd heads": ((3, 64, None, 1), 1, "3 kv heads do not pair"),
    "64 wide, int8": ((8, 64, "int8", 1), 1, "int8"),
    "96 wide": ((32, 96, None, 1), 1, ""),
    "128 wide": ((8, 128, None, 1), 1, ""),
    "16 wide": ((2, 16, None, 1), 1, ""),
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_the_pool_says_its_own_row(case):
    """One rule (``heads_per_row``) decides the stored row; the pools, a
    page's payload check and the bytes a page takes all follow it, and
    the bytes never change with the layout."""
    from llms_on_kubernetes_tpu.engine.cache import (
        heads_per_row, payload_shape_ok,
    )

    (n_kv, d, kv_dtype, shards), pair, gist = ROW_CASES[case]
    got, why = heads_per_row(n_kv, d, kv_dtype, shards)
    assert got == pair and gist in why and bool(why) == bool(gist)
    cc = CacheConfig(num_layers=2, num_kv_heads=n_kv, head_dim=d,
                     num_pages=3, page_size=8, pages_per_slot=2,
                     kv_dtype=kv_dtype, model_shards=shards)
    assert cc.pool_row == (n_kv // pair, d * pair)
    kp, _ = init_pages(cc)
    assert kp.shape == (n_kv // pair, 6, 8, d * pair)
    per_tok = n_kv * (d + 4 if kv_dtype else 2 * d) * 2 * 2
    assert cc.bytes_per_token == per_tok
    assert sum(x.nbytes for x in jax.tree.leaves(kp)) * 2 == \
        cc.bytes_per_page * 3
    page = {"k": np.zeros((n_kv // pair, 2, 8, d * pair), kp.dtype),
            "ks": None, "vs": None}
    page["v"] = page["k"]
    if kv_dtype:
        page["ks"] = page["vs"] = np.zeros((n_kv, 2, 8), np.float32)
    assert payload_shape_ok(page, cc)
    if pair > 1:   # the logical shape is another engine's: a missing page
        page["k"] = page["v"] = np.zeros((n_kv, 2, 8, d), kp.dtype)
        assert not payload_shape_ok(page, cc)


def test_gate_names_the_model_axis_that_would_split_a_pair(monkeypatch):
    """4 KV heads of 64 under ``--tp 4``: the engine's pool stays one head
    a row (each chip keeps one head), and the gate's reason says why."""
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import (
        make_mesh, set_active_mesh,
    )

    cc = CacheConfig(num_layers=1, num_kv_heads=4, head_dim=64, num_pages=2,
                     page_size=8, pages_per_slot=1, model_shards=4)
    assert cc.pool_row == (4, 64)
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    set_active_mesh(make_mesh(model=4, devices=jax.devices()[:4]))
    try:
        mode, why = attention._paged_kernel_mode(
            jnp.zeros((1, 32, 64), jnp.bfloat16),
            jnp.zeros((4, 2, 8, 64), jnp.bfloat16),
            jnp.zeros((1, 1), jnp.int32), None)
    finally:
        set_active_mesh(None)
    assert mode is None and why == (
        "head_dim 64 is not a multiple of 128 and a model axis of 4 does "
        "not divide 2 pairs of heads")


# ---------------------------------------------------------------------------
# the latent pool's decode kernel (DeepSeek MLA, absorbed)
# ---------------------------------------------------------------------------

LAT, ROPE, WIDTH, HEADS = 16, 8, 32, 4
# name -> (page, pages a slot, lengths incl. the current token, rows whose
# first two pages are ONE pair of adopted prefix pages, pool type). A page
# of 16 x 40 pages is two blocks of 320 tokens; 64 x 24 three of 512.
LATENT_CASES = {
    "idle rows among live ones": (16, 40, [0, 70, 0, 0, 330, 0], (), "float32"),
    "all rows idle": (16, 40, [0, 0, 0], (), "float32"),
    "a length of 1": (16, 40, [1, 300, 1], (), "float32"),
    "lengths off a block and off a page boundary":
        (16, 40, [321, 319, 17, 15, 640, 639], (), "float32"),
    "a row of all 144 pages beside a row of one":
        (64, 144, [9216, 5], (), "float32"),
    "two rows share prefix pages": (16, 40, [200, 0, 300], (0, 2), "float32"),
    "page 16, three blocks": (16, 96, [1500, 40, 1536], (), "float32"),
    "page 64, three blocks": (64, 24, [1500, 40, 0, 1536, 33], (), "float32"),
    "bfloat16 pool": (64, 24, [1500, 40, 0, 1536, 33], (), "bfloat16"),
    "float32 pool, float32 queries": (64, 24, [600, 0, 513], (), "float32"),
    # row 0's second block leaves its last page's unwritten rows (NaN here)
    # at offsets 188-191 of staging half 1; row 2's second block lands in
    # that half and fetches offsets 0-127 only: the NaN lies beyond its
    # n_valid (88) in a block that is attended
    "a stale staging half holds NaN beyond n_valid":
        (64, 24, [700, 0, 600], (), "float32"),
}


def _latent_pools(rng, lengths, page, pps, width, share=()):
    """(clean pool, poisoned pool, page table) of rows of ``lengths``
    written tokens behind a permuted page table: the poisoned pool holds
    NaN wherever no row has written."""
    B, P = len(lengths), len(lengths) * pps + 1
    pool = rng.normal(size=(1, P, page, width)).astype(np.float32)
    pool[..., LAT + ROPE:] = 0.0
    table = np.zeros((B, pps), np.int32)
    perm = rng.permutation(P - 1) + 1
    written = np.zeros((P, page), bool)
    for b in range(B):
        used = -(-lengths[b] // page)
        table[b, :used] = perm[b * pps:b * pps + used]
    for b in share[1:]:
        table[b, :2] = table[share[0], :2]
    for b in range(B):
        for t in range(lengths[b]):
            written[table[b, t // page], t % page] = True
    return pool, np.where(written[None, :, :, None], pool, np.nan), table


def latent_case(rng, case):
    """(q_abs, clean pool, poisoned pool, table, lengths) of a latent
    case. The poisoned pool holds NaN wherever no row has written: page 0,
    the pages no table names and a row's last page past its length (the
    kernel must never let them through; the XLA loop multiplies them by a
    zero probability, so it is given the clean pool)."""
    page, pps, lengths, share, dtype = LATENT_CASES[case]
    lengths = np.asarray(lengths, np.int32)
    pool, poisoned, table = _latent_pools(rng, lengths, page, pps, WIDTH,
                                          share)
    q = jnp.asarray(rng.normal(size=(len(lengths), HEADS, LAT + ROPE)), dtype)
    return (q, jnp.asarray(pool, dtype), jnp.asarray(poisoned, dtype),
            jnp.asarray(table), jnp.asarray(lengths))


def check_latent_case(rng, case, interpret=True):
    from llms_on_kubernetes_tpu.ops.attention import latent_paged_attention
    from llms_on_kubernetes_tpu.ops.pallas_paged import pallas_latent_attention

    q, pool, poisoned, table, lengths = latent_case(rng, case)
    ref = latent_paged_attention(q, pool, table, lengths, scale=0.2, lat=LAT)
    out = pallas_latent_attention(q, poisoned, table, lengths, scale=0.2,
                                  lat=LAT, interpret=interpret)
    assert out.shape == (len(lengths), HEADS, LAT) and out.dtype == q.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    act = np.asarray(lengths) > 0
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out[act], ref[act], rtol=tol, atol=tol)
    assert (out[~act] == 0).all()     # an idle row moves nothing: zeros


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_decode_kernel_matches_reference(rng, case):
    """The latent kernel (one kv head whose row all query heads share, key
    and value from the same staged block, products in the pool's type)
    against ``latent_paged_attention`` on what the pipeline across rows
    can get wrong, with NaN wherever nothing was written."""
    check_latent_case(rng, case)


def test_latent_dispatcher_says_why_it_took_the_xla_loop(rng, monkeypatch):
    """``dispatch_latent_decode`` takes the kernel where it can and
    otherwise the XLA loop, recording the reason: the CPU backend, a mesh
    (a latent pool has one head: nothing to give each chip), a page no
    multiple of 8 where Mosaic compiles it."""
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import (
        make_mesh, set_active_mesh,
    )

    q, pool, _, table, lengths = latent_case(rng, "a length of 1")

    def took():
        attention._chosen.clear()
        out = attention.dispatch_latent_decode(q, pool, table, lengths,
                                               scale=0.2, lat=LAT)
        return out, attention._chosen["decode"]

    monkeypatch.delenv("LLMK_ATTENTION_IMPL", raising=False)
    ref, (impl, why) = took()
    assert impl == "xla" and why.startswith("absorbed: 4 query heads")
    assert why.endswith("last block; cpu backend")
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    out, said = took()
    assert said == ("pallas-interpret", "latent: 4 query heads over one "
                    "32-lane row a token, live pages only")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    set_active_mesh(make_mesh(model=2, devices=jax.devices()[:2]))
    try:
        _, (impl, why) = took()
    finally:
        set_active_mesh(None)
    assert impl == "xla" and "a mesh of model 2 x seq 1" in why
    # on the chip: Mosaic's tiling decides, from the pool's stored shape
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    for shape, reason in (((1, 3, 4, 128), "a page of 4 is not a multiple "
                                           "of 8"),
                          ((1, 3, 16, 576), "a pool row of 576 is not a "
                                            "multiple of 128")):
        mode, why = attention._latent_kernel_mode(
            jnp.zeros(shape, jnp.bfloat16), table)
        assert mode is None and why == reason
    assert attention._latent_kernel_mode(
        jnp.zeros((1, 3, 64, 640), jnp.bfloat16), table) == ("compiled", "")


# ---------------------------------------------------------------------------
# the latent rows' flash kernel (DeepSeek MLA, expanded: buckets and chunks)
# ---------------------------------------------------------------------------

NOPE, VDIM, FLASH_WIDTH = 16, 16, 48
# the kernel's constants at this size: query tiles of 16, key blocks of 32
# (two pages of 16), two of the four heads a program
FLASH_BLOCKS = {"LATENT_BLOCK_Q": 16, "LATENT_BLOCK_K": 32, "LATENT_HEADS": 2}
# name -> (bucket, pages a slot or None for a bucket over its own rows,
# (history, tokens) a row, type). A chunk's rows are read from a pool that
# holds NaN wherever nothing was written, through a permuted page table.
FLASH_CASES = {
    "a bucket with ragged lengths": (64, None, [(0, 64), (0, 37), (0, 1),
                                                (0, 33)], "float32"),
    "a bucket beside an idle row": (64, None, [(0, 0), (0, 50)], "float32"),
    "a chunk whose history ends inside a page and a key block":
        (64, 16, [(37, 64)], "float32"),
    "a chunk whose history ends on a page's edge inside a key block":
        (64, 16, [(48, 64)], "float32"),
    "a chunk whose history ends on both edges": (64, 16, [(64, 64)],
                                                 "float32"),
    "a last chunk padded to a smaller bucket": (32, 16, [(96, 11)],
                                                "float32"),
    "an idle row among chunks": (64, 16, [(70, 64), (0, 0), (128, 5)],
                                 "float32"),
    "two rows of different history": (64, 16, [(20, 64), (130, 40)],
                                      "float32"),
    "a chunk up to the slot's last row": (64, 16, [(192, 64)], "float32"),
    "bfloat16 rows and queries": (64, 16, [(37, 64), (150, 20)], "bfloat16"),
}


def flash_case(rng, case):
    """(qn, qr, clean rows, poisoned rows, w_uk, w_uv, history, kv_len) of
    a flash case; a chunk's rows are gathered from the two pools."""
    from llms_on_kubernetes_tpu.ops.attention import _gather_latent

    T, pps, spans, dtype = FLASH_CASES[case]
    history, tokens = np.asarray(spans, np.int32).T
    kv_len, B, page = history + tokens, len(spans), 16

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    qn, qr = normal(B, T, HEADS, NOPE), normal(B, T, HEADS, ROPE)
    w_uk, w_uv = normal(HEADS, LAT, NOPE), normal(HEADS, LAT, VDIM)
    if pps is None:                     # a bucket: its own rows, as wide
        rows = normal(B, T, LAT + ROPE)  # as the projection leaves them
        return (qn, qr, rows, rows, w_uk, w_uv, jnp.asarray(history),
                jnp.asarray(kv_len))
    pool, poisoned, table = _latent_pools(rng, kv_len, page, pps,
                                          FLASH_WIDTH)
    clean, poisoned = (_gather_latent(jnp.asarray(a, dtype),
                                      jnp.asarray(table))
                       for a in (pool, poisoned))
    return (qn, qr, clean, poisoned, w_uk, w_uv, jnp.asarray(history),
            jnp.asarray(kv_len))


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_latent_flash_kernel_matches_reference(rng, monkeypatch, case):
    """``flash_latent_attention`` against ``latent_expanded_attention`` on
    what blocks, tiles and the page table can get wrong: ragged lengths, a
    history that ends inside or on the edge of a page and of a key block,
    padded queries, idle rows (zeros, and nothing of them read), rows of
    different history, and NaN in every cached row nobody wrote."""
    from llms_on_kubernetes_tpu.ops import pallas_flash
    from llms_on_kubernetes_tpu.ops.attention import latent_expanded_attention

    for name, n in FLASH_BLOCKS.items():
        monkeypatch.setattr(pallas_flash, name, n)
    qn, qr, rows, poisoned, w_uk, w_uv, history, kv_len = flash_case(rng, case)
    T = qn.shape[1]
    q_pos = history[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    ref = latent_expanded_attention(qn, qr, rows, w_uk, w_uv, q_pos, kv_len,
                                    scale=0.2, block=32)
    # the traced function: the jitted one would keep another test's blocks
    out = pallas_flash.flash_latent_attention.__wrapped__(
        qn, qr, poisoned, w_uk, w_uv, history, kv_len, scale=0.2,
        interpret=True)
    assert out.shape == (len(kv_len), T, HEADS, VDIM) and out.dtype == qn.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    tol = 3e-2 if qn.dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    assert (out[np.asarray(kv_len) == 0] == 0).all()


def test_latent_prompt_dispatchers_say_what_they_took(rng, monkeypatch):
    """``dispatch_latent_prefill`` and ``dispatch_latent_chunk`` take the
    flash kernel where they can and otherwise the XLA loop, recording the
    reason: the CPU backend, a mesh (latent rows have one head), widths or
    a bucket off Mosaic's tiling, a bucket whose running state is over the
    VMEM budget; the cell's three shapes take the kernel."""
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import (
        make_mesh, set_active_mesh,
    )

    qn, qr, rows, _, w_uk, w_uv, _, kv_len = flash_case(
        rng, "a bucket with ragged lengths")
    _, pool, _, table, lengths = latent_case(rng, "a length of 1")
    cq = jnp.asarray(rng.normal(size=(3, 16, HEADS, NOPE + ROPE)), jnp.float32)
    cw = jnp.asarray(rng.normal(size=(2, HEADS, LAT, NOPE)), jnp.float32)

    def took():
        attention._chosen.clear()
        out = (attention.dispatch_latent_prefill(qn, qr, rows, w_uk, w_uv,
                                                 kv_len, scale=0.2),
               attention.dispatch_latent_chunk(
                   cq[..., :NOPE], cq[..., NOPE:], pool, table, *cw,
                   jnp.zeros_like(lengths), lengths, scale=0.2))
        return out, attention._chosen["prefill"], attention._chosen["chunk"]

    monkeypatch.delenv("LLMK_ATTENTION_IMPL", raising=False)
    ref, prefill, chunk = took()
    assert prefill == ("xla", "the bucket's own latent rows, expanded to 4 "
                       "heads a block of up to 256 keys at a time as far as "
                       "the row's last written block, bucket 64; cpu backend")
    assert chunk[0] == "xla" and chunk[1].startswith(
        "cached latent rows gathered through the page table, expanded to 4 ")
    assert chunk[1].endswith("bucket 16; cpu backend")
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    out, prefill, chunk = took()
    assert prefill == (
        "pallas-interpret", "latent flash kernel: the bucket's own latent "
        "rows, a block of 64 keys expanded to 4 of 4 heads a program in "
        "VMEM, q.k 24 wide, query tiles of 64, bucket 64")
    assert chunk == (
        "pallas-interpret", "latent flash kernel: cached latent rows "
        "gathered through the page table, a block of 128 keys expanded to "
        "4 of 4 heads a program in VMEM, q.k 24 wide, query tiles of 16, "
        "bucket 16")
    for got, want in zip(out, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    set_active_mesh(make_mesh(model=2, devices=jax.devices()[:2]))
    try:
        _, prefill, chunk = took()
    finally:
        set_active_mesh(None)
    for impl, why in (prefill, chunk):
        assert impl == "xla" and why.endswith(
            "; a mesh of model 2 x seq 1: a latent pool has one head, the "
            "kernel is not partitioned")
    # on the chip: Mosaic's tiling and the VMEM a program's state takes
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")

    def mode(T, S, lat=512, nope=128, vd=128, heads=128):
        sds = jax.ShapeDtypeStruct
        return attention._latent_flash_mode(
            sds((1, T, heads, nope), jnp.bfloat16),
            sds((1, T, heads, 64), jnp.bfloat16), S,
            sds((heads, lat, nope), jnp.bfloat16),
            sds((heads, lat, vd), jnp.bfloat16))

    for T, S in ((512, 512), (2048, 2048), (2048, 9216)):   # the cell's
        assert mode(T, S) == ("compiled", "")
    assert mode(512, 512, nope=192) == (
        None, "an un-roped key of 192 is not a multiple of 128")
    assert mode(512, 512, lat=576) == (
        None, "a latent of 576 is not a multiple of 128")
    assert mode(512, 9216 + 64) == (
        None, "a key block of 9280 rows of 64 is not a multiple of 128")
    assert mode(8, 512) == (
        None, "a query tile of bucket 8 is 8 rows, not 16s")
    over, why = mode(16384, 16384)
    assert over is None and why.startswith("bucket 16384 needs ")
    assert why.endswith(" MiB VMEM > 96 MiB budget")


# ---------------------------------------------------------------------------
# no fallback that hides the device
# ---------------------------------------------------------------------------

def test_interpret_mode_on_an_accelerator_is_an_error(rng, monkeypatch):
    """The interpreter is a CPU test path: asked for on any other backend,
    the kernel raises instead of answering as slow XLA ops."""
    from llms_on_kubernetes_tpu.ops import attention

    q, k, v = _qkv(rng, 1, 16, 4, 2, 8)
    lengths = jnp.asarray([16], jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="interpret mode.*'tpu'"):
        attention.check_interpret(True)
    with pytest.raises(RuntimeError, match="interpret mode"):
        flash_prefill_attention(q, k, v, lengths, scale=0.3, interpret=True)
    assert attention.check_interpret(False) is False
    # and the dispatchers never ask for it there
    assert attention.pallas_mode() == "compiled"
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    assert attention.pallas_mode() == "compiled"


def test_dispatchers_say_what_they_took(rng, monkeypatch, capfd):
    """Each dispatcher records its choice and prints a change once, so a
    reader outside the process can tell which implementation ran and why
    — including a geometry the VMEM budget rules out."""
    from llms_on_kubernetes_tpu.ops import attention

    q, k, v = _qkv(rng, 1, 16, 4, 2, 8)
    lengths = jnp.asarray([16], jnp.int32)
    attention._chosen.clear()
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "xla")
    for _ in range(2):
        attention.dispatch_prefill_attention(q, k, v, lengths, scale=0.3)
    assert attention._chosen["prefill"] == ("xla", "LLMK_ATTENTION_IMPL=xla")
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    attention.dispatch_prefill_attention(q, k, v, lengths, scale=0.3)
    assert attention._chosen["prefill"][0] == "pallas-interpret"
    # the kernels stage a block of 512 tokens, not a slot: a 32k-token slot
    # of 8 x 128 heads (parent: 144 MiB of staging, the XLA path) fits ...
    pt = jnp.zeros((1, 512), jnp.int32)          # 512 x 64 = 32k tokens
    mode, why = attention._paged_kernel_mode(
        jnp.zeros((1, 32, 128), jnp.bfloat16),
        jnp.zeros((8, 2, 64, 128), jnp.bfloat16), pt, 4096)
    assert (mode, why) == ("interpret", "")
    # ... and heads too many to stage even a block of: XLA, saying why
    mode, why = attention._paged_kernel_mode(
        jnp.zeros((1, 256, 128), jnp.bfloat16),
        jnp.zeros((256, 2, 64, 128), jnp.bfloat16), pt, 4096)
    assert mode is None and "VMEM" in why and "256 x 128 heads" in why
    # a pool of paired 64-wide heads names its layout on the record
    mode, why = attention._paged_kernel_mode(
        jnp.zeros((1, 32, 64), jnp.bfloat16),
        jnp.zeros((4, 2, 64, 128), jnp.bfloat16), pt, None)
    assert (mode, why) == (
        "interpret", ", 2 heads of 64 to a 128-lane page row")
    err = capfd.readouterr().err
    assert err.count("[attention] op=prefill impl=xla "
                     "why=LLMK_ATTENTION_IMPL=xla") == 1
    assert "[attention] op=prefill impl=pallas-interpret" in err
