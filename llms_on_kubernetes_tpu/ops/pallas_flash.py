"""Pallas blockwise (flash) prefill attention.

The TPU-native replacement for the flash-attention CUDA kernels the
reference pulled inside the vLLM image (SURVEY §2.3 row 1). Semantics match
``ops/attention.py::prefill_attention`` (the XLA reference implementation)
and are pinned by tests/test_pallas.py.

Kernel shape (v2):
- Inputs are transposed to head-major [B, H, T, d] at the wrapper so every
  block's minor two dims are (T-block, d) — Mosaic requires the last two
  block dims be multiples of (8, 128) or the full axis, which the v1
  token-major layout [B, T, H, d] violated (head axis block of 1 in the
  sublane slot fails to lower on real TPU; interpret mode hid it).
- grid = (B, n_q_heads, T // BLOCK_Q); each program owns one query block of
  one head and streams the head's full K/V through VMEM (prefill buckets
  are <= a few K tokens, so K/V fit VMEM comfortably: T=4096, d=128, bf16
  -> 1 MB each). Logits never touch HBM — the [T, T] score matrix the XLA
  path materializes per head stays in VMEM one [BLOCK_Q, T] tile at a time.
- GQA via the index map: query head h reads kv head h // group; the q-block
  index varies fastest so the same K/V block is reused across the whole
  row of q-blocks without re-fetching.
- Masking (causal + pad-length + optional sliding window) is additive in
  f32; softmax in f32 (same numerics policy as the reference impl).

A second kernel, ``flash_latent_attention`` (below, PR 47), serves the two
EXPANDED paths of latent attention (DeepSeek MLA: a prompt bucket over its
own rows, a chunk over its history's cached rows). It is a function of its
own because nothing above fits it: a key is 192 wide and a value 128, both
exist only as a 512-wide latent row that all heads share, and a chunk's
keys are too many to hold a head's whole K/V in VMEM. It STREAMS: grid
(B, heads / 4, key blocks) with the keys innermost; a program expands one
block of 512 latent rows to its four heads' keys and values in VMEM (never
in HBM), and every tile of 512 queries that can see the block takes its
scores, its running softmax and p.v there, the running maximum, sum and
accumulator of all the bucket's queries resident in VMEM scratch. Its
reference, and what every other backend and a mesh run, is
``ops/attention.py::latent_expanded_attention``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llms_on_kubernetes_tpu.ops.attention import (
    NEG_INF, check_interpret, softcap,
)

BLOCK_Q = 128


def flash_vmem_bytes(T: int, d: int, itemsize: int) -> int:
    """VMEM one program needs at bucket T: the head's K and V blocks
    (double-buffered by the pipeline) and their f32 casts, four
    [BLOCK_Q, T] f32 tiles (logits, mask, probabilities, exp temporaries)
    and 4 MiB for the q/o blocks and Mosaic's own scratch. Passed to
    Mosaic as ``vmem_limit_bytes`` and checked against
    ``attention.VMEM_BUDGET_BYTES`` where the kernel is chosen."""
    kv = 2 * T * d * (2 * itemsize + 4)
    return kv + 4 * min(BLOCK_Q, T) * T * 4 + (4 << 20)


def _flash_kernel(
    lengths_ref,   # SMEM [B] — true lengths (whole array, indexed by b)
    q_ref,         # VMEM [1, 1, BLOCK_Q, d]
    k_ref,         # VMEM [1, 1, T, d]
    v_ref,         # VMEM [1, 1, T, d]
    o_ref,         # VMEM [1, 1, BLOCK_Q, d]
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    block_q: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    T = k_ref.shape[2]
    length = lengths_ref[b]

    q = q_ref[0, 0].astype(jnp.float32)                # [Bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                # [T, d]
    v = v_ref[0, 0].astype(jnp.float32)                # [T, d]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # [Bq, T]
    logits = softcap(logits, attn_softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, T), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, T), 1)
    mask = (k_pos <= q_pos) & (k_pos < length)
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    logits = jnp.where(mask, logits, NEG_INF)

    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / denom
    o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap", "interpret")
)
def flash_prefill_attention(
    q: jnp.ndarray,           # [B, T, n_q, d]
    k: jnp.ndarray,           # [B, T, n_kv, d]
    v: jnp.ndarray,
    lengths: jnp.ndarray,     # [B] int32
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, n_q, d = q.shape
    n_kv = k.shape[2]
    group = n_q // n_kv
    block_q = min(BLOCK_Q, T)
    assert T % block_q == 0, f"prefill bucket {T} not a multiple of {block_q}"

    # head-major layout so block minor dims are (tokens, head_dim)
    qh = jnp.swapaxes(q, 1, 2)  # [B, n_q, T, d]
    kh = jnp.swapaxes(k, 1, 2)  # [B, n_kv, T, d]
    vh = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap, block_q=block_q,
    )
    grid = (B, n_q, T // block_q)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, T, d), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, T, d), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_q, T, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=flash_vmem_bytes(T, d, q.dtype.itemsize)),
        interpret=check_interpret(interpret),
    )(lengths.astype(jnp.int32), qh, kh, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B, T, n_q, d]


# ---------------------------------------------------------------------------
# A chunk of a prompt over its slot's cached keys and values
# ---------------------------------------------------------------------------

CHUNK_BLOCK_K = 512


def chunk_flash_blocks(T: int, S: int) -> tuple[int, int]:
    """(queries a program, keys a block) of ``flash_chunk_attention`` at a
    chunk of T queries over S gathered keys."""
    return min(BLOCK_Q, T), math.gcd(S, CHUNK_BLOCK_K)


def chunk_gather_pages(T: int, page: int, slot_pages: int,
                       sliding_window: Optional[int]) -> int:
    """Pages of a slot that a chunk of T queries gathers: all of them
    without a window; inside one, those from the page that holds the first
    query's window edge to the last query's (``sliding_window + T - 1``
    positions and the rest of the first page), in whole key blocks of the
    size the whole slot would have."""
    if sliding_window is None:
        return slot_pages
    kb = math.gcd(slot_pages * page, CHUNK_BLOCK_K)
    unit = kb * page // math.gcd(kb, page)
    need = sliding_window + T - 1 + page - 1
    return min(-(-need // unit) * unit // page, slot_pages)


def chunk_flash_vmem_bytes(T: int, S: int, d: int, itemsize: int) -> int:
    """VMEM a program of ``flash_chunk_attention`` is given: the head's
    gathered K and V (double-buffered by the pipeline), six [queries,
    key block] float32 temporaries (scores, mask, probabilities and exp's),
    the running state and 4 MiB for the q/o blocks and Mosaic's scratch."""
    bq, kb = chunk_flash_blocks(T, S)
    return (2 * 2 * S * d * itemsize + 6 * bq * kb * 4
            + bq * (d + 2 * _LANES) * 4 + (4 << 20))


def _flash_chunk_kernel(
    history_ref,   # SMEM [B] position of each row's first query (prefetch)
    kv_len_ref,    # SMEM [B] keys that are written; 0 => idle (prefetch)
    q_ref,         # VMEM [1, 1, bq, d]
    k_ref,         # VMEM [1, 1, S, d]   the slot's keys, gathered
    v_ref,         # VMEM [1, 1, S, d]
    o_ref,         # VMEM [1, 1, bq, d]
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    kb: int,
):
    """One block of ``bq`` queries of one head against the key blocks it
    can see: from the block that holds the first query's window edge (0
    without a window) to the block that holds the last query's own
    position or the last written key. ``attention.chunk_attention``'s
    numbers with the operands in their type: float32 products, maximum,
    sum and accumulator; p cast to the values' type."""
    b, qi = pl.program_id(0), pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    S = k_ref.shape[2]
    history, kv_len = history_ref[b], kv_len_ref[b]
    q0 = history + qi * bq
    q = q_ref[0, 0]
    lo = 0 if sliding_window is None else (
        jnp.maximum(q0 - sliding_window + 1, 0) // kb)
    hi = jnp.minimum((jnp.minimum(q0 + bq, kv_len) + kb - 1) // kb, S // kb)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, kb), 0)
    k_off = jax.lax.broadcasted_iota(jnp.int32, (bq, kb), 1)

    def block(j, carry):
        m_old, l_old, acc = carry
        r = pl.ds(pl.multiple_of(j * kb, kb), kb)
        s = jax.lax.dot_general(
            q, k_ref[0, 0, r, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = softcap(s, attn_softcap)
        k_pos = j * kb + k_off
        mask = (k_pos <= q_pos) & (k_pos < kv_len)
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        # a row with no key yet in sight has m = NEG_INF and exp(0) = 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        v = v_ref[0, 0, r, :]
        return (m_new, l_old * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))

    _, l, acc = jax.lax.fori_loop(
        lo, hi, block,
        (jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32), jnp.zeros((bq, d), jnp.float32)))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap",
                              "interpret"))
def flash_chunk_attention(
    q: jnp.ndarray,           # [B, T, n_q, d]
    k: jnp.ndarray,           # [n_kv, B, S, d]: a slot's pages, gathered
    v: jnp.ndarray,
    history: jnp.ndarray,     # [B] int32: query t of a row is at history + t
    kv_len: jnp.ndarray,      # [B] int32: keys written (0 => idle row)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``attention.chunk_attention`` as a kernel, over keys and values
    already gathered through the page table (9 MB a KV head pair at 9,216
    positions: nothing beside a [T, S] score tile a head in float32; a
    window layer gathers ``chunk_gather_pages`` of them and counts its
    positions from the first gathered row):
    [B, T, n_q, d] in q's type. Grid (B, n_q, T / bq), a head's K and V
    resident across its group's query blocks; key blocks outside a query
    block's causal range and window are not computed. A query with no key
    in sight (padding past the chunk's length) gives zeros."""
    B, T, n_q, d = q.shape
    n_kv, S = k.shape[0], k.shape[2]
    group = n_q // n_kv
    bq, kb = chunk_flash_blocks(T, S)
    assert T % bq == 0 and S % kb == 0, (T, S)
    out = pl.pallas_call(
        functools.partial(_flash_chunk_kernel, scale=scale,
                          sliding_window=sliding_window,
                          attn_softcap=attn_softcap, kb=kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_q, T // bq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, S, d),
                             lambda b, h, i, *_: (h // group, b, 0, 0)),
                pl.BlockSpec((1, 1, S, d),
                             lambda b, h, i, *_: (h // group, b, 0, 0))],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b, h, i, *_: (b, h, i, 0))),
        out_shape=jax.ShapeDtypeStruct((B, n_q, T, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=chunk_flash_vmem_bytes(
                T, S, d, q.dtype.itemsize)),
        name="flash_chunk_attention",
        interpret=check_interpret(interpret),
    )(history.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.swapaxes(q, 1, 2), k, v)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Latent rows (DeepSeek MLA), expanded: prompt buckets and chunks
# ---------------------------------------------------------------------------

# queries a tile, keys a block, heads a program: chosen once from the
# chip's readings at deepseek-v3's cell (PERF.md section 6, PR 47)
LATENT_BLOCK_Q = 512
LATENT_BLOCK_K = 512
LATENT_HEADS = 4
_LANES = 128
_LATENT_HEADROOM = 8 << 20


def latent_flash_blocks(T: int, S: int, H: int) -> tuple[int, int, int]:
    """(queries a tile, keys a block, heads a program) at a bucket of T
    queries over S rows and H heads: the constants, or what of them divides
    the shape."""
    return (math.gcd(T, LATENT_BLOCK_Q), math.gcd(S, LATENT_BLOCK_K),
            math.gcd(H, LATENT_HEADS))


def latent_flash_vmem_bytes(T: int, S: int, H: int, lat: int, rope: int,
                            nope: int, vd: int, itemsize: int) -> int:
    """VMEM a program of ``flash_latent_attention`` is given: its blocks of
    queries, output, rows and the heads' two matrices (double-buffered by
    the pipeline), the running maximum, sum and accumulator of every query
    of its heads in float32, a key block's expansion to those heads, six
    [tile, block] float32 temporaries (scores, mask, probabilities and
    exp's) and ``_LATENT_HEADROOM``. ``rope`` counts as whole lane tiles."""
    qb, kb, hb = latent_flash_blocks(T, S, H)
    rope = _whole_lanes(rope)
    blocks = (T * hb * (nope + rope + vd) + kb * (lat + rope)
              + hb * lat * (nope + vd)) * itemsize
    state = hb * T * (2 * _LANES + vd) * 4
    block = hb * kb * (nope + vd) * (4 + itemsize) + 6 * qb * kb * 4
    return 2 * blocks + state + block + _LATENT_HEADROOM


def _whole_lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _lanes(x, n: int):
    """A lane-replicated [rows, 128] column as [rows, n]."""
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == _LANES else pltpu.repeat(x, n // _LANES, axis=1)


def _flash_latent_kernel(
    history_ref,   # SMEM [B] position of each row's first query (prefetch)
    kv_len_ref,    # SMEM [B] rows that are written; 0 => idle (prefetch)
    qn_ref,        # VMEM [1, T, hb * nope]   heads side by side on lanes
    qr_ref,        # VMEM [1, T, hb * rp]     rotated part, zeros behind
    rows_ref,      # VMEM [1, kb, lat + rp]   [c | k_r | 0] of a key block
    wuk_ref,       # VMEM [hb, lat, nope]
    wuv_ref,       # VMEM [hb, lat, vd]
    o_ref,         # VMEM [1, T, hb * vd]
    m_ref,         # VMEM [hb, T, 128] f32 running maximum, lane-replicated
    l_ref,         # VMEM [hb, T, 128] f32 running sum
    acc_ref,       # VMEM [hb, T, vd]  f32
    *,
    scale: float,
    qb: int,
    kb: int,
):
    """One batch row's ``hb`` heads against one block of ``kb`` keys (grid
    (B, H / hb, S / kb), keys innermost): the block's latents expanded to
    each head's keys and values ONCE, in VMEM, then every tile of ``qb``
    queries that can see the block takes its scores, running softmax and
    p.v there. ``latent_expanded_attention``'s numbers: operands in their
    type, float32 products, maximum, sum and accumulator; p cast to the
    values' type. Tiles wholly under the diagonal and inside ``kv_len``
    skip the mask; key blocks past ``kv_len`` are not run (nor fetched: the
    rows' index map stops at the last written block)."""
    b, ki = pl.program_id(0), pl.program_id(2)
    last = pl.num_programs(2) - 1
    hb, lat, nope = wuk_ref.shape
    vd = wuv_ref.shape[2]
    rp = rows_ref.shape[2] - lat
    T = qn_ref.shape[1]
    nq = T // qb
    history, kv_len = history_ref[b], kv_len_ref[b]
    k0 = ki * kb
    nt = (((1,), (1,)), ((), ()))          # a . b^T

    def tiles(body):
        def run(j, carry):
            body(pl.ds(pl.multiple_of(j * qb, qb), qb), j)
            return carry
        return run

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(k0 < kv_len)
    def _():
        rows = rows_ref[0]
        # a row nobody wrote may hold anything: p = 0 times it must be 0
        written = k0 + jax.lax.broadcasted_iota(jnp.int32, (kb, 1), 0) < kv_len
        rows = jnp.where(written, rows, jnp.zeros_like(rows))
        c, kr = rows[:, :lat], rows[:, lat:]
        # the first tile whose last query reaches this block, and the first
        # whose every query sees all of it (none if the block is not whole)
        first = jnp.maximum(k0 - history, 0) // qb
        clear = jnp.where(
            k0 + kb <= kv_len,
            jnp.clip((k0 + kb - 1 - history + qb - 1) // qb, first, nq), nq)

        kns = [jnp.dot(c, wuk_ref[i], preferred_element_type=jnp.float32
                       ).astype(c.dtype) for i in range(hb)]
        vs = [jnp.dot(c, wuv_ref[i], preferred_element_type=jnp.float32
                      ).astype(c.dtype) for i in range(hb)]

        def tile(r, j, i, masked):
            s = (jax.lax.dot_general(
                    qn_ref[0, r, i * nope:(i + 1) * nope], kns[i], nt,
                    preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(
                    qr_ref[0, r, i * rp:(i + 1) * rp], kr, nt,
                    preferred_element_type=jnp.float32)) * scale
            if masked:
                q_pos = history + j * qb + jax.lax.broadcasted_iota(
                    jnp.int32, (qb, kb), 0)
                k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
                mask = (k_pos <= q_pos) & (k_pos < kv_len)
                s = jnp.where(mask, s, NEG_INF)
            m_old = m_ref[i, r, :]
            m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, kb))
            if masked:
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_old - m_new)
            m_ref[i, r, :] = m_new
            l_ref[i, r, :] = (l_ref[i, r, :] * alpha
                              + p.sum(axis=-1, keepdims=True))
            acc_ref[i, r, :] = (
                acc_ref[i, r, :] * _lanes(alpha, vd) + jnp.dot(
                    p.astype(vs[i].dtype), vs[i],
                    preferred_element_type=jnp.float32))

        # the heads' tiles side by side in one loop body: independent
        # chains, so one head's softmax runs under another's products
        # (7 % faster on a v5e than a loop a head)
        def all_heads(masked):
            def body(r, j):
                for i in range(hb):
                    tile(r, j, i, masked)
            return tiles(body)

        jax.lax.fori_loop(first, clear, all_heads(True), None)
        jax.lax.fori_loop(clear, nq, all_heads(False), None)

    @pl.when(ki == last)
    def _():
        for i in range(hb):
            def write(r, j, i=i):
                l = jnp.maximum(l_ref[i, r, :], 1e-30)
                o_ref[0, r, i * vd:(i + 1) * vd] = (
                    acc_ref[i, r, :] / _lanes(l, vd)).astype(o_ref.dtype)

            jax.lax.fori_loop(0, nq, tiles(write), None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_latent_attention(
    qn: jnp.ndarray,        # [B, T, H, nope]
    qr: jnp.ndarray,        # [B, T, H, rope]  (rotated)
    rows: jnp.ndarray,      # [B, S, >= lat + rope]: [c | k_r | zeros]
    w_uk: jnp.ndarray,      # [H, lat, nope]
    w_uv: jnp.ndarray,      # [H, lat, vd]
    history: jnp.ndarray,   # [B] int32: query t of a row is at history + t
    kv_len: jnp.ndarray,    # [B] int32: rows written (0 => idle row)
    *,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """``attention.latent_expanded_attention`` as a kernel, for queries at
    consecutive positions: [B, T, H, vd] in qn's type. The rows are read as
    they lie, a block of keys at a time; no score tile, no expanded key or
    value and no running state is written to HBM."""
    B, T, H, nope = qn.shape
    S, lat, vd, rope = rows.shape[1], w_uk.shape[1], w_uv.shape[2], qr.shape[3]
    qb, kb, hb = latent_flash_blocks(T, S, H)
    # the rotated part in whole lane tiles: a cached row already is (zeros
    # behind its 64 roped values); a bucket's own rows and q_r are padded
    rp = _whole_lanes(rope)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, max(
        0, lat + rp - rows.shape[2]))))[..., :lat + rp]
    qr = jnp.pad(qr, ((0, 0),) * 3 + ((0, rp - rope),))

    def last_block(b, kv_len):
        return jnp.maximum((kv_len[b] + kb - 1) // kb - 1, 0)

    def heads(width):
        return pl.BlockSpec((1, T, hb * width), lambda b, h, k, *_: (b, 0, h))

    def matrix(width):
        return pl.BlockSpec((hb, lat, width), lambda b, h, k, *_: (h, 0, 0))

    out = pl.pallas_call(
        functools.partial(_flash_latent_kernel, scale=scale, qb=qb, kb=kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H // hb, S // kb),
            in_specs=[
                heads(nope), heads(rp),
                pl.BlockSpec((1, kb, lat + rp), lambda b, h, k, hist, n: (
                    b, jnp.minimum(k, last_block(b, n)), 0)),
                matrix(nope), matrix(vd)],
            out_specs=heads(vd),
            scratch_shapes=[pltpu.VMEM((hb, T, _LANES), jnp.float32),
                            pltpu.VMEM((hb, T, _LANES), jnp.float32),
                            pltpu.VMEM((hb, T, vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, T, H * vd), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=latent_flash_vmem_bytes(
                T, S, H, lat, rope, nope, vd, qn.dtype.itemsize)),
        name="flash_latent_attention",
        interpret=check_interpret(interpret),
    )(history.astype(jnp.int32), kv_len.astype(jnp.int32),
      qn.reshape(B, T, H * nope), qr.reshape(B, T, H * rp), rows, w_uk, w_uv)
    return out.reshape(B, T, H, vd)
