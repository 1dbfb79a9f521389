"""Pallas paged decode attention.

The TPU-native replacement for vLLM's PagedAttention CUDA kernel (SURVEY
§2.3 row 1; §7 hard-part 1). Semantics match
``ops/attention.py::paged_attention`` (the XLA reference) and are pinned by
tests/test_pallas.py.

Why a kernel at all: the XLA path materializes every slot's logical KV
([B, S_max, n_kv, d]) in HBM via gather before the matmul — decode reads
the KV pool twice (gather write + matmul read). This kernel DMAs each
slot's pages HBM→VMEM once and attends in-place:

- ``PrefetchScalarGridSpec`` prefetches the page table and lengths into
  SMEM so DMA source addresses are computable before the body runs.
- The page pool is **head-major** [n_kv, P, page, d] (engine/cache.py), so
  each (head, page) slice is one contiguous aligned [page, d] block — a
  single DMA with no sublane-tile slicing (a head-minor pool layout is
  rejected by Mosaic: slicing n_kv to 1 in the tiled sublane slot).
- grid = (B, n_kv); each program owns one slot x one kv head: it issues
  one async DMA per page (unused table entries point at the reserved
  trash page 0 — uniform DMA pattern, garbage masked out), waits once,
  then computes the whole group's attention with two MXU matmuls
  ([group, d] x [d, S] and [group, S] x [S, d]) in f32.
- A slot's pages are staged in VMEM scratch ([n_kv, S_max, d] each for K
  and V) and attended in blocks of <= 512 tokens with an online softmax
  (``_attend_staged``), so the f32 temporaries stay a few MiB however
  long the slot is. The scratch itself is what bounds S_max:
  ``paged_vmem_bytes`` is its size plus headroom, passed to Mosaic as the
  kernel's ``vmem_limit_bytes`` (the default scoped limit is 16 MiB, which
  a 4096-token bf16 slot of 8 heads x 128 already fills) and checked
  against ``attention.VMEM_BUDGET_BYTES`` where the kernel is chosen.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llms_on_kubernetes_tpu.ops.attention import (
    NEG_INF, check_interpret, softcap,
)

_BLOCK_TOKENS = 512   # keys attended per online-softmax block
# block temporaries (f32 K/V casts, logits, probabilities), the
# double-buffered q/o blocks and the write kernels' small RMW scratch
_VMEM_HEADROOM = 16 << 20


def paged_vmem_bytes(n_kv: int, S: int, d: int, itemsize: int,
                     quantized: bool = False) -> int:
    """VMEM the paged decode kernels need for one slot of S tokens: the K
    and V staging scratch (plus f32 per-token scales when int8) and
    ``_VMEM_HEADROOM``."""
    per_tok = n_kv * (d * itemsize + (4 if quantized else 0))
    return 2 * S * per_tok + _VMEM_HEADROOM


def _compiler_params(n_kv, S, d, dtype, quantized=False):
    return pltpu.CompilerParams(vmem_limit_bytes=paged_vmem_bytes(
        n_kv, S, d, jnp.dtype(dtype).itemsize, quantized))


def _block_tokens(page_size: int, pages_per_seq: int) -> int:
    """Largest whole-page block <= _BLOCK_TOKENS that tiles the slot."""
    g = max(1, min(pages_per_seq, _BLOCK_TOKENS // page_size))
    while pages_per_seq % g:
        g -= 1
    return g * page_size


def _attend_staged(q, k_buf, v_buf, ks_buf, vs_buf, n_valid, q_pos, *,
                   blk: int, scale: float, sliding_window: Optional[int],
                   attn_softcap: Optional[float]):
    """Online-softmax attention of q [n_kv, group, d] (f32) over the keys
    [0, n_valid) staged in k_buf/v_buf [n_kv, S, d], one ``blk``-token
    block at a time. ``ks_buf``/``vs_buf`` [n_kv, S] are the per-token
    int8 scales (None for a float pool): the per-key scale is applied to
    the LOGITS column and the per-value scale to the PROBABILITY column
    (q.(k*s) == (q.k)*s), both lane-dim broadcasts. Returns the partials
    (m [n_kv, group, 1], l [n_kv, group, 1], acc [n_kv, group, d]); the
    caller divides (and, in the write kernels, first merges the current
    token). Blocks wholly outside [q_pos - window, n_valid) are skipped.

    Stale scratch (pages never DMA'd, lanes beyond n_valid) may hold
    anything, NaN included: logits there are REPLACED by the substitutive
    mask, probabilities are zeroed, and V rows / value scales are zeroed
    before the p @ v matmul (0 * NaN = NaN otherwise)."""
    n_kv, group, d = q.shape
    lo = 0
    if sliding_window is not None:
        lo = jnp.maximum(q_pos - sliding_window + 1, 0) // blk
    hi = (n_valid + blk - 1) // blk

    def body(j, carry):
        m, l, acc = carry
        start = pl.multiple_of(j * blk, blk)
        k = k_buf[:, pl.ds(start, blk), :].astype(jnp.float32)
        v = v_buf[:, pl.ds(start, blk), :].astype(jnp.float32)
        row = start + jax.lax.broadcasted_iota(jnp.int32, (n_kv, blk, 1), 1)
        v = jnp.where(row < n_valid, v, 0.0)
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [n_kv, group, blk]
        k_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, group, blk), 2)
        valid = k_pos < n_valid
        if ks_buf is not None:
            logits = logits * ks_buf[:, pl.ds(start, blk)][:, None, :]
        logits = softcap(logits, attn_softcap)
        mask = valid
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if vs_buf is not None:
            sc_v = vs_buf[:, pl.ds(start, blk)][:, None, :]
            p = p * jnp.where(valid[:, :1], sc_v, 0.0)
        acc = alpha * acc + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                              # [n_kv, group, d]
        return m_new, l, acc

    init = (jnp.full((n_kv, group, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_kv, group, 1), jnp.float32),
            jnp.zeros((n_kv, group, d), jnp.float32))
    return jax.lax.fori_loop(lo, hi, body, init)


def _merge_current(q, part, k_cur, v_cur, *, scale, attn_softcap):
    """Fold the current token (k_cur/v_cur [n_kv, d] f32, held in
    registers — never read back from HBM; always inside any sliding
    window, it IS the query position) into staged partials and normalize.
    Returns o [n_kv, group, d]."""
    m, l, acc = part
    l_cur = jnp.sum(q * k_cur[:, None, :], axis=-1, keepdims=True) * scale
    l_cur = softcap(l_cur, attn_softcap)               # [n_kv, group, 1]
    m_new = jnp.maximum(m, l_cur)
    alpha = jnp.exp(m - m_new)
    w_cur = jnp.exp(l_cur - m_new)
    num = alpha * acc + w_cur * v_cur[:, None, :]
    return num / (alpha * l + w_cur)


def _paged_kernel(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, n_kv, group, d]
    k_hbm,            # ANY  [n_kv, P, page, d] (head-major pool)
    v_hbm,            # ANY  [n_kv, P, page, d]
    o_ref,            # VMEM [1, n_kv, group, d]
    k_buf,            # VMEM [n_kv, S, d] scratch
    v_buf,            # VMEM [n_kv, S, d] scratch
    sems,             # DMA semaphores [2, pages_per_seq]
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
):
    """Grid is (B,): ONE program per slot computes ALL kv heads.

    A (B, n_kv) grid ran B*n_kv tiny sequential programs (a v5e chip has a
    single TensorCore — grid steps serialize), and per-program overhead
    (DMA issue/wait, matmul setup) dominated: measured ~2 ms per LAYER at
    B=64, ~13 ms of a 33 ms decode step. Batching the head dimension into
    one program amortizes that overhead 8x: each page DMA moves the
    [n_kv, page, d] strided block for every head at once, and the two MXU
    contractions run batched over heads."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    # LENGTH-BOUNDED DMA: only pages actually covering this slot's tokens
    # are fetched. A slot 100 tokens into a 2048-token window must not pay
    # 20x its KV bandwidth (the full-table DMA was the decode step's
    # biggest HBM consumer at long windows). Skipped regions of the
    # scratch stay stale; every key beyond `length` is masked to NEG_INF
    # before the softmax, so stale lanes never contribute.
    n_pages = (length + page_size - 1) // page_size

    # one strided [n_kv, page, d] DMA per page per K/V (covers all heads)
    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _start(i=i):
            page_id = page_table_ref[b, i]
            pltpu.make_async_copy(
                k_hbm.at[:, page_id],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i],
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[:, page_id],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i],
            ).start()
    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _wait(i=i):
            pltpu.make_async_copy(
                k_hbm.at[:, page_table_ref[b, i]],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i],
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[:, page_table_ref[b, i]],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i],
            ).wait()

    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    _, l, acc = _attend_staged(
        q, k_buf, v_buf, None, None, length, length - 1,
        blk=_block_tokens(page_size, pages_per_seq), scale=scale,
        sliding_window=sliding_window, attn_softcap=attn_softcap)
    # idle slot (length 0): no block ran, l == 0 -> a finite zero row
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_kernel_int8(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, n_kv, group, d]
    k_hbm,            # ANY  [n_kv, P, page, d] int8 (head-major pool)
    ks_hbm,           # ANY  [n_kv, P, page] f32 per-token scales
    v_hbm,            # ANY  [n_kv, P, page, d] int8
    vs_hbm,           # ANY  [n_kv, P, page] f32
    o_ref,            # VMEM [1, n_kv, group, d]
    k_buf,            # VMEM [n_kv, S, d] int8 scratch
    v_buf,            # VMEM [n_kv, S, d] int8 scratch
    ks_buf,           # VMEM [n_kv, S] f32 scratch
    vs_buf,           # VMEM [n_kv, S] f32 scratch
    sems,             # DMA semaphores [4, pages_per_seq]
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
):
    """int8 decode attention, head-batched like _paged_kernel (one program
    per slot — see that kernel's grid rationale): the page DMA moves
    1-byte KV plus a per-token scale vector, and the dequantize folds
    into LANE-dim multiplies — decode attention HBM traffic is halved vs
    bf16.

    Layout trick: a per-KEY-token scale can be applied to the LOGITS
    column instead of to K rows (q·(k·s) == (q·k)·s), and a per-VALUE
    scale to the probability column instead of V rows. Both are [*, S]
    lane-dim broadcasts, so no sublane-broadcast/transpose of the [S]
    scale vector is ever needed — and the scale DMAs land at lane offsets
    i*page_size, which Mosaic accepts only when page_size is a multiple
    of the 128-lane tile (enforced by the dispatcher)."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = (length + page_size - 1) // page_size

    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _start(i=i):
            page_id = page_table_ref[b, i]
            pltpu.make_async_copy(
                k_hbm.at[:, page_id],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i],
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[:, page_id],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i],
            ).start()
            pltpu.make_async_copy(
                ks_hbm.at[:, page_id],
                ks_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[2, i],
            ).start()
            pltpu.make_async_copy(
                vs_hbm.at[:, page_id],
                vs_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[3, i],
            ).start()
    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _wait(i=i):
            pid = page_table_ref[b, i]
            pltpu.make_async_copy(
                k_hbm.at[:, pid],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i]).wait()
            pltpu.make_async_copy(
                v_hbm.at[:, pid],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i]).wait()
            pltpu.make_async_copy(
                ks_hbm.at[:, pid],
                ks_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[2, i]).wait()
            pltpu.make_async_copy(
                vs_hbm.at[:, pid],
                vs_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[3, i]).wait()

    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    _, l, acc = _attend_staged(
        q, k_buf, v_buf, ks_buf, vs_buf, length, length - 1,
        blk=_block_tokens(page_size, pages_per_seq), scale=scale,
        sliding_window=sliding_window, attn_softcap=attn_softcap)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap", "interpret")
)
def pallas_paged_attention_int8(
    q: jnp.ndarray,            # [B, n_q, d]
    k_data: jnp.ndarray,       # [n_kv, P, page, d] int8
    k_scale: jnp.ndarray,      # [n_kv, P, page] f32
    v_data: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    lengths: jnp.ndarray,      # [B] int32 (incl. current token)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, n_q, d = q.shape
    n_kv, P, page_size, _ = k_data.shape
    pages_per_seq = page_table.shape[1]
    S = pages_per_seq * page_size
    group = n_q // n_kv

    kernel = functools.partial(
        _paged_kernel_int8,
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap,
        page_size=page_size, pages_per_seq=pages_per_seq,
    )
    qg = q.reshape(B, n_kv, group, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, S, d), k_data.dtype),
            pltpu.VMEM((n_kv, S, d), v_data.dtype),
            pltpu.VMEM((n_kv, S), jnp.float32),
            pltpu.VMEM((n_kv, S), jnp.float32),
            pltpu.SemaphoreType.DMA((4, pages_per_seq)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
        compiler_params=_compiler_params(n_kv, S, d, k_data.dtype, True),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_data, k_scale, v_data, v_scale)
    return out.reshape(B, n_q, d)


def _paged_kernel_write(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, n_kv, group, d]
    k_hbm,            # ANY  [n_kv, P, page, d] (aliased with k_out)
    v_hbm,            # ANY  [n_kv, P, page, d] (aliased with v_out)
    k_new_ref,        # VMEM [1, n_kv, d] — current token's K
    v_new_ref,        # VMEM [1, n_kv, d]
    o_ref,            # VMEM [1, n_kv, group, d]
    k_out,            # ANY  (alias of k_hbm)
    v_out,            # ANY  (alias of v_hbm)
    k_buf,            # VMEM [n_kv, S, d] scratch
    v_buf,            # VMEM [n_kv, S, d] scratch
    kblk,             # VMEM [n_kv, 8, d] write-block scratch
    vblk,             # VMEM [n_kv, 8, d]
    sems,             # DMA semaphores [2, pages_per_seq]
    wsem,             # DMA semaphores [2] (write-block RMW)
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
):
    """Decode attention WITH the current token's KV write folded in.

    The per-slot DUS write loop costs ~3 ms/step at B=64 (4096 tiny ops
    of pure dispatch overhead — round-4 profile), and the opt-in HLO
    scatter reserves a ~0.37-pool HBM temp that breaks the 16 GB bench
    config at compile time. This kernel removes the separate write
    entirely: each slot's program (which is already running for the
    attention) DMAs its new K/V row [n_kv, d] into the pool page
    in place (input_output aliasing) and folds the current token into
    the softmax IN REGISTERS via the online-softmax merge — so the row
    never needs to be read back from HBM, and cached-page DMAs cover
    only the length-1 previously written tokens.

    Idle slots (length == 0) skip the write and produce a harmless
    pure-current-token output (discarded by the engine)."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    cached = length - 1                       # tokens already in the pool
    n_pages = (cached + page_size - 1) // page_size

    # The new row's write is an 8-token-block READ-MODIFY-WRITE (Mosaic
    # requires page-dim slices be 8-sublane-tile aligned): fetch the
    # aligned block the new token lands in, splice the row in with a
    # vector select, DMA the block back. The block's other rows are the
    # same slot's own earlier tokens (pages are slot-private at the write
    # position — adopted prefix pages always end before it) or unwritten
    # garbage, both of which round-trip unchanged. The block's fetch is
    # started WITH the page DMAs and waited for with them, so it costs no
    # DMA round trip of its own.
    pos = jnp.maximum(cached, 0)
    w_pid = page_table_ref[b, pos // page_size]
    off8 = pl.multiple_of((pos % page_size) // 8 * 8, 8)

    @pl.when(length > 0)
    def _write_fetch():
        pltpu.make_async_copy(
            k_hbm.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).start()
        pltpu.make_async_copy(
            v_hbm.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).start()

    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _start(i=i):
            page_id = page_table_ref[b, i]
            pltpu.make_async_copy(
                k_hbm.at[:, page_id],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i],
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[:, page_id],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i],
            ).start()
    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _wait(i=i):
            pltpu.make_async_copy(
                k_hbm.at[:, page_table_ref[b, i]],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i],
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[:, page_table_ref[b, i]],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i],
            ).wait()

    # Write-back AFTER the cached-page reads are done (the target page is
    # often in this program's own read set — its stale lanes beyond
    # `cached` are masked, so read-then-write order is safe); it overlaps
    # the attention below and is waited for at the end.
    @pl.when(length > 0)
    def _write_back():
        pltpu.make_async_copy(
            k_hbm.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).wait()
        pltpu.make_async_copy(
            v_hbm.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).wait()
        row = jax.lax.broadcasted_iota(
            jnp.int32, (1, 8, 1), 1) == (pos % page_size) - off8
        kblk[...] = jnp.where(row, k_new_ref[0][:, None, :], kblk[...])
        vblk[...] = jnp.where(row, v_new_ref[0][:, None, :], vblk[...])
        pltpu.make_async_copy(
            kblk, k_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).start()
        pltpu.make_async_copy(
            vblk, v_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).start()

    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    part = _attend_staged(
        q, k_buf, v_buf, None, None, cached, cached,
        blk=_block_tokens(page_size, pages_per_seq), scale=scale,
        sliding_window=sliding_window, attn_softcap=attn_softcap)
    o_ref[0] = _merge_current(
        q, part, k_new_ref[0].astype(jnp.float32),
        v_new_ref[0].astype(jnp.float32),
        scale=scale, attn_softcap=attn_softcap).astype(o_ref.dtype)

    @pl.when(length > 0)
    def _finish():
        pltpu.make_async_copy(
            kblk, k_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).wait()
        pltpu.make_async_copy(
            vblk, v_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).wait()


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap", "interpret")
)
def pallas_paged_attention_write(
    q: jnp.ndarray,            # [B, n_q, d]
    k_pages: jnp.ndarray,      # [n_kv, P, page, d] (head-major pool; donated)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    lengths: jnp.ndarray,      # [B] int32 (incl. current token; 0 => idle)
    k_new: jnp.ndarray,        # [B, n_kv, d] current token's K (post-rope)
    v_new: jnp.ndarray,        # [B, n_kv, d]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused decode attention + in-place KV append (see _paged_kernel_write).
    Returns (attn [B, n_q, d], k_pages, v_pages)."""
    B, n_q, d = q.shape
    n_kv, P, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    S = pages_per_seq * page_size
    group = n_q // n_kv

    kernel = functools.partial(
        _paged_kernel_write,
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap,
        page_size=page_size, pages_per_seq=pages_per_seq,
    )
    qg = q.reshape(B, n_kv, group, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, n_kv, d), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, d), lambda b, *_: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, S, d), k_pages.dtype),
            pltpu.VMEM((n_kv, S, d), v_pages.dtype),
            pltpu.VMEM((n_kv, 8, d), k_pages.dtype),
            pltpu.VMEM((n_kv, 8, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, pages_per_seq)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out, k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # inputs count scalar-prefetch args first: pt=0, lengths=1, q=2,
        # k_pages=3, v_pages=4, k_new=5, v_new=6; outputs: attn=0, k=1, v=2
        input_output_aliases={3: 1, 4: 2},
        compiler_params=_compiler_params(n_kv, S, d, k_pages.dtype),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages,
      k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype))
    return out.reshape(B, n_q, d), k_pages, v_pages


def _paged_kernel_write_window(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    base_ref,         # SMEM [B] first token's 0-based pool position
    width_ref,        # SMEM [B] tokens to write (0 => idle row)
    k_hbm,            # ANY  [n_kv, P, page, d] (aliased with k_out)
    v_hbm,            # ANY  [n_kv, P, page, d]
    k_new_ref,        # VMEM [1, W, n_kv, d] — window of new K rows
    v_new_ref,        # VMEM [1, W, n_kv, d]
    k_out,            # ANY  (alias of k_hbm)
    v_out,            # ANY  (alias of v_hbm)
    kblk,             # VMEM [n_kv, 8, d] write-block scratch
    vblk,             # VMEM [n_kv, 8, d]
    wsem,             # DMA semaphores [2]
    *,
    window: int,
    page_size: int,
):
    """In-place append of a K-token WINDOW per slot (multi-step decode).

    Same 8-sublane-tile READ-MODIFY-WRITE as _paged_kernel_write, applied
    token-by-token through the window: fetch the aligned 8-row block the
    token lands in, splice the row, DMA the block back, and WAIT before
    the next token — consecutive window tokens often share a block, so
    the RMW chain must be ordered. Tokens past the row's ``width`` (early
    exit: the row stopped mid-window) are skipped, leaving the pool
    byte-identical to a per-step write sequence that stopped there."""
    b = pl.program_id(0)
    base = base_ref[b]
    width = width_ref[b]

    # every fetch AND write-back goes through the OUTPUT alias: token t+1
    # often lands in the same 8-row block as token t, and fetching from
    # the input ref would re-read pre-window bytes — losing token t's
    # splice (a lost update the interpret mode catches deterministically)
    for t in range(window):
        @pl.when(t < width)
        def _rmw(t=t):
            pos = base + t
            w_pid = page_table_ref[b, pos // page_size]
            off8 = pl.multiple_of((pos % page_size) // 8 * 8, 8)
            pltpu.make_async_copy(
                k_out.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).start()
            pltpu.make_async_copy(
                v_out.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).start()
            pltpu.make_async_copy(
                k_out.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).wait()
            pltpu.make_async_copy(
                v_out.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).wait()
            row = jax.lax.broadcasted_iota(
                jnp.int32, (1, 8, 1), 1) == (pos % page_size) - off8
            k_row = k_new_ref[0, t]                      # [n_kv, d]
            v_row = v_new_ref[0, t]
            kblk[...] = jnp.where(row, k_row[:, None, :], kblk[...])
            vblk[...] = jnp.where(row, v_row[:, None, :], vblk[...])
            pltpu.make_async_copy(
                kblk, k_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).start()
            pltpu.make_async_copy(
                vblk, v_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).start()
            pltpu.make_async_copy(
                kblk, k_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).wait()
            pltpu.make_async_copy(
                vblk, v_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).wait()


def pallas_paged_write_window(
    k_pages: jnp.ndarray,      # [n_kv, P, page, d] (head-major pool; donated)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    base: jnp.ndarray,         # [B] int32 0-based position of token 0
    widths: jnp.ndarray,       # [B] int32 tokens to write (<= window)
    k_new: jnp.ndarray,        # [B, W, n_kv, d] window of new K rows
    v_new: jnp.ndarray,        # [B, W, n_kv, d]
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused in-place append of up to W tokens per slot in ONE kernel
    launch (see _paged_kernel_write_window). The multi-step decode
    window's verify-k speculative path lands on this entry point: a
    draft-and-verify step commits 0..W accepted tokens per slot, and
    ``widths`` is exactly the per-slot acceptance count. Returns
    (k_pages, v_pages) updated in place via input/output aliasing."""
    n_kv, P, page_size, d = k_pages.shape
    B, W = k_new.shape[:2]

    kernel = functools.partial(
        _paged_kernel_write_window,
        window=W, page_size=page_size,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, W, n_kv, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, W, n_kv, d), lambda b, *_: (b, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, 8, d), k_pages.dtype),
            pltpu.VMEM((n_kv, 8, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    k_pages, v_pages = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # inputs count scalar-prefetch args first: pt=0, base=1, widths=2,
        # k_pages=3, v_pages=4, k_new=5, v_new=6; outputs: k=0, v=1
        input_output_aliases={3: 0, 4: 1},
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), base.astype(jnp.int32),
      widths.astype(jnp.int32), k_pages, v_pages,
      k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def _quantize_row(xf):
    """In-register per-token symmetric int8 — MUST match cache.quantize_kv
    bit-for-bit (same max/clip/round chain), so a page written by this
    kernel is byte-identical to one written by the host-side write path.
    xf [n_kv, d] f32 -> (int8 [n_kv, d], f32 scale [n_kv])."""
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    data = jnp.clip(jnp.round(xf / s[:, None]), -127, 127).astype(jnp.int8)
    return data, s


def _paged_kernel_write_int8(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, n_kv, group, d]
    kd_hbm,           # ANY  [n_kv, P, page, d] int8 (aliased with kd_out)
    ks_hbm,           # ANY  [n_kv, P, page] f32     (aliased with ks_out)
    vd_hbm,           # ANY  [n_kv, P, page, d] int8
    vs_hbm,           # ANY  [n_kv, P, page] f32
    k_new_ref,        # VMEM [1, n_kv, d] — current token's K (full width)
    v_new_ref,        # VMEM [1, n_kv, d]
    o_ref,            # VMEM [1, n_kv, group, d]
    kd_out,           # ANY  (alias of kd_hbm)
    ks_out,           # ANY  (alias of ks_hbm)
    vd_out,           # ANY  (alias of vd_hbm)
    vs_out,           # ANY  (alias of vs_hbm)
    k_buf,            # VMEM [n_kv, S, d] int8 scratch
    v_buf,            # VMEM [n_kv, S, d] int8 scratch
    ks_buf,           # VMEM [n_kv, S] f32 scratch
    vs_buf,           # VMEM [n_kv, S] f32 scratch
    kblk,             # VMEM [n_kv, 8, d] int8 write-block scratch
    vblk,             # VMEM [n_kv, 8, d] int8
    ksrow,            # VMEM [n_kv, page] f32 scale-row scratch
    vsrow,            # VMEM [n_kv, page] f32
    sems,             # DMA semaphores [4, pages_per_seq]
    wsem,             # DMA semaphores [4] (write-block RMW)
    *,
    scale: float,
    sliding_window: Optional[int],
    attn_softcap: Optional[float],
    page_size: int,
    pages_per_seq: int,
):
    """int8 decode attention WITH the current token QUANTIZED AND WRITTEN
    in the same program — the storage-side twin of _paged_kernel_write.

    The new K/V row arrives full-width, is quantized in registers
    (bit-identical to cache.quantize_kv, so fused and host write paths
    produce the same pool bytes), and lands in the pool via the same
    8-sublane-tile data RMW as the fp kernel plus a FULL-PAGE scale-row
    RMW ([n_kv, page] is a whole aligned lane row — an 8-lane scale
    slice would violate Mosaic's 128-lane tiling, a full page row never
    does). The current token folds into the online softmax using its
    DEQUANTIZED value (data * scale), so the output matches a
    write-then-attend over the quantized pool, not the fp input."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    cached = length - 1                       # tokens already in the pool
    n_pages = (cached + page_size - 1) // page_size

    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _start(i=i):
            pid = page_table_ref[b, i]
            pltpu.make_async_copy(
                kd_hbm.at[:, pid],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i]).start()
            pltpu.make_async_copy(
                vd_hbm.at[:, pid],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i]).start()
            pltpu.make_async_copy(
                ks_hbm.at[:, pid],
                ks_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[2, i]).start()
            pltpu.make_async_copy(
                vs_hbm.at[:, pid],
                vs_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[3, i]).start()
    for i in range(pages_per_seq):
        @pl.when(i < n_pages)
        def _wait(i=i):
            pid = page_table_ref[b, i]
            pltpu.make_async_copy(
                kd_hbm.at[:, pid],
                k_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[0, i]).wait()
            pltpu.make_async_copy(
                vd_hbm.at[:, pid],
                v_buf.at[:, pl.ds(i * page_size, page_size), :],
                sems.at[1, i]).wait()
            pltpu.make_async_copy(
                ks_hbm.at[:, pid],
                ks_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[2, i]).wait()
            pltpu.make_async_copy(
                vs_hbm.at[:, pid],
                vs_buf.at[:, pl.ds(i * page_size, page_size)],
                sems.at[3, i]).wait()

    # quantize the incoming row once; both the write-back and the in-
    # register softmax contribution use the SAME quantized values
    kq, ks_new = _quantize_row(k_new_ref[0].astype(jnp.float32))
    vq, vs_new = _quantize_row(v_new_ref[0].astype(jnp.float32))

    pos = jnp.maximum(cached, 0)
    w_pid = page_table_ref[b, pos // page_size]
    off8 = pl.multiple_of((pos % page_size) // 8 * 8, 8)

    @pl.when(length > 0)
    def _write_fetch():
        pltpu.make_async_copy(
            kd_hbm.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).start()
        pltpu.make_async_copy(
            vd_hbm.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).start()
        pltpu.make_async_copy(
            ks_hbm.at[:, w_pid], ksrow, wsem.at[2]).start()
        pltpu.make_async_copy(
            vs_hbm.at[:, w_pid], vsrow, wsem.at[3]).start()

    @pl.when(length > 0)
    def _write_back():
        pltpu.make_async_copy(
            kd_hbm.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).wait()
        pltpu.make_async_copy(
            vd_hbm.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).wait()
        pltpu.make_async_copy(
            ks_hbm.at[:, w_pid], ksrow, wsem.at[2]).wait()
        pltpu.make_async_copy(
            vs_hbm.at[:, w_pid], vsrow, wsem.at[3]).wait()
        row = jax.lax.broadcasted_iota(
            jnp.int32, (1, 8, 1), 1) == (pos % page_size) - off8
        kblk[...] = jnp.where(row, kq[:, None, :], kblk[...])
        vblk[...] = jnp.where(row, vq[:, None, :], vblk[...])
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1) == pos % page_size
        ksrow[...] = jnp.where(lane, ks_new[:, None], ksrow[...])
        vsrow[...] = jnp.where(lane, vs_new[:, None], vsrow[...])
        pltpu.make_async_copy(
            kblk, kd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).start()
        pltpu.make_async_copy(
            vblk, vd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).start()
        pltpu.make_async_copy(
            ksrow, ks_out.at[:, w_pid], wsem.at[2]).start()
        pltpu.make_async_copy(
            vsrow, vs_out.at[:, w_pid], wsem.at[3]).start()

    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    part = _attend_staged(
        q, k_buf, v_buf, ks_buf, vs_buf, cached, cached,
        blk=_block_tokens(page_size, pages_per_seq), scale=scale,
        sliding_window=sliding_window, attn_softcap=attn_softcap)
    # current token, dequantized in registers: the output matches a
    # write-then-attend over the quantized pool, not the fp input
    o_ref[0] = _merge_current(
        q, part, kq.astype(jnp.float32) * ks_new[:, None],
        vq.astype(jnp.float32) * vs_new[:, None],
        scale=scale, attn_softcap=attn_softcap).astype(o_ref.dtype)

    @pl.when(length > 0)
    def _finish():
        pltpu.make_async_copy(
            kblk, kd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).wait()
        pltpu.make_async_copy(
            vblk, vd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).wait()
        pltpu.make_async_copy(
            ksrow, ks_out.at[:, w_pid], wsem.at[2]).wait()
        pltpu.make_async_copy(
            vsrow, vs_out.at[:, w_pid], wsem.at[3]).wait()


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap", "interpret")
)
def pallas_paged_attention_write_int8(
    q: jnp.ndarray,            # [B, n_q, d]
    k_data: jnp.ndarray,       # [n_kv, P, page, d] int8 (donated)
    k_scale: jnp.ndarray,      # [n_kv, P, page] f32    (donated)
    v_data: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    lengths: jnp.ndarray,      # [B] int32 (incl. current token; 0 => idle)
    k_new: jnp.ndarray,        # [B, n_kv, d] current token's K (post-rope)
    v_new: jnp.ndarray,        # [B, n_kv, d]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
):
    """Fused int8 decode attention + quantize-at-write KV append (see
    _paged_kernel_write_int8). Returns
    (attn [B, n_q, d], k_data, k_scale, v_data, v_scale)."""
    B, n_q, d = q.shape
    n_kv, P, page_size, _ = k_data.shape
    pages_per_seq = page_table.shape[1]
    S = pages_per_seq * page_size
    group = n_q // n_kv

    kernel = functools.partial(
        _paged_kernel_write_int8,
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap,
        page_size=page_size, pages_per_seq=pages_per_seq,
    )
    qg = q.reshape(B, n_kv, group, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, n_kv, d), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, d), lambda b, *_: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, S, d), k_data.dtype),
            pltpu.VMEM((n_kv, S, d), v_data.dtype),
            pltpu.VMEM((n_kv, S), jnp.float32),
            pltpu.VMEM((n_kv, S), jnp.float32),
            pltpu.VMEM((n_kv, 8, d), k_data.dtype),
            pltpu.VMEM((n_kv, 8, d), v_data.dtype),
            pltpu.VMEM((n_kv, page_size), jnp.float32),
            pltpu.VMEM((n_kv, page_size), jnp.float32),
            pltpu.SemaphoreType.DMA((4, pages_per_seq)),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    out, kd, ks, vd, vs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
            jax.ShapeDtypeStruct(k_data.shape, k_data.dtype),
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_data.shape, v_data.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ],
        # inputs count scalar-prefetch args first: pt=0, lengths=1, q=2,
        # k_data=3, k_scale=4, v_data=5, v_scale=6, k_new=7, v_new=8;
        # outputs: attn=0, kd=1, ks=2, vd=3, vs=4
        input_output_aliases={3: 1, 4: 2, 5: 3, 6: 4},
        compiler_params=_compiler_params(n_kv, S, d, k_data.dtype, True),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_data, k_scale, v_data, v_scale,
      k_new.astype(jnp.float32), v_new.astype(jnp.float32))
    return out.reshape(B, n_q, d), kd, ks, vd, vs


def _paged_kernel_write_window_int8(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    base_ref,         # SMEM [B] first token's 0-based pool position
    width_ref,        # SMEM [B] tokens to write (0 => idle row)
    kd_hbm,           # ANY  [n_kv, P, page, d] int8 (aliased with kd_out)
    ks_hbm,           # ANY  [n_kv, P, page] f32     (aliased with ks_out)
    vd_hbm,           # ANY  [n_kv, P, page, d] int8
    vs_hbm,           # ANY  [n_kv, P, page] f32
    k_new_ref,        # VMEM [1, W, n_kv, d] — window of new K rows (f32)
    v_new_ref,        # VMEM [1, W, n_kv, d]
    kd_out,           # ANY  (alias of kd_hbm)
    ks_out,           # ANY  (alias of ks_hbm)
    vd_out,           # ANY  (alias of vd_hbm)
    vs_out,           # ANY  (alias of vs_hbm)
    kblk,             # VMEM [n_kv, 8, d] int8 write-block scratch
    vblk,             # VMEM [n_kv, 8, d] int8
    ksrow,            # VMEM [n_kv, page] f32 scale-row scratch
    vsrow,            # VMEM [n_kv, page] f32
    wsem,             # DMA semaphores [4]
    *,
    window: int,
    page_size: int,
):
    """In-place QUANTIZING append of a K-token window per slot — the int8
    twin of _paged_kernel_write_window. Each committed token's row is
    quantized in registers (bit-identical to cache.quantize_kv) and
    spliced via the 8-sublane data RMW + full-page scale-row RMW (see
    _paged_kernel_write_int8 for the lane-tiling rationale). The RMW
    chain is ordered token-by-token: consecutive tokens often share a
    data block AND always share the scale row while inside one page, so
    every write-back completes before the next fetch."""
    b = pl.program_id(0)
    base = base_ref[b]
    width = width_ref[b]

    for t in range(window):
        @pl.when(t < width)
        def _rmw(t=t):
            pos = base + t
            w_pid = page_table_ref[b, pos // page_size]
            off8 = pl.multiple_of((pos % page_size) // 8 * 8, 8)
            pltpu.make_async_copy(
                kd_out.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).start()
            pltpu.make_async_copy(
                vd_out.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).start()
            pltpu.make_async_copy(
                ks_out.at[:, w_pid], ksrow, wsem.at[2]).start()
            pltpu.make_async_copy(
                vs_out.at[:, w_pid], vsrow, wsem.at[3]).start()
            pltpu.make_async_copy(
                kd_out.at[:, w_pid, pl.ds(off8, 8)], kblk, wsem.at[0]).wait()
            pltpu.make_async_copy(
                vd_out.at[:, w_pid, pl.ds(off8, 8)], vblk, wsem.at[1]).wait()
            pltpu.make_async_copy(
                ks_out.at[:, w_pid], ksrow, wsem.at[2]).wait()
            pltpu.make_async_copy(
                vs_out.at[:, w_pid], vsrow, wsem.at[3]).wait()
            kq, ks_new = _quantize_row(k_new_ref[0, t].astype(jnp.float32))
            vq, vs_new = _quantize_row(v_new_ref[0, t].astype(jnp.float32))
            row = jax.lax.broadcasted_iota(
                jnp.int32, (1, 8, 1), 1) == (pos % page_size) - off8
            kblk[...] = jnp.where(row, kq[:, None, :], kblk[...])
            vblk[...] = jnp.where(row, vq[:, None, :], vblk[...])
            lane = jax.lax.broadcasted_iota(
                jnp.int32, (1, page_size), 1) == pos % page_size
            ksrow[...] = jnp.where(lane, ks_new[:, None], ksrow[...])
            vsrow[...] = jnp.where(lane, vs_new[:, None], vsrow[...])
            pltpu.make_async_copy(
                kblk, kd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).start()
            pltpu.make_async_copy(
                vblk, vd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).start()
            pltpu.make_async_copy(
                ksrow, ks_out.at[:, w_pid], wsem.at[2]).start()
            pltpu.make_async_copy(
                vsrow, vs_out.at[:, w_pid], wsem.at[3]).start()
            pltpu.make_async_copy(
                kblk, kd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[0]).wait()
            pltpu.make_async_copy(
                vblk, vd_out.at[:, w_pid, pl.ds(off8, 8)], wsem.at[1]).wait()
            pltpu.make_async_copy(
                ksrow, ks_out.at[:, w_pid], wsem.at[2]).wait()
            pltpu.make_async_copy(
                vsrow, vs_out.at[:, w_pid], wsem.at[3]).wait()


def pallas_paged_write_window_int8(
    k_data: jnp.ndarray,       # [n_kv, P, page, d] int8 (donated)
    k_scale: jnp.ndarray,      # [n_kv, P, page] f32    (donated)
    v_data: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    base: jnp.ndarray,         # [B] int32 0-based position of token 0
    widths: jnp.ndarray,       # [B] int32 tokens to write (<= window)
    k_new: jnp.ndarray,        # [B, W, n_kv, d] window of new K rows
    v_new: jnp.ndarray,        # [B, W, n_kv, d]
    *,
    interpret: bool = False,
):
    """Fused quantize-at-write append of up to W tokens per slot in ONE
    kernel launch — the int8 storage mode of pallas_paged_write_window
    (same entry-point contract: per-slot ``widths`` is the committed
    window length, speculative rejects simply shrink it). Returns
    (k_data, k_scale, v_data, v_scale) updated in place."""
    n_kv, P, page_size, d = k_data.shape
    B, W = k_new.shape[:2]

    kernel = functools.partial(
        _paged_kernel_write_window_int8,
        window=W, page_size=page_size,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, W, n_kv, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, W, n_kv, d), lambda b, *_: (b, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_kv, 8, d), k_data.dtype),
            pltpu.VMEM((n_kv, 8, d), v_data.dtype),
            pltpu.VMEM((n_kv, page_size), jnp.float32),
            pltpu.VMEM((n_kv, page_size), jnp.float32),
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    kd, ks, vd, vs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_data.shape, k_data.dtype),
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_data.shape, v_data.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ],
        # inputs count scalar-prefetch args first: pt=0, base=1, widths=2,
        # k_data=3, k_scale=4, v_data=5, v_scale=6, k_new=7, v_new=8;
        # outputs: kd=0, ks=1, vd=2, vs=3
        input_output_aliases={3: 0, 4: 1, 5: 2, 6: 3},
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), base.astype(jnp.int32),
      widths.astype(jnp.int32), k_data, k_scale, v_data, v_scale,
      k_new.astype(jnp.float32), v_new.astype(jnp.float32))
    return kd, ks, vd, vs


@functools.partial(
    jax.jit, static_argnames=("scale", "sliding_window", "attn_softcap", "interpret")
)
def pallas_paged_attention(
    q: jnp.ndarray,            # [B, n_q, d]
    k_pages: jnp.ndarray,      # [n_kv, P, page, d] (head-major pool)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32
    lengths: jnp.ndarray,      # [B] int32 (incl. current token)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, n_q, d = q.shape
    n_kv, P, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    S = pages_per_seq * page_size
    group = n_q // n_kv

    kernel = functools.partial(
        _paged_kernel,
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap,
        page_size=page_size, pages_per_seq=pages_per_seq,
    )
    # [B, n_kv, group, d]: the block's minor two dims are (group, d), both
    # equal to the full axis — satisfies Mosaic's (8, 128)-or-full-dim rule
    # for any group size (the flat [B, n_q, d] layout did not).
    qg = q.reshape(B, n_kv, group, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_kv, group, d), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, S, d), k_pages.dtype),
            pltpu.VMEM((n_kv, S, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, pages_per_seq)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
        compiler_params=_compiler_params(n_kv, S, d, k_pages.dtype),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, n_q, d)
