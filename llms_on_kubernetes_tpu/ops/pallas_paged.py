"""Pallas paged decode attention.

The TPU-native replacement for vLLM's PagedAttention CUDA kernel (SURVEY
§2.3 row 1; §7 hard-part 1). Semantics match
``ops/attention.py::paged_attention`` (the XLA reference) and are pinned by
tests/test_pallas.py.

Why a kernel at all: the XLA path materializes every slot's logical KV
([B, S_max, n_kv, d]) in HBM via gather before the matmul — decode reads
the KV pool twice (gather write + matmul read). These kernels DMA each
slot's pages HBM→VMEM once and attend in place:

- ``PrefetchScalarGridSpec`` prefetches the page table and lengths into
  SMEM, so any program can compute any row's DMA source addresses.
- The page pool is **head-major** [n_kv, P, page, d] (engine/cache.py), so
  each page is one strided [n_kv, page, d] block for every head at once —
  a single DMA with no sublane-tile slicing (a head-minor pool layout is
  rejected by Mosaic: slicing n_kv to 1 in the tiled sublane slot).
- A page row is whole 128-lane tiles (Mosaic compiles the page DMA for
  nothing narrower). Heads of 64 therefore come two to a row, a pool of
  [n_kv/2, P, page, 128] (cache.heads_per_row), and the kernel bodies run
  on it UNCHANGED as n_kv/2 heads of 128 with twice the group:
  ``_decode_call`` lays q out as [B, n_kv/2, 2*group, 128], row j*group+g
  holding query g of the pair's head j in lanes j*64 .. j*64+63 and zero
  in the other head's, so q'.K' is that head's logits exactly and the
  online softmax is per row as before; the new token's K/V rows
  [B, n_kv, 64] are [B, n_kv/2, 128] by a reshape that moves nothing; and
  of output row j*group+g the wrapper keeps lanes j*64 .. j*64+63 (the
  other half is the probabilities times the OTHER head's values: finite,
  dropped). The MXU does twice the (tiny) work; the bytes moved are the
  live pages' only.
- grid = (B,), sequential: ONE program a slot computes ALL kv heads with
  two batched MXU contractions a block ([group, d] x [d, blk] and
  [group, blk] x [blk, d], f32) under an online softmax. A v5e has one
  TensorCore, so the programs run one after another, and what a program
  does not overlap nobody overlaps for it.
- So the four attending kernels share ONE two-deep software pipeline that
  runs ACROSS the grid steps (``_attend_pipelined``). The work item is a
  block of ``_block_tokens`` (<= 512) tokens of one live row; the items of
  a launch, in order, are each live row's blocks, rows in grid order. They
  alternate between the two halves of a double-buffered staging scratch
  ([2, n_kv, blk, d] for K and for V: 4 MiB at 8 x 128 bf16 however long
  the slot is). While item n is attended, item n + 1 is already in flight
  into the other half: the next block of the same row or, from a row's
  last block, the first block of the NEXT LIVE row (found by scanning
  ``lengths`` past the idle rows) together with that row's 8-row write
  block. A program therefore waits only for a fetch that was started a
  whole block's attention earlier. Which half holds the next item, and
  whether it was already started, is carried between grid steps in SMEM
  scratch; only the first live row of a launch (and a row that follows one
  with nothing to attend) starts its own first fetch.
- Every started DMA is waited for by exactly one program: a block by the
  program of the row it belongs to, just before that block is attended; a
  write block by its row's program before the splice; a write-back by the
  program that started it, at its end. A fetch for the next live row is
  started only if such a row exists, so nothing is in flight when the last
  program ends. An idle row (length 0) starts no DMA, waits for none, and
  leaves the carried state alone — a launch of idle rows touches nothing.
- Why the in-place append cannot race a prefetch: a row's write-back
  rewrites one aligned 8-row block of the page its new token lands in.
  That page is private to the row (an adopted prefix page always ends
  before the write position), so the only fetches that can touch it are
  the row's own, and the write-back starts after the row's LAST block has
  landed (the rows it rewrites besides the new one carry the bytes they
  had; the new row is beyond every key the row attends). What is in
  flight meanwhile — the next live row's pages and write block — lies in
  that row's own pages or in shared prefix pages, which no program of a
  launch writes. All fetches read the input alias and all write-backs go
  to the output alias; no byte is both read and written by two rows.
- ``paged_vmem_bytes`` is what the kernels allocate (the write kernels,
  which allocate most) plus headroom for the block temporaries; it is the
  kernel's ``vmem_limit_bytes`` (the default scoped limit is 16 MiB) and is
  checked against ``attention.VMEM_BUDGET_BYTES`` where the kernel is
  chosen. Staging a block and not a slot makes it independent of the
  slot's length.
- The LATENT pool (DeepSeek MLA: engine/cache.py, [1, P, page, width], one
  row a token that all heads share, every layer's pages in one array) has
  a fifth attending kernel, ``pallas_latent_attention``, for the absorbed
  decode step: the same pipeline as the case n_kv = 1, group = the 128
  query heads, with ONE source, because a row is the key ([c | k_r], 576
  of 640 lanes, zeros behind) and its first 512 lanes are the value. A
  live slot's pages are fetched once and the value operand is a slice of
  the staged key block; staging is [2, 1, 512, 640] bf16 = 1.3 MB
  (``latent_vmem_bytes``). 128 heads over one shared row is ~230 FLOP a
  byte, the v5e's ridge, so its block body (``_attend_latent_block``)
  hands the MXU the pool's own type with float32 accumulation, as the XLA
  loop it replaces does, where ``_attend_block`` widens K and V to float32
  (free at a group of 4). No window, no softcap, no scales, and no append:
  the step's rows are written by ``cache.write_latent`` before the call
  (192 one-row updates a step), and the pool goes in whole in HBM, read
  only. The four K/V kernels trace what they traced before it existed.
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

_BLOCK_TOKENS = 512   # keys attended per online-softmax block
# block temporaries (f32 K/V casts, logits, probabilities) and the
# double-buffered q/o blocks
_VMEM_HEADROOM = 16 << 20


def _block_tokens(page_size: int, pages_per_seq: int) -> int:
    """Largest whole-page block <= _BLOCK_TOKENS that tiles the slot."""
    g = max(1, min(pages_per_seq, _BLOCK_TOKENS // page_size))
    while pages_per_seq % g:
        g -= 1
    return g * page_size


def _scratch_shapes(n_kv: int, page_size: int, pages_per_seq: int, d: int,
                    dtype, quantized: bool, write: bool) -> list:
    """Scratch of one attending kernel, in the order its refs arrive: both
    halves of the K and V staging (and of the f32 per-token scales when
    int8); in a write kernel both halves of the 8-row write blocks (and of
    the scale page rows); the DMA semaphores, one a half, source and page
    of a block (and one a half and write block); the pipeline's state."""
    blk = _block_tokens(page_size, pages_per_seq)
    n_src = 4 if quantized else 2
    shapes = [pltpu.VMEM((2, n_kv, blk, d), dtype)] * 2
    if quantized:
        shapes += [pltpu.VMEM((2, n_kv, blk), jnp.float32)] * 2
    if write:
        shapes += [pltpu.VMEM((2, n_kv, 8, d), dtype)] * 2
        if quantized:
            shapes += [pltpu.VMEM((2, n_kv, page_size), jnp.float32)] * 2
    shapes.append(pltpu.SemaphoreType.DMA((2, n_src, blk // page_size)))
    if write:
        shapes.append(pltpu.SemaphoreType.DMA((2, n_src)))
    shapes.append(pltpu.SMEM((3,), jnp.int32))
    return shapes


def paged_vmem_bytes(n_kv: int, page_size: int, pages_per_seq: int, d: int,
                     dtype, quantized: bool = False) -> int:
    """VMEM the paged decode kernels are given: the VMEM scratch of the
    write kernel (``_scratch_shapes``; the plain kernel's lacks the write
    blocks) and ``_VMEM_HEADROOM``."""
    return _VMEM_HEADROOM + sum(
        math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
        for s in _scratch_shapes(n_kv, page_size, pages_per_seq, d, dtype,
                                 quantized, True)
        if getattr(s, "memory_space", None) == pltpu.VMEM)


def _attend_block(q, carry, k, v, ks, vs, start, n_valid, q_pos, *,
                  scale: float, sliding_window: Optional[int],
                  attn_softcap: Optional[float]):
    """One online-softmax step: fold the staged block k/v [n_kv, blk, d]
    (keys ``start`` ..., of which those below ``n_valid`` exist) into the
    partials ``carry`` = (m, l, acc) of q [n_kv, group, d] at ``q_pos``.
    ``ks``/``vs`` [n_kv, blk] are an int8 pool's per-token scales (None
    for a float pool): the per-key scale is applied to the LOGITS column
    and the per-value scale to the PROBABILITY column (q.(k*s) ==
    (q.k)*s), both lane-dim broadcasts.

    Stale staging (pages not fetched, lanes beyond n_valid) may hold
    anything, NaN included: logits there are REPLACED by the substitutive
    mask, probabilities are zeroed, and V rows / value scales are zeroed
    before the p @ v matmul (0 * NaN = NaN otherwise)."""
    m, l, acc = carry
    n_kv, group, _ = q.shape
    blk = k.shape[1]
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    row = start + jax.lax.broadcasted_iota(jnp.int32, (n_kv, blk, 1), 1)
    v = jnp.where(row < n_valid, v, 0.0)
    logits = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale                                          # [n_kv, group, blk]
    k_pos = start + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, group, blk), 2)
    valid = k_pos < n_valid
    if ks is not None:
        logits = logits * ks[:, None, :]
    logits = softcap(logits, attn_softcap)
    mask = valid
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    logits = jnp.where(mask, logits, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    if vs is not None:
        p = p * jnp.where(valid[:, :1], vs[:, None, :], 0.0)
    acc = alpha * acc + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                  # [n_kv, group, d]
    return m_new, l, acc


def _attend_latent_block(q, carry, buf, half, start, n_valid, *, scale: float,
                         lat: int):
    """``_attend_block`` for a latent pool: fold the block staged in
    ``buf[half]`` ([1, blk, width], rows ``start`` ..., those below
    ``n_valid`` written) into the partials of q [1, heads, width]. A row is
    every head's key and, in its first ``lat`` lanes, every head's value:
    the value operand is a slice of the block the scores were taken from.

    Both products take their operands in the POOL'S type and accumulate in
    float32, as ``attention.latent_paged_attention`` does. All the heads
    share a row, so a block is ~230 FLOP a byte, the v5e's ridge: widened
    to float32 first, as ``_attend_block`` can afford at a group of 4, the
    products would bound the kernel several times over.

    Rows that were not fetched or lie beyond ``n_valid`` may hold anything:
    their scores are replaced and their probabilities zeroed as in
    ``_attend_block``, and, value operand that they are, they are zeroed IN
    the staging half first (0 * NaN is NaN): only in a block that has such
    rows, a row's last. The half is this program's until the next fetch
    into it, which starts after this block."""
    m, l, acc = (x[0] for x in carry)
    heads, blk = q.shape[1], buf.shape[2]

    @pl.when(start + blk > n_valid)
    def _zero_stale_rows():
        row = start + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
        buf[half, 0] = jnp.where(
            row < n_valid, buf[half, 0].astype(jnp.float32), 0.0
        ).astype(buf.dtype)

    rows = buf[half, 0]                                # [blk, width]
    both = jnp.promote_types(q.dtype, rows.dtype)      # one type on the chip
    s = jax.lax.dot_general(
        q[0].astype(both), rows.astype(both), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [heads, blk]
    mask = start + jax.lax.broadcasted_iota(
        jnp.int32, (heads, blk), 1) < n_valid
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jnp.dot(p.astype(rows.dtype), rows[:, :lat],
                                preferred_element_type=jnp.float32)
    return m_new[None], l[None], acc[None]


def _attend_pipelined(q, page_table_ref, lengths_ref, srcs, bufs, sems, st, *,
                      cur: int, append, page_size: int, pages_per_seq: int,
                      scale: float, sliding_window: Optional[int],
                      attn_softcap: Optional[float],
                      latent: Optional[int] = None):
    """This program's part of the launch-wide pipeline (module docstring):
    online-softmax attention of q [n_kv, group, d] (f32) over the cached
    keys of row ``program_id(0)``, one ``blk``-token block at a time, each
    block's pages fetched one item ahead of its attention.

    ``srcs`` are the pool refs in HBM (K, V and, for an int8 pool, their
    per-token scales [n_kv, P, page]) and ``bufs`` their double-buffered
    staging ([2, n_kv, blk, d] / [2, n_kv, blk]); ``sems`` [2, sources,
    pages a block]. ``cur`` is 1 where the row's last token is not in the
    pool yet (the write kernels: the caller merges it from registers), so
    a row of ``length`` attends keys [0, length - cur) from position
    length - 1. ``append`` is the write kernels' ``_Append`` (None
    otherwise): its fetch rides with a row's first block, its write-back
    starts when the row's last block has landed. ``st`` (SMEM int32 [3]):
    the half the next item goes to, whether that item is already in
    flight, and the append's half.

    Only pages that cover a row's tokens are fetched (a slot 100 tokens
    into a 2048-token window must not pay 20x its KV bandwidth), and only
    blocks a static sliding window reaches; what else a half holds is
    masked (``_attend_block``).

    ``latent`` (the latent kernel alone): the ONE source is a latent pool
    whose row is key and, in its first ``latent`` lanes, value; a block is
    folded by ``_attend_latent_block`` in the pool's type and the partial
    ``acc`` is ``latent`` wide.

    Returns the partials (m [n_kv, group, 1], l [n_kv, group, 1],
    acc [n_kv, group, d]); the caller divides (and, in the write kernels,
    first merges the current token). An idle row runs no block."""
    n_kv, group, d = q.shape
    b, B = pl.program_id(0), pl.num_programs(0)
    blk = _block_tokens(page_size, pages_per_seq)
    ppb = blk // page_size

    @pl.when(b == 0)
    def _init():
        for i in range(3):
            st[i] = 0

    def span(length):
        """(first block, blocks, pages) a row of ``length`` attends."""
        lo = 0
        if sliding_window is not None:
            lo = jnp.maximum(length - sliding_window, 0) // blk
        hi = (length - cur + blk - 1) // blk
        n = jnp.where(length > 0, jnp.maximum(hi - lo, 0), 0)
        return lo, n, (length - cur + page_size - 1) // page_size

    def pages(row, jb, n_pages, half, go, wait=False):
        """Start (or wait for) the fetch of block ``jb`` of ``row`` into
        ``half``: one strided [n_kv, page, d] DMA a page and source."""
        for i in range(ppb):
            g = jb * ppb + i

            @pl.when(jnp.logical_and(go, g < n_pages))
            def _page(i=i, g=g):
                pid = page_table_ref[row, g]
                for s, (src, buf) in enumerate(zip(srcs, bufs)):
                    dma = pltpu.make_async_copy(
                        src.at[:, pid],
                        buf.at[half, :, pl.ds(i * page_size, page_size)],
                        sems.at[half, s, i])
                    if wait:
                        dma.wait()
                    else:
                        dma.start()

    length = lengths_ref[b]
    live = length > 0
    lo, n, n_pages = span(length)
    half0, primed = st[0], st[1] == 1

    # the next live row, B where there is none. A row with nothing to
    # attend (one token, nothing cached) has nothing to hide a fetch
    # behind: it looks for nobody and the row after it starts its own.
    def _live(i):
        return lengths_ref[jnp.minimum(i, B - 1)] > 0

    first = jnp.where(n > 0, b + 1, B)
    nxt, _ = jax.lax.while_loop(
        lambda c: jnp.logical_and(c[0] < B, jnp.logical_not(c[1])),
        lambda c: (c[0] + 1, _live(c[0] + 1)), (first, _live(first)))
    has_next = nxt < B
    nrow = jnp.minimum(nxt, B - 1)
    nlo, nn, nn_pages = span(lengths_ref[nrow])

    @pl.when(jnp.logical_and(live, jnp.logical_not(primed)))
    def _own_first_fetch():
        pages(b, lo, n_pages, half0, n > 0)
        if append is not None:
            append.fetch(b, ahead=False)

    def body(j, carry):
        half = (half0 + j) % 2
        last = j == n - 1
        # item n + 1 goes out before item n is waited for
        pages(jnp.where(last, nrow, b), jnp.where(last, nlo, lo + j + 1),
              jnp.where(last, nn_pages, n_pages), 1 - half,
              jnp.where(last, jnp.logical_and(has_next, nn > 0), True))
        if append is not None:
            @pl.when(jnp.logical_and(last, has_next))
            def _next_rows_block():
                append.fetch(nrow, ahead=True)
        pages(b, lo + j, n_pages, half, True, wait=True)
        if append is not None:
            pl.when(last)(append.write)

        if latent is not None:
            return _attend_latent_block(
                q, carry, bufs[0], half, (lo + j) * blk, length - cur,
                scale=scale, lat=latent)
        k, v, *scales = (buf[half] for buf in bufs)
        ks, vs = scales or (None, None)
        return _attend_block(
            q, carry, k, v, ks, vs, (lo + j) * blk, length - cur, length - 1,
            scale=scale, sliding_window=sliding_window,
            attn_softcap=attn_softcap)

    init = (jnp.full((n_kv, group, 1), NEG_INF, jnp.float32),
            jnp.zeros((n_kv, group, 1), jnp.float32),
            jnp.zeros((n_kv, group, latent or d), jnp.float32))
    part = jax.lax.fori_loop(0, n, body, init)

    if append is not None:
        pl.when(jnp.logical_and(live, n == 0))(append.write)

    @pl.when(live)
    def _carry():
        st[0] = (half0 + n) % 2
        st[1] = has_next.astype(jnp.int32)

    return part


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


def _quantize_row(xf):
    """In-register per-token symmetric int8 — MUST match cache.quantize_kv
    bit-for-bit (same max/clip/round chain), so a page written by this
    kernel is byte-identical to one written by the host-side write path.
    xf [n_kv, d] f32 -> (int8 [n_kv, d], f32 scale [n_kv])."""
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    data = jnp.clip(jnp.round(xf / s[:, None]), -127, 127).astype(jnp.int8)
    return data, s


class _Append:
    """The in-place append of a live row's current token, as the write
    kernels' part of the pipeline.

    The new row's write is an 8-token-block READ-MODIFY-WRITE (Mosaic
    requires page-dim slices be 8-sublane-tile aligned): fetch the aligned
    block the token lands in, splice the row in with a vector select, DMA
    the block back through the output alias. An int8 pool's scale takes a
    FULL-PAGE row [n_kv, page] the same way (an 8-lane slice would break
    the 128-lane tiling, a whole page row never does). The block's other
    rows are the same slot's own earlier tokens (pages are slot-private at
    the write position — adopted prefix pages always end before it) or
    unwritten garbage, both of which round-trip unchanged.

    ``ins``/``outs`` are the pool refs (aliased pairs), ``blks`` their
    double-buffered block scratch ([2, n_kv, 8, d] data, [2, n_kv, page]
    scales), ``news`` the row's values to splice ([n_kv, d] / [n_kv]).
    The row's block is in half ``st[2]``; the NEXT live row's is fetched
    into the other while this row's write-back may still be in flight
    from this one."""

    def __init__(self, page_table_ref, lengths_ref, ins, outs, blks, wsem,
                 st, news, page_size):
        self.page_table_ref, self.lengths_ref = page_table_ref, lengths_ref
        self.ins, self.outs, self.blks = ins, outs, blks
        self.wsem, self.st, self.news = wsem, st, news
        self.page_size = page_size
        self.row = pl.program_id(0)     # read here: not inside a loop body

    def _copies(self, row, ahead, back):
        """The DMAs of ``row``'s write position: pool -> block scratch,
        or (``back``) block scratch -> pool."""
        pos = jnp.maximum(self.lengths_ref[row] - 1, 0)
        pid = self.page_table_ref[row, pos // self.page_size]
        off8 = pl.multiple_of((pos % self.page_size) // 8 * 8, 8)
        half = 1 - self.st[2] if ahead else self.st[2]
        for s, (src, out, blk) in enumerate(
                zip(self.ins, self.outs, self.blks)):
            hbm = out if back else src
            # a data pool's aligned 8-row block, a scale pool's page row
            at = (hbm.at[:, pid, pl.ds(off8, 8)] if len(hbm.shape) == 4
                  else hbm.at[:, pid])
            yield pltpu.make_async_copy(
                *((blk.at[half], at) if back else (at, blk.at[half])),
                self.wsem.at[half, s])

    def fetch(self, row, ahead):
        for dma in self._copies(row, ahead, False):
            dma.start()

    def write(self):
        """Splice this program's row into its fetched block and start the
        write-back; ``finish`` waits for it."""
        for dma in self._copies(self.row, False, False):
            dma.wait()
        at = jnp.maximum(self.lengths_ref[self.row] - 1, 0) % self.page_size
        half = self.st[2]
        for blk, new in zip(self.blks, self.news):
            if len(blk.shape) == 4:
                hit = jax.lax.broadcasted_iota(
                    jnp.int32, (1, 8, 1), 1) == at % 8
                blk[half] = jnp.where(hit, new[:, None, :], blk[half])
            else:
                hit = jax.lax.broadcasted_iota(
                    jnp.int32, (1, self.page_size), 1) == at
                blk[half] = jnp.where(hit, new[:, None], blk[half])
        for dma in self._copies(self.row, False, True):
            dma.start()

    def finish(self):
        """Wait for a live row's write-back and hand the other half to
        the next live row (whose block may already be in it)."""
        @pl.when(self.lengths_ref[self.row] > 0)
        def _wait():
            for dma in self._copies(self.row, False, True):
                dma.wait()
            self.st[2] = 1 - self.st[2]


def _paged_kernel(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, n_kv, group, d]
    k_hbm,            # ANY  [n_kv, P, page, d] (head-major pool)
    v_hbm,            # ANY  [n_kv, P, page, d]
    o_ref,            # VMEM [1, n_kv, group, d]
    k_buf,            # VMEM [2, n_kv, blk, d] staging, both halves
    v_buf,            # VMEM [2, n_kv, blk, d]
    sems,             # DMA semaphores [2, 2, pages a block]
    st,               # SMEM [3] the pipeline's carried state
    **kw,             # _attend_pipelined's: scale, window, softcap, geometry
):
    """Grid is (B,): ONE program per slot computes ALL kv heads.

    A (B, n_kv) grid ran B*n_kv tiny sequential programs (a v5e chip has a
    single TensorCore — grid steps serialize), and per-program overhead
    (DMA issue/wait, matmul setup) dominated: measured ~2 ms per LAYER at
    B=64, ~13 ms of a 33 ms decode step. Batching the head dimension into
    one program amortizes that overhead 8x: each page DMA moves the
    [n_kv, page, d] strided block for every head at once, and the two MXU
    contractions run batched over heads. What is left of it, a program's
    DMA round trip, the pipeline hides behind the previous block's
    attention (module docstring)."""
    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    _, l, acc = _attend_pipelined(
        q, page_table_ref, lengths_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        st, cur=0, append=None, **kw)
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
    k_buf,            # VMEM [2, n_kv, blk, d] int8 staging
    v_buf,            # VMEM [2, n_kv, blk, d] int8
    ks_buf,           # VMEM [2, n_kv, blk] f32
    vs_buf,           # VMEM [2, n_kv, blk] f32
    sems,             # DMA semaphores [2, 4, pages a block]
    st,               # SMEM [3]
    **kw,
):
    """int8 decode attention, head-batched and pipelined like _paged_kernel:
    the page DMA moves 1-byte KV plus a per-token scale vector, and the
    dequantize folds into LANE-dim multiplies — decode attention HBM
    traffic is halved vs bf16.

    Layout trick: a per-KEY-token scale can be applied to the LOGITS
    column instead of to K rows (q·(k·s) == (q·k)·s), and a per-VALUE
    scale to the probability column instead of V rows. Both are [*, blk]
    lane-dim broadcasts, so no sublane-broadcast/transpose of the scale
    vector is ever needed — and the scale DMAs land at lane offsets
    i*page_size, which Mosaic accepts only when page_size is a multiple
    of the 128-lane tile (enforced by the dispatcher)."""
    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    _, l, acc = _attend_pipelined(
        q, page_table_ref, lengths_ref, (k_hbm, v_hbm, ks_hbm, vs_hbm),
        (k_buf, v_buf, ks_buf, vs_buf), sems, st, cur=0, append=None, **kw)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
    k_buf,            # VMEM [2, n_kv, blk, d] staging, both halves
    v_buf,            # VMEM [2, n_kv, blk, d]
    kblk,             # VMEM [2, n_kv, 8, d] write blocks, both halves
    vblk,             # VMEM [2, n_kv, 8, d]
    sems,             # DMA semaphores [2, 2, pages a block]
    wsem,             # DMA semaphores [2, 2] (write-block RMW)
    st,               # SMEM [3]
    **kw,
):
    """Decode attention WITH the current token's KV write folded in.

    The per-slot DUS write loop costs ~3 ms/step at B=64 (4096 tiny ops
    of pure dispatch overhead — round-4 profile), and the opt-in HLO
    scatter reserves a ~0.37-pool HBM temp that breaks the 16 GB bench
    config at compile time. This kernel removes the separate write
    entirely: each slot's program (which is already running for the
    attention) DMAs its new K/V row [n_kv, d] into the pool page
    in place (input_output aliasing, ``_Append``) and folds the current
    token into the softmax IN REGISTERS via the online-softmax merge — so
    the row never needs to be read back from HBM, and cached-page DMAs
    cover only the length-1 previously written tokens. The write block is
    fetched with the row's first block (one item ahead, like it) and
    written back once the row's last block has landed; the write-back
    overlaps that block's attention and is waited for at the end.

    Idle slots (length == 0) skip the write and produce a harmless
    pure-current-token output (discarded by the engine)."""
    news = (k_new_ref[0], v_new_ref[0])
    append = _Append(page_table_ref, lengths_ref, (k_hbm, v_hbm),
                     (k_out, v_out), (kblk, vblk), wsem, st, news,
                     kw["page_size"])
    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    part = _attend_pipelined(
        q, page_table_ref, lengths_ref, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        st, cur=1, append=append, **kw)
    o_ref[0] = _merge_current(
        q, part, news[0].astype(jnp.float32), news[1].astype(jnp.float32),
        scale=kw["scale"], attn_softcap=kw["attn_softcap"]).astype(o_ref.dtype)
    append.finish()


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
    k_buf,            # VMEM [2, n_kv, blk, d] int8 staging
    v_buf,            # VMEM [2, n_kv, blk, d] int8
    ks_buf,           # VMEM [2, n_kv, blk] f32
    vs_buf,           # VMEM [2, n_kv, blk] f32
    kblk,             # VMEM [2, n_kv, 8, d] int8 write blocks
    vblk,             # VMEM [2, n_kv, 8, d] int8
    ksrow,            # VMEM [2, n_kv, page] f32 scale page rows
    vsrow,            # VMEM [2, n_kv, page] f32
    sems,             # DMA semaphores [2, 4, pages a block]
    wsem,             # DMA semaphores [2, 4] (write-block RMW)
    st,               # SMEM [3]
    **kw,
):
    """int8 decode attention WITH the current token QUANTIZED AND WRITTEN
    in the same program — the storage-side twin of _paged_kernel_write.

    The new K/V row arrives full-width, is quantized in registers
    (bit-identical to cache.quantize_kv, so fused and host write paths
    produce the same pool bytes), and lands in the pool via the same
    8-sublane-tile data RMW as the fp kernel plus a FULL-PAGE scale-row
    RMW (``_Append``). The current token folds into the online softmax
    using its DEQUANTIZED value (data * scale), so the output matches a
    write-then-attend over the quantized pool, not the fp input."""
    # quantize the incoming row once; both the write-back and the in-
    # register softmax contribution use the SAME quantized values
    kq, ks_new = _quantize_row(k_new_ref[0].astype(jnp.float32))
    vq, vs_new = _quantize_row(v_new_ref[0].astype(jnp.float32))
    srcs = (kd_hbm, vd_hbm, ks_hbm, vs_hbm)
    append = _Append(page_table_ref, lengths_ref, srcs,
                     (kd_out, vd_out, ks_out, vs_out),
                     (kblk, vblk, ksrow, vsrow), wsem, st,
                     (kq, vq, ks_new, vs_new), kw["page_size"])
    q = q_ref[0].astype(jnp.float32)                   # [n_kv, group, d]
    part = _attend_pipelined(
        q, page_table_ref, lengths_ref, srcs, (k_buf, v_buf, ks_buf, vs_buf),
        sems, st, cur=1, append=append, **kw)
    o_ref[0] = _merge_current(
        q, part, kq.astype(jnp.float32) * ks_new[:, None],
        vq.astype(jnp.float32) * vs_new[:, None],
        scale=kw["scale"], attn_softcap=kw["attn_softcap"]).astype(o_ref.dtype)
    append.finish()


def _decode_call(kernel, q, pools, page_table, lengths, news, *, interpret,
                 **static):
    """One attending kernel over ``pools`` (K, V; or K data, K scale, V
    data, V scale of an int8 pool) for q [B, n_q, d]. With ``news`` (the
    current token's K and V [B, n_kv, d]) the pools are updated in place
    and returned after the attention [B, n_q, d]. Where a pool row holds
    ``pair`` heads of q's width side by side, the kernel sees them as one
    head of the row's width (module docstring)."""
    B, n_q, head_dim = q.shape
    n_kv, _, page_size, d = pools[0].shape
    pages_per_seq = page_table.shape[1]
    pair = d // head_dim
    group = n_q // n_kv              # query rows a pool row: pair * the GQA group
    quantized, write = len(pools) == 4, bool(news)
    q = q.reshape(B, n_kv, group, head_dim)
    if pair > 1:
        # each query row, in its own head's lanes of the row and zero in
        # the others'
        own = jnp.eye(pair, dtype=q.dtype)[:, None, :, None]
        q = (q.reshape(B, n_kv, pair, group // pair, 1, head_dim)
             * own).reshape(B, n_kv, group, d)
        news = [x.reshape(B, n_kv, d) for x in news]

    def row_block(*shape):
        return pl.BlockSpec((1, *shape), lambda b, *_: (b, *(0,) * len(shape)))

    # q as [B, n_kv, group, d]: the block's minor two dims are (group, d),
    # both equal to the full axis — satisfies Mosaic's (8, 128)-or-full-dim
    # rule for any group size (the flat [B, n_q, d] layout did not).
    in_pool = [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_block(n_kv, group, d), *in_pool,
                  *[row_block(n_kv, d)] * len(news)],
        out_specs=[row_block(n_kv, group, d), *(in_pool if write else [])],
        scratch_shapes=_scratch_shapes(n_kv, page_size, pages_per_seq, d,
                                       pools[0].dtype, quantized, write),
    )
    out, *pools_out = pl.pallas_call(
        functools.partial(kernel, page_size=page_size,
                          pages_per_seq=pages_per_seq, **static),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, n_kv, group, d), q.dtype),
                   *[jax.ShapeDtypeStruct(p.shape, p.dtype)
                     for p in (pools if write else [])]],
        # inputs count the scalar-prefetch args first (page table 0,
        # lengths 1, q 2, then the pools); output 0 is the attention
        input_output_aliases=(
            {3 + i: 1 + i for i in range(len(pools))} if write else {}),
        compiler_params=pltpu.CompilerParams(
            # the pipeline carries state from one grid step to the next
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=paged_vmem_bytes(
                n_kv, page_size, pages_per_seq, d, pools[0].dtype,
                quantized)),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, *pools, *news)
    if pair > 1:
        # output row j*g+i is head j's: its values are lanes j*64 ...
        out = out.reshape(B, n_kv, pair, group // pair, pair, head_dim)
        out = jnp.stack([out[:, :, j, :, j] for j in range(pair)], axis=2)
    return (out.reshape(B, n_q, head_dim), *pools_out)


_STATIC = ("scale", "sliding_window", "attn_softcap", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    return _decode_call(
        _paged_kernel, q, (k_pages, v_pages), page_table, lengths, (),
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    return _decode_call(
        _paged_kernel_int8, q, (k_data, k_scale, v_data, v_scale),
        page_table, lengths, (), scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    return _decode_call(
        _paged_kernel_write, q, (k_pages, v_pages), page_table, lengths,
        (k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype)),
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    return _decode_call(
        _paged_kernel_write_int8, q, (k_data, k_scale, v_data, v_scale),
        page_table, lengths,
        (k_new.astype(jnp.float32), v_new.astype(jnp.float32)),
        scale=scale, sliding_window=sliding_window,
        attn_softcap=attn_softcap, interpret=interpret)


# ---------------------------------------------------------------------------
# The latent pool's decode kernel (DeepSeek MLA, absorbed)
# ---------------------------------------------------------------------------

def latent_vmem_bytes(page_size: int, pages_per_seq: int, width: int,
                      dtype) -> int:
    """VMEM the latent kernel is given (``paged_vmem_bytes``' rule): both
    halves of its ONE staging, 1.3 MB at 512 tokens of 640 bfloat16 lanes
    however long the slot, and ``_VMEM_HEADROOM``."""
    blk = _block_tokens(page_size, pages_per_seq)
    return _VMEM_HEADROOM + 2 * blk * width * jnp.dtype(dtype).itemsize


def _latent_kernel(
    page_table_ref,   # SMEM [B, pages_per_seq] (scalar prefetch)
    lengths_ref,      # SMEM [B]                (scalar prefetch)
    q_ref,            # VMEM [1, 1, heads, width] absorbed queries, zero-padded
    pool_hbm,         # ANY  [1, P, page, width]  every layer's latent rows
    o_ref,            # VMEM [1, 1, heads, lat]
    buf,              # VMEM [2, 1, blk, width] staging, both halves
    sems,             # DMA semaphores [2, 1, pages a block]
    st,               # SMEM [3] the pipeline's carried state
    **kw,             # _attend_pipelined's: scale, latent, geometry
):
    """The pipeline's case of ONE kv head whose row every query head
    shares (n_kv = 1, group = heads): a live slot's pages come HBM -> VMEM
    once and serve as keys and as values; an idle slot moves nothing. The
    current token's row is already in the pool (``cache.write_latent``), so
    nothing is merged from registers (``cur`` = 0). q stays in its type:
    it is an operand of the product (``_attend_latent_block``)."""
    _, l, acc = _attend_pipelined(
        q_ref[0], page_table_ref, lengths_ref, (pool_hbm,), (buf,), sems, st,
        cur=0, append=None, sliding_window=None, attn_softcap=None, **kw)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "lat", "interpret"))
def pallas_latent_attention(
    q_abs: jnp.ndarray,        # [B, heads, <= width] = [q_n W_UK^T | q_r]
    pool: jnp.ndarray,         # [1, P, page, width]; past lat + rope, zeros
    page_table: jnp.ndarray,   # [B, pages_per_seq] int32 (this layer's pages)
    lengths: jnp.ndarray,      # [B] int32 (incl. current token; 0 => idle)
    *,
    scale: float,
    lat: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """``attention.latent_paged_attention`` as a kernel: o_lat [B, heads,
    lat] in q_abs' type. The pool is read where it lies, whole in HBM."""
    B, heads, w = q_abs.shape
    _, _, page_size, width = pool.shape
    pages_per_seq = page_table.shape[1]
    blk = _block_tokens(page_size, pages_per_seq)
    q = jnp.pad(q_abs, ((0, 0), (0, 0), (0, width - w)))[:, None]

    def row_block(d):
        return pl.BlockSpec((1, 1, heads, d), lambda b, *_: (b, 0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size,
                          pages_per_seq=pages_per_seq, scale=scale,
                          latent=lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[row_block(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_block(lat),
            # both halves of ONE staging (a row is key and value), a DMA
            # semaphore a half and page, the pipeline's state
            scratch_shapes=[
                pltpu.VMEM((2, 1, blk, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2, 1, blk // page_size)),
                pltpu.SMEM((3,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, 1, heads, lat), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=latent_vmem_bytes(
                page_size, pages_per_seq, width, pool.dtype)),
        interpret=check_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
    return out[:, 0]
