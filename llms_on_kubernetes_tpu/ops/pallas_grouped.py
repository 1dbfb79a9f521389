"""Pallas grouped matmul for the expert layers (ops/moe.py).

What ``jax.lax.ragged_dot`` computes, for the shapes where XLA's own
lowering of it is bound by its 256-row tile and not by the experts' bytes:
rows sorted by expert x that expert's matrix, taken out of the layer-stacked
weights IN PLACE.

The unit of work is (touched expert, weight block), read once:

- The rows are laid out GROUP-ALIGNED (``layout``): each expert's rows
  start at a multiple of the row tile, so a tile belongs to exactly one
  expert. At most ``tile - 1`` rows of padding an expert, a static bound
  (``num_tiles``); how many tiles hold rows is known on the device only,
  so it is the kernel's one dynamic grid bound: an expert without rows
  costs no grid step, no DMA and no MXU work.
- grid = (N blocks, tiles, K blocks), the tiles inside a column block:
  consecutive tiles of one expert name the same weight block, which the
  pipeline then does not fetch again. K is whole (one block) wherever a
  [K, tn] block fits ``BLOCK_BYTES``, so an expert's block is fetched once a
  product however many tiles its rows fill.
- The weight operand is the whole stack [n, E, K, N]; its index map picks
  (layer, expert of this tile) from scalar-prefetched values (the layer is
  traced: it comes from the run's ``scan``). Nothing is sliced or copied.
- bf16 x bf16 -> float32 on the MXU, K blocks accumulated in order, bf16
  out. An int8 stack is widened a block at a time as it is loaded; its
  per-column scale is applied to the result by the caller, as for
  ``ragged_dot``. A row's result depends on that row and its expert's
  weights only.

The row tile is 16 (bf16's sublane packing) where an expert gets a handful
of rows, and grows with the mean rows an expert to ``ROW_TILE_MAX``
(``row_tile``). ``grouped_vmem_bytes`` is what the kernel hands Mosaic as
its limit and what ops/moe.py holds against ``attention.VMEM_BUDGET_BYTES``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llms_on_kubernetes_tpu.ops.attention import check_interpret

ROW_TILE_MIN = 16           # a bf16 vreg holds 16 sublanes
ROW_TILE_MAX = 128
# a weight block: large enough that a grid step's DMA (2.5-5 us at
# 819 GB/s) hides the ~0.35 us a step costs, small enough that the first
# block's fetch, which nothing hides, stays a few us a product
BLOCK_BYTES = 4 << 20
_VMEM_HEADROOM = 4 << 20    # Mosaic's own scratch


def row_tile(pairs: int, experts: int) -> int:
    """Rows a grid step multiplies: the power of two at or above the mean
    rows an expert gets, within [ROW_TILE_MIN, ROW_TILE_MAX]."""
    mean = -(-pairs // experts)
    return min(max(ROW_TILE_MIN, 1 << (mean - 1).bit_length()), ROW_TILE_MAX)


def num_tiles(pairs: int, experts: int, tile: int) -> int:
    """Static bound on the tiles of a group-aligned layout: every expert
    wastes at most tile - 1 rows, and a tile holds at least one pair."""
    return max(1, min(pairs, (pairs + experts * (tile - 1)) // tile))


def _divisors(n: int):
    """The multiples of 128 (a lane tile) that divide n."""
    return [d for d in range(128, n + 1, 128) if n % d == 0]


def weight_block(K: int, N: int, itemsize: int) -> tuple[int, int]:
    """(tk, tn) of a weight block [tk, tn]: the widest column block that
    keeps K whole inside BLOCK_BYTES; where even 512 columns do not (a
    14336-deep ``w_down``), 512 columns (1 KiB bursts) of the deepest K
    block that fits. A width that 128 does not divide is taken whole (the
    interpreter's shapes; ops/moe.py keeps them off the compiled kernel)."""
    cols, deep = _divisors(N) or [N], _divisors(K) or [K]
    fit = [d for d in cols if K * d * itemsize <= BLOCK_BYTES]
    if fit and (fit[-1] >= 512 or fit[-1] == cols[-1]):
        return K, fit[-1]
    tn = max([d for d in cols if d <= 512] or cols[:1])
    fit = [d for d in deep if d * tn * itemsize <= BLOCK_BYTES]
    return (fit[-1] if fit else deep[0]), tn


def grouped_vmem_bytes(tile: int, K: int, N: int, x_itemsize: int,
                       w_itemsize: int) -> int:
    """VMEM one product needs: the pipeline's two buffers of the row
    tile, the weight block and the result block, the float32 product (and
    accumulator, where K is split), a widened copy of an int8 block, and
    headroom."""
    tk, tn = weight_block(K, N, w_itemsize)
    need = 2 * (tile * tk * x_itemsize + tk * tn * w_itemsize
                + tile * tn * x_itemsize)
    need += tile * tn * 4 * (2 if tk < K else 1)
    if w_itemsize < x_itemsize:
        need += tk * tn * x_itemsize
    return need + _VMEM_HEADROOM


class Layout(NamedTuple):
    """Where the sorted pairs sit in the group-aligned rows."""
    source: jnp.ndarray       # [T * tile] sorted position feeding each row
    expert: jnp.ndarray       # [T * tile] the row's expert (E: no tile)
    offset: jnp.ndarray       # [E] row of an expert's first pair - its
    #                           sorted position
    tile_expert: jnp.ndarray  # [T] the expert of each tile, for the kernel
    #                           (any, past the tiles that hold rows)
    n_tiles: jnp.ndarray      # [1] tiles that hold rows


def layout(rows: jnp.ndarray, pairs: int, tile: int) -> Layout:
    """rows [E]: the pairs each expert got, in sorted order. Comparisons
    and sums over [T, E] and arithmetic over [T, tile]: nothing is
    scattered, and nothing gathered from an [E]-sized table (XLA unrolls
    such a gather into a select a table entry: thousands of lines of HLO a
    step, seconds of every start-up)."""
    E = rows.shape[0]
    T = num_tiles(pairs, E, tile)
    tiles = (rows + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)                                     # [E]
    sorted_start = jnp.cumsum(rows) - rows
    t = jnp.arange(T, dtype=jnp.int32)
    tile_expert = jnp.sum(tile_end[None, :] <= t[:, None], axis=1,
                          dtype=jnp.int32)                           # [T]
    mine = tile_expert[:, None] == jnp.arange(E, dtype=jnp.int32)    # [T, E]

    def of_tile(table):            # table[tile_expert], 0 past the last
        return jnp.sum(jnp.where(mine, table[None, :], 0), axis=1)

    # a row's place among its expert's pairs
    r = ((t - of_tile(tile_end - tiles))[:, None] * tile
         + jnp.arange(tile, dtype=jnp.int32))                        # [T, tile]
    source = jnp.where(r < of_tile(rows)[:, None],
                       of_tile(sorted_start)[:, None] + r, 0)
    return Layout(source.reshape(T * tile), jnp.repeat(tile_expert, tile),
                  (tile_end - tiles) * tile - sorted_start,
                  jnp.minimum(tile_expert, E - 1), tile_end[-1:])


def _kernel(layer_ref, n_tiles_ref, tile_expert_ref, x_ref, w_ref, o_ref,
            *acc, nk: int):
    del layer_ref, tile_expert_ref          # the index maps read them
    t, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(t < n_tiles_ref[0])
    def _():
        p = jnp.dot(x_ref[...], w_ref[...].astype(x_ref.dtype),
                    preferred_element_type=jnp.float32)
        if nk == 1:
            o_ref[...] = p.astype(o_ref.dtype)
            return
        acc_ref, = acc

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = p

        @pl.when(kk > 0)
        def _():
            acc_ref[...] += p

        @pl.when(kk == nk - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, layer: jnp.ndarray,
                   tile_expert: jnp.ndarray, n_tiles: jnp.ndarray, *,
                   tile: int, interpret: bool = False) -> jnp.ndarray:
    """x [T * tile, K] group-aligned (``layout``) x layer ``layer`` of
    w [n, E, K, N] -> [T * tile, N] in x's type. Rows of tiles past
    ``n_tiles`` are not written."""
    (M, K), N = x.shape, w.shape[3]
    T = tile_expert.shape[0]
    assert M == T * tile and w.shape[2] == K, (x.shape, w.shape, tile, T)
    tk, tn = weight_block(K, N, w.dtype.itemsize)
    nk = K // tk

    def x_map(j, t, kk, layer, n_tiles, tile_expert):
        return t, kk

    def w_map(j, t, kk, layer, n_tiles, tile_expert):
        return layer[0], tile_expert[t], kk, j

    def o_map(j, t, kk, layer, n_tiles, tile_expert):
        return t, j

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # the tiles that hold rows: the one bound the device decides
            # (a grid of one step where none does: the kernel skips it)
            grid=(N // tn, jnp.maximum(n_tiles[0], 1), nk),
            in_specs=[pl.BlockSpec((tile, tk), x_map),
                      pl.BlockSpec((None, None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tile, tn), o_map),
            scratch_shapes=([pltpu.VMEM((tile, tn), jnp.float32)]
                            if nk > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=grouped_vmem_bytes(
                tile, K, N, x.dtype.itemsize, w.dtype.itemsize)),
        name="grouped_expert_matmul",
        interpret=check_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), n_tiles.astype(jnp.int32),
      tile_expert.astype(jnp.int32), x, w)
