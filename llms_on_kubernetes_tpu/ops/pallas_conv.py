"""Pallas convolution-window step over live tiles (the Mamba layers' token
step).

What ``attention.conv_token_step`` computes for one token of a Mamba
layer, ``c = w0 tap0 + w1 tap1 + w2 tap2 + w3 x + b`` in float32 in that
order, ``xc = silu(c)``, and for a live row the new window ``[tap1 | tap2 |
x]``, on the window array where it lies and in ONE pass over its bytes.

Why a kernel at all: the XLA form reads a layer's 128 rows out of the
array into a buffer of their own (the write goes to the lanes the read
comes from, one tap to the left, so the compiler cannot update in place),
selects, convolves and writes the rows back: four ops a layer over
``bf16[128, 15360]`` where the general ``T``-position code ran ten (PERF.md
section 6, PR 52). Here:

- The WHOLE window array [n_layers, slots + 1, (taps - 1) Di] goes in and
  comes out in HBM, aliased (``input_output_aliases``), as the state-space
  state goes through ``pallas_ssm_step``; the layer is a scalar in SMEM.
- The rows go ``TILE_ROWS`` at a time, a whole (16, 128) bfloat16 tile of
  sublanes, through the pipeline's own DMAs: while a tile is convolved in
  VMEM the next is in flight in and the last in flight out. A tile is read
  whole before any of it is written, so the shift by one tap needs no
  second buffer in HBM.
- Only tiles with a live row are visited (``attention.live_tiles_first``:
  the engine takes the lowest free slot, so the live rows crowd the first
  tiles): the grid's bound is the count, a scalar the device reads. An
  idle row of a visited tile keeps its window by a select; a tile with no
  live row, and the trash row, are neither read nor written. With no row
  live the one step copies tile 0 onto itself.
- x comes as the first ``Di`` lanes of the in-projection's result [B, 2 Di]
  (the block's index map takes them: no slice is made in HBM); ``xc`` goes
  out in x's type and once more in float32, what the state-space step
  reads. Their rows in a tile that was not visited are never written (the
  caller masks them).
- The sum is float32, oldest tap first, as the XLA forms add it; the
  select is made on the float32 taps and rounded back (exact: they came
  from the window's type). The tile is worked through ``LANES`` lanes at a
  time so that no temporary outgrows the registers by much.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llms_on_kubernetes_tpu.ops.attention import check_interpret

TILE_ROWS = 16             # rows a grid step: one bfloat16 tile of sublanes
LANES = 512                # lanes of the tile convolved at a time


def _conv_step_kernel(
    tiles_ref,        # SMEM [B / rows] live tiles first (the index maps')
    n_ref,            # SMEM [1] how many tiles hold a live row (the grid's)
    layer_ref,        # SMEM [1] the layer's index in the window array
    w_ref,            # VMEM [taps, Di] float32
    b_ref,            # VMEM [1, Di] float32
    live_ref,         # VMEM [rows, 1] int32: 1 where the row decodes
    x_ref,            # VMEM [rows, Di] the token's input
    win_ref,          # VMEM [rows, (taps - 1) Di] the tile's windows
    xc_ref,           # VMEM [rows, Di] out, x's type
    xf_ref,           # VMEM [rows, Di] out, float32
    out_ref,          # VMEM [rows, (taps - 1) Di] out (alias of win_ref's)
    *,
    lanes: int,
):
    del tiles_ref, n_ref, layer_ref
    rows, Di = x_ref.shape
    taps = w_ref.shape[0]
    f32 = jnp.float32
    live = jnp.broadcast_to(live_ref[...], (rows, lanes)) > 0
    for lo in range(0, Di, lanes):
        sl = slice(lo, lo + lanes)
        t = [win_ref[:, j * Di + lo:j * Di + lo + lanes].astype(f32)
             for j in range(taps - 1)]
        t.append(x_ref[:, sl].astype(f32))
        c = t[0] * w_ref[0:1, sl]
        for j in range(1, taps):
            c = c + t[j] * w_ref[j:j + 1, sl]
        xc = jax.nn.silu(c + b_ref[:, sl]).astype(xc_ref.dtype)
        xc_ref[:, sl] = xc
        xf_ref[:, sl] = xc.astype(f32)
        for j in range(taps - 1):
            out_ref[:, j * Di + lo:j * Di + lo + lanes] = jnp.where(
                live, t[j + 1], t[j]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_conv_step(
    xz: jnp.ndarray,        # [B, >= Di]: x in the first Di lanes
    w: jnp.ndarray,         # [taps, Di] float32
    b: jnp.ndarray,         # [Di] float32
    conv: jnp.ndarray,      # [n_layers, slots + 1, (taps - 1) Di] (donated)
    layer: jnp.ndarray,     # int32 scalar: the layer's index in ``conv``
    live: jnp.ndarray,      # [B] bool
    tiles: jnp.ndarray,     # [B / rows] int32, ``attention.live_tiles_first``'s
    n_tiles: jnp.ndarray,   # [1] int32
    *,
    interpret: bool = False,
):
    """One token step of one Mamba layer's convolution on the window
    array, in place: (xc [B, Di] in xz's type, the same float32, conv).
    Row i is slot i. ``xc`` of a row in a tile with no live row is whatever
    the buffer held; no such tile's windows, and not the trash row, are
    read or written."""
    B = xz.shape[0]
    taps, Di = w.shape
    rows = B // tiles.shape[0]
    assert conv.dtype == xz.dtype and conv.shape[2] == (taps - 1) * Di, (
        conv.shape, conv.dtype, xz.dtype)
    # (the interpreter takes any width whole; Mosaic gets whole chunks:
    # attention.conv_step_mode)
    lanes = LANES if Di % LANES == 0 else Di
    f32 = jnp.float32

    def whole(i, tiles, n, layer):
        return 0, 0

    def tile(i, tiles, n, layer):
        return tiles[i], 0

    def window(i, tiles, n, layer):
        return layer[0], tiles[i], 0

    win_spec = pl.BlockSpec((None, rows, (taps - 1) * Di), window)
    n_tiles = n_tiles.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_conv_step_kernel, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # the tiles that hold a live row: the one bound the device
            # decides (a grid of one step where none does)
            grid=(jnp.maximum(n_tiles[0], 1),),
            in_specs=[pl.BlockSpec((taps, Di), whole),
                      pl.BlockSpec((1, Di), whole),
                      pl.BlockSpec((rows, 1), tile),
                      pl.BlockSpec((rows, Di), tile), win_spec],
            out_specs=[pl.BlockSpec((rows, Di), tile),
                       pl.BlockSpec((rows, Di), tile), win_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, Di), xz.dtype),
                   jax.ShapeDtypeStruct((B, Di), f32),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        # inputs count the scalar-prefetch arguments first: tiles=0, n=1,
        # layer=2, w=3, b=4, live=5, xz=6, conv=7; outputs: xc=0, xf=1,
        # conv=2
        input_output_aliases={7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="conv_step_live_tiles",
        interpret=check_interpret(interpret),
    )(tiles.astype(jnp.int32), n_tiles,
      jnp.asarray(layer, jnp.int32).reshape(1),
      w.astype(f32), b.astype(f32).reshape(1, Di),
      live.astype(jnp.int32).reshape(B, 1), xz, conv)
