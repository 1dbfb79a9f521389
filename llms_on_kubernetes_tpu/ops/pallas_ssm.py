"""Pallas state-space step over live slots (the Mamba layers' token step).

What ``models/decoder.py::_ssm_scan``'s one-step branch computes,
``h = exp(delta A) h + (delta x) B`` and ``y = sum_N(h C)``, for the rows
that decode and for no other, on the state where it lies.

Why a kernel at all: the XLA step reads ``ssm[layer, :B]``, computes every
row, selects old against new for the idle rows and writes all B rows back,
then reads what it wrote once more for ``y``: three passes over B x [N, Di]
float32 a layer whatever is live (4.8 ms of a 16.3 ms token step at 26
layers of 128 slots of [16, 5120], 56 of them live: PERF.md section 6,
PR 48). Here:

- The WHOLE state array [n_layers, slots + 1, N, Di] float32 goes in and
  comes out in HBM, aliased (``input_output_aliases``), as the page pool
  goes through ``pallas_paged_attention_write``; the layer and the live
  slots are scalars in SMEM (``attention.live_first``: the live slots'
  numbers first, in slot order, and how many).
- ONE program a layer loops over the live slots with hand-issued DMAs, two
  slots deep: while slot i's [N, Di] block (328 KB at [16, 5120]) is
  updated in VMEM, slot i + 1's block is in flight in and slot i - 1's in
  flight out. A block crosses HBM once each way; ``y`` is summed from the
  block in hand. (A grid step a live slot under the pipeline's own DMAs
  measured the same to 1 % on a v5e; it has to map a launch with no live
  row onto some block and write that back.)
- An idle slot and the trash row are in no list: no DMA is issued for them
  and nothing is written, so a masked row leaves its slot's state where its
  last live step put it by construction. ``y`` rows of idle slots are never
  written (the caller masks them).
- The step's other operands come as XLA lays them out, whole in VMEM:
  delta, x and y [B, Di] (a slot's row is read and written at a dynamic
  sublane; handed over a row a slot, [B, 1, Di], every producer and
  consumer around the kernel paid a re-layout: 2.8 ms a token step), B and
  C [B, N] (a slot's row becomes the column that broadcasts over the
  channels by a select and a sum, ``_column``), A [N, Di].
- Every operation is float32 on the VPU and the EUP: ``h`` keeps the
  precision it has at rest (a bfloat16 ``h`` fails the model's tolerance,
  tests/test_jamba.py). The block is worked through ``LANES`` lanes at a
  time so that no temporary outgrows the registers by much.
- Every started DMA is waited for exactly once: a slot's fetch by the
  iteration that computes it, its write-back two iterations later (before
  its buffer is filled again) or after the loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llms_on_kubernetes_tpu.ops.attention import check_interpret

LANES = 512                # lanes of the block updated at a time
_VMEM_HEADROOM = 8 << 20    # the chunk temporaries and Mosaic's own scratch


def ssm_step_vmem_bytes(B: int, N: int, Di: int) -> int:
    """VMEM the kernel is given: both halves of a slot's block in and out,
    and twice (the pipeline's two buffers of a block that never changes)
    A, delta, x and y whole and B and C with their 16 lanes padded to 128;
    and ``_VMEM_HEADROOM``."""
    return _VMEM_HEADROOM + 4 * (4 * N * Di + 2 * (N + 3 * B) * Di
                                 + 4 * B * 128)


def _column(t_ref, slot):
    """Row ``slot`` of t [B, N] as a column [N, 1] (a slot's B or C, to be
    broadcast over the channels): the row spread over N sublanes, its
    diagonal kept, summed over the lanes."""
    row = t_ref[pl.ds(slot, 1), :]
    N = row.shape[1]
    at = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
          == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
    return jnp.sum(jnp.where(at, jnp.broadcast_to(row, (N, N)), 0.0),
                   axis=1, keepdims=True)


def _update_slot(slot, a_ref, b_ref, c_ref, d_ref, x_ref, y_ref, h_in, h_out,
                 lanes):
    """One slot's step, ``lanes`` lanes at a time: h_in, h_out [N, Di]
    (refs), row ``slot`` of d, x and y [B, Di], float32 throughout."""
    b, c = _column(b_ref, slot), _column(c_ref, slot)
    at = pl.ds(slot, 1)
    for lo in range(0, a_ref.shape[1], lanes):
        sl = slice(lo, lo + lanes)
        d = d_ref[at, sl]
        h = jnp.exp(d * a_ref[:, sl]) * h_in[:, sl] + (d * x_ref[at, sl]) * b
        h_out[:, sl] = h
        y_ref[at, sl] = jnp.sum(h * c, axis=0, keepdims=True)


def _ssm_step_kernel(
    slots_ref,        # SMEM [B] live slots first (scalar prefetch)
    n_ref,            # SMEM [1] how many are live
    layer_ref,        # SMEM [1] the layer's index in the state array
    a_ref,            # VMEM [N, Di] A = -exp(A_log)
    b_ref, c_ref,     # VMEM [B, N]  B and C, a row a slot
    d_ref, x_ref,     # VMEM [B, Di] delta and x
    h_hbm,            # ANY  [n_layers, slots + 1, N, Di] (aliased with h_out)
    y_ref,            # VMEM [B, Di] out
    h_out,            # ANY  (alias of h_hbm)
    hin, hout,        # VMEM [2, N, Di] a slot's block, in and out, two deep
    sems,             # DMA semaphores [2, 2]
    *,
    lanes: int,
):
    n = n_ref[0]
    layer = layer_ref[0]

    def fetch(slot, k):
        return pltpu.make_async_copy(h_hbm.at[layer, slot], hin.at[k],
                                     sems.at[0, k])

    def write_back(slot, k):
        return pltpu.make_async_copy(hout.at[k], h_out.at[layer, slot],
                                     sems.at[1, k])

    @pl.when(n > 0)
    def _first():
        fetch(slots_ref[0], 0).start()

    def one_slot(i, carry):
        k = i % 2
        slot = slots_ref[i]

        @pl.when(i + 1 < n)
        def _next():
            fetch(slots_ref[i + 1], 1 - k).start()

        fetch(slot, k).wait()

        @pl.when(i >= 2)
        def _free():
            # what iteration i - 2 sent from this buffer has left it
            write_back(slot, k).wait()

        _update_slot(slot, a_ref, b_ref, c_ref, d_ref, x_ref, y_ref,
                     hin.at[k], hout.at[k], lanes)
        write_back(slot, k).start()
        return carry

    jax.lax.fori_loop(0, n, one_slot, 0)

    # the last two slots' write-backs (a DMA's wait takes its size from
    # the descriptor: any slot's is every slot's)
    @pl.when(n >= 2)
    def _last_but_one():
        write_back(slots_ref[0], n % 2).wait()

    @pl.when(n >= 1)
    def _last():
        write_back(slots_ref[0], (n + 1) % 2).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_ssm_step(
    delta: jnp.ndarray,     # [B, Di] float32
    A: jnp.ndarray,         # [N, Di] float32
    x: jnp.ndarray,         # [B, Di] float32
    Bm: jnp.ndarray,        # [B, N] float32
    Cm: jnp.ndarray,        # [B, N] float32
    ssm: jnp.ndarray,       # [n_layers, slots + 1, N, Di] float32 (donated)
    layer: jnp.ndarray,     # int32 scalar: the layer's index in ``ssm``
    slots: jnp.ndarray,     # [B] int32, ``attention.live_first``'s
    n_live: jnp.ndarray,    # [1] int32
    *,
    interpret: bool = False,
):
    """One token step of one Mamba layer on the live slots' state, in
    place: (y [B, Di] float32, ssm). Row i is slot i. ``y`` of an idle
    row is whatever the buffer held; no idle slot's block, and not the
    trash row, is read or written."""
    B, Di = delta.shape
    N = A.shape[0]
    assert ssm.dtype == jnp.float32 and ssm.shape[2:] == (N, Di), ssm.shape
    # (the interpreter takes any width whole; Mosaic gets whole chunks:
    # attention.ssm_step_mode)
    lanes = LANES if Di % LANES == 0 else Di
    f32 = jnp.float32

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_ssm_step_kernel, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole((N, Di)), whole((B, N)), whole((B, N)),
                      whole((B, Di)), whole((B, Di)), any_space],
            out_specs=[whole((B, Di)), any_space],
            scratch_shapes=[
                pltpu.VMEM((2, N, Di), f32), pltpu.VMEM((2, N, Di), f32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct((B, Di), f32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # inputs count the scalar-prefetch arguments first: slots=0, n=1,
        # layer=2, A=3, B=4, C=5, delta=6, x=7, ssm=8; outputs: y=0, ssm=1
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ssm_step_vmem_bytes(B, N, Di)),
        name="ssm_step_live_slots",
        interpret=check_interpret(interpret),
    )(slots.astype(jnp.int32), n_live.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      A.astype(f32), Bm.astype(f32), Cm.astype(f32),
      delta.astype(f32), x.astype(f32), ssm)
