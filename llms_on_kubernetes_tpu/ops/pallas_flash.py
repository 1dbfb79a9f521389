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
