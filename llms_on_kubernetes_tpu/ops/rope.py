"""Rotary position embeddings (HF "rotate_half" convention).

Frequencies are computed on the fly from integer positions rather than from a
precomputed [max_len, dim] table: under ``jit`` XLA folds the trig into the
surrounding fusion, and avoiding the table keeps the decode step free of a
max_len-sized HBM read per layer.

Supports the llama3 long-context frequency rescaling used by Llama-3.1+
(`rope_scaling={"rope_type": "llama3", ...}` in HF configs), linear
scaling, and YaRN: interpolated frequencies (``rope_frequencies``) with, as
DeepSeek-V3 applies it, a factor on the softmax scale
(``yarn_attention_factor``) or, as a published ``attention_factor`` states
it, a factor on cosine and sine (``yarn_cos_sin_factor``).
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    theta: float,
    rope_scaling: Optional[dict] = None,
) -> np.ndarray:
    """Inverse frequencies [head_dim // 2], float32.

    Supported ``rope_scaling`` schemes: llama3 (Llama-3.1+), linear
    (e.g. Gemma-3 global layers) and yarn (DeepSeek-V3). Anything else
    raises — silently dropping a scaling scheme would serve wrong positions
    (see configs.from_hf_config).

    yarn: a pair whose wavelength fits ``beta_fast`` or more times into the
    original context keeps its frequency, one that fits ``beta_slow`` or
    fewer times is divided by ``factor``, and the pairs between are blended
    linearly in the pair's index: with corr(n) = dim ln(orig / (2 pi n)) /
    (2 ln theta), low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)),
    r_i = clip((i - low) / (high - low), 0, 1),
    inv_freq_i = theta_i (1 - r_i) + theta_i / factor r_i.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    if rope_scaling:
        kind = rope_scaling.get("rope_type", rope_scaling.get("type", "llama3"))
        if kind == "linear":
            return (inv_freq / float(rope_scaling.get("factor", 1.0))).astype(np.float32)
        if kind == "yarn":
            low, high = yarn_correction_range(head_dim, theta, rope_scaling)
            ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                           / max(high - low, 1e-3), 0.0, 1.0)
            factor = float(rope_scaling["factor"])
            return (inv_freq * (1.0 - ramp)
                    + inv_freq / factor * ramp).astype(np.float32)
        if kind != "llama3":
            raise NotImplementedError(f"unsupported rope_scaling type {kind!r}")
        factor = float(rope_scaling.get("factor", 8.0))
        low = float(rope_scaling.get("low_freq_factor", 1.0))
        high = float(rope_scaling.get("high_freq_factor", 4.0))
        orig = float(rope_scaling.get("original_max_position_embeddings", 8192))
        wavelen = 2.0 * math.pi / inv_freq
        # llama3 scheme: leave high-freq alone, divide low-freq by factor,
        # smooth interpolation in between.
        smooth = (orig / wavelen - low) / (high - low)
        smooth = np.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = np.where(
            wavelen > orig / low,  # low frequency band
            scaled,
            np.where(
                wavelen < orig / high,  # high frequency band
                inv_freq,
                (1.0 - smooth) * scaled + smooth * inv_freq,
            ),
        )
    return inv_freq.astype(np.float32)


def yarn_correction_range(head_dim: int, theta: float,
                          rope_scaling: dict) -> tuple[int, int]:
    """(low, high): the pair indices between which yarn blends the kept and
    the interpolated frequency (``rope_frequencies``)."""
    orig = float(rope_scaling.get("original_max_position_embeddings", 4096))

    def corr(rotations: float) -> float:
        return (head_dim * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = math.floor(corr(float(rope_scaling.get("beta_fast", 32))))
    high = math.ceil(corr(float(rope_scaling.get("beta_slow", 1))))
    return max(low, 0), min(high, head_dim - 1)


def yarn_attention_factor(rope_scaling: Optional[dict]) -> float:
    """m = 0.1 mscale_all_dim ln(factor) + 1: DeepSeek's yarn multiplies
    the softmax scale by m squared (1.0 without yarn). The factor the
    scheme also puts on cos and sin is mscale's m over mscale_all_dim's,
    1 where the two are equal, which is all that is served
    (configs.from_hf_config refuses the rest)."""
    if not rope_scaling or rope_scaling.get(
            "rope_type", rope_scaling.get("type")) != "yarn":
        return 1.0
    return _yarn_m(float(rope_scaling["factor"]),
                   float(rope_scaling.get("mscale_all_dim", 0.0)))


def _yarn_m(factor: float, mscale: float) -> float:
    """yarn's magnitude correction 0.1 mscale ln(factor) + 1 (1.0 where
    nothing is stretched)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_cos_sin_factor(rope_scaling: Optional[dict]) -> float:
    """The factor yarn puts on cosine and sine (1.0 without yarn): the
    published ``attention_factor``; without one, mscale's m over
    mscale_all_dim's where the config gives both (DeepSeek: 1 where they
    are equal), else 0.1 ln(factor) + 1. On a rotated query AND key it is
    the same as its square on the softmax scale, which is how
    models/decoder.py serves it: cached keys stay unscaled."""
    if not rope_scaling or rope_scaling.get(
            "rope_type", rope_scaling.get("type")) != "yarn":
        return 1.0
    if rope_scaling.get("attention_factor") is not None:
        return float(rope_scaling["attention_factor"])
    factor = float(rope_scaling["factor"])
    if rope_scaling.get("mscale") and rope_scaling.get("mscale_all_dim"):
        return (_yarn_m(factor, float(rope_scaling["mscale"]))
                / _yarn_m(factor, float(rope_scaling["mscale_all_dim"])))
    return _yarn_m(factor, 1.0)


def apply_rope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotate q and k.

    q: [..., T, num_heads, head_dim]
    k: [..., T, num_kv_heads, head_dim]
    positions: [..., T] int32
    inv_freq: [head_dim // 2] float32
    """
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]

    def rot(x: jnp.ndarray) -> jnp.ndarray:
        half = x.shape[-1] // 2
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


def apply_mrope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    pos3: jnp.ndarray,
    inv_freq: jnp.ndarray,
    mrope_section: tuple,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Interleaved multimodal RoPE (Qwen3-VL text decoder).

    pos3 [..., 3, T]: (temporal, height, width) position per token — all
    three equal for text tokens (then this reduces EXACTLY to apply_rope),
    spatially varying for image soft tokens. The per-axis frequency
    channels interleave as [T,H,W,T,H,W,...] up to 3*section[i] then fall
    back to the temporal axis — matching the public Qwen3-VL scheme.
    """
    angles3 = pos3[..., :, :, None].astype(jnp.float32) * inv_freq
    # [..., 3, T, hd/2] -> interleaved combined [..., T, hd/2]. The
    # channel->axis assignment is STATIC, so plain where-selects fold into
    # the surrounding fusion (no gather).
    half = inv_freq.shape[-1]
    axis_sel = np.zeros((half,), np.int32)           # default: temporal
    for dim, offset in ((1, 1), (2, 2)):             # H, W
        idx = np.arange(offset, 3 * mrope_section[dim], 3)
        axis_sel[idx[idx < half]] = dim
    angles = angles3[..., 0, :, :]
    for dim in (1, 2):
        angles = jnp.where(jnp.asarray(axis_sel == dim),
                           angles3[..., dim, :, :], angles)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]

    def rot(x: jnp.ndarray) -> jnp.ndarray:
        half_d = x.shape[-1] // 2
        x1 = x[..., :half_d].astype(jnp.float32)
        x2 = x[..., half_d:].astype(jnp.float32)
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)
