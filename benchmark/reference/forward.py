"""The plain reference: a decoder-only transformer's forward pass in
float32 ``jax.numpy``, written from the published description of the
Mistral / Phi-3 block (pre-norm residual blocks of RMSNorm, rotary
grouped-query attention with a causal sliding window, SwiGLU) — no
kernels, no cache, no batching, matmuls at ``highest`` precision.

It reads the architecture from a configuration file's published keys, not
from the program's registry, and takes weights in the layout the program's
seeded generator emits (layer-stacked; int8 matrices carry a scale and are
dequantized here, one layer at a time so that a 7B model's float32 copy
never exists as a whole).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _dequant(w):
    """int8 data x float32 scale -> float32; plain arrays pass through."""
    if hasattr(w, "data") and hasattr(w, "scale"):
        return w.data.astype(jnp.float32) * w.scale.astype(jnp.float32)
    return w.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """HF "rotate_half" rotary embedding. x: [T, H, hd]."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv        # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "theta", "window"))
def _layer(x, lp, *, n_heads, n_kv, eps, theta, window):
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms_norm(x, _dequant(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", h, _dequant(lp["wq"]))
    k = jnp.einsum("td,dhk->thk", h, _dequant(lp["wk"]))
    v = jnp.einsum("td,dhk->thk", h, _dequant(lp["wv"]))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) * (q.shape[-1] ** -0.5)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask = mask & (pos[None, :] > pos[:, None] - window)
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("thk,hkd->td", a, _dequant(lp["wo"]))
    h = _rms_norm(x, _dequant(lp["mlp_norm"]), eps)
    gate = jax.nn.silu(h @ _dequant(lp["w_gate"]))
    return x + (gate * (h @ _dequant(lp["w_up"]))) @ _dequant(lp["w_down"])


def logits_at(cfg: dict, params: dict, tokens, positions):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end)."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("the reference knows SwiGLU with SiLU")
    n_layers = cfg["num_hidden_layers"]
    kw = dict(n_heads=cfg["num_attention_heads"],
              n_kv=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]),
              theta=float(cfg["rope_theta"]),
              window=int(cfg.get("sliding_window") or 0))
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        for i in range(n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x = _layer(x, lp, **kw)
        x = _rms_norm(x[jnp.asarray(positions)],
                      params["final_norm"].astype(jnp.float32), kw["eps"])
        head = (params["embed"].astype(jnp.float32).T
                if cfg.get("tie_word_embeddings")
                else params["lm_head"].astype(jnp.float32))
        return x @ head
