"""The plain reference of the Mellum 2 block (JetBrains/Mellum2-12B-A2.5B):
pre-norm residual layers of grouped-query attention and a mixture of
experts, where the attention of layer ``i`` is one of two kinds, named in
the published ``layer_types``, in float32 ``jax.numpy`` at ``highest``
matmul precision: no kernels, no cache, no batching.

  h = x + W_o Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

``sliding_attention``: query t sees key j iff t - sliding_window < j <= t;
queries and keys rotated over the whole head by ``rope_parameters.
sliding_attention.rope_theta``, unscaled. This IS ``forward.py``'s dense
block (taken through ``moe.py``, with no feed-forward network).
``full_attention``: causal, no window; rotated with YaRN as
``rope_parameters.full_attention`` states it: with extrap_i = theta^(-2i/d),
interp_i = extrap_i / factor, ramp_i = clip((i - low) / (high - low), 0, 1)
and (low, high) the floor and ceiling of the pair indices that turn
beta_fast and beta_slow times in ``original_max_position_embeddings``
positions, inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i), and cosine
and sine are multiplied by ``attention_factor``.
The network of every layer (``mlp_layer_types`` all ``sparse``) is
``moe.py``'s: softmax over ``num_experts`` scores, the ``num_experts_per_tok``
best renormalised to sum one, SwiGLU experts of ``moe_intermediate_size``;
no shared expert, no selection bias (``intermediate_size`` is used by none).
No per-head norm on queries and keys (the configuration's ``assumed``).

Weights come in the layout the program's seeded generator emits: a tuple
of layer-stacked runs, one for each run of consecutive layers of one kind.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.forward import _dequant, _rms_norm
from reference.moe import _attention as _window_attention
from reference.moe import _experts


def yarn_inv_freq(dim: int, rope: dict):
    """[dim // 2] float32 rotation frequencies of a ``rope_type: yarn``
    section of ``rope_parameters``."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def pair_that_turns(rotations: float) -> float:
        return (dim * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(pair_that_turns(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_that_turns(float(rope["beta_slow"]))), dim - 1)
    extrap = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extrap / factor * ramp + extrap * (1.0 - ramp)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "factor"))
def _full_attention(x, lp, inv_freq, *, n_heads, n_kv, eps, factor):
    """x + W_o Attn(RMSNorm(x)): causal, no window, cosine and sine of the
    given frequencies times ``factor``."""
    T = x.shape[0]
    pos = jnp.arange(T)
    ang = pos[:, None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]

    def rot(a):
        a1, a2 = a[..., :a.shape[-1] // 2], a[..., a.shape[-1] // 2:]
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)

    h = _rms_norm(x, _dequant(lp["attn_norm"]), eps)
    q = rot(jnp.einsum("td,dhk->thk", h, _dequant(lp["wq"])))
    k = rot(jnp.einsum("td,dhk->thk", h, _dequant(lp["wk"])))
    v = jnp.einsum("td,dhk->thk", h, _dequant(lp["wv"]))
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) * (q.shape[-1] ** -0.5)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    a = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.einsum("thk,hkd->td", a, _dequant(lp["wo"]))


def layers_of(cfg: dict, params: dict):
    """Each layer's (published kind, parameters), cut out of the stacked
    run of consecutive layers of its kind that holds it."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(kinds))} over "
                         f"{len(kinds)} of {cfg['num_hidden_layers']} layers")
    run, at = -1, 0
    for i, kind in enumerate(kinds):
        if i == 0 or kind != kinds[i - 1]:
            run, at = run + 1, 0
        lp = jax.tree_util.tree_map(lambda a: a[at], params["layers"][run])
        at += 1
        yield kind, lp
    if run + 1 != len(params["layers"]):
        raise ValueError(f"{len(params['layers'])} runs in the weights, "
                         f"{run + 1} in the configuration")


def _hidden(cfg: dict, params: dict, tokens, before_experts=None):
    """The residual stream [T, D] after the last layer.
    ``before_experts(i, g, lp)`` is shown each layer's normed input to its
    router (``router_margins``)."""
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get("attention_bias") \
            or set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise NotImplementedError(
            "the reference knows the Mellum block: SiLU, no biases, every "
            "network sparse")
    eps = float(cfg["rms_norm_eps"])
    heads = dict(n_heads=cfg["num_attention_heads"],
                 n_kv=cfg["num_key_value_heads"], eps=eps)
    local = cfg["rope_parameters"]["sliding_attention"]
    full = cfg["rope_parameters"]["full_attention"]
    if local["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise NotImplementedError("plain window layers, yarn full layers")
    experts, top_k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    inv_freq = yarn_inv_freq(int(cfg["head_dim"]), full)
    x = params["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
    for i, (kind, lp) in enumerate(layers_of(cfg, params)):
        if lp["wq"].shape[-1] != cfg["head_dim"] \
                or lp["router"].shape[-1] != experts \
                or lp["w_gate"].shape[-1] != cfg["moe_intermediate_size"]:
            raise ValueError("the weights' head, router or expert width "
                             "is not the configuration's")
        if kind == "sliding_attention":
            x = _window_attention(
                x, lp, theta=float(local["rope_theta"]),
                window=int(cfg["sliding_window"]), **heads)
        else:
            x = _full_attention(
                x, lp, inv_freq, factor=float(full["attention_factor"]),
                **heads)
        if before_experts is not None:
            before_experts(i, _rms_norm(x, _dequant(lp["mlp_norm"]), eps), lp)
        x = _experts(x, lp, top_k=top_k, eps=eps)
    return x


def router_margins(cfg: dict, params: dict, tokens, position: int) -> list:
    """At ``position``, for each layer: the gap between the last softmax
    score the router chooses and the first it does not. Where it is within
    a lower precision's rounding of the scores, that precision may send
    the token to another expert: a flip."""
    k = int(cfg["num_experts_per_tok"])
    gaps = []

    def note(_i, g, lp):
        s = jnp.sort(jax.nn.softmax(g[position] @ _dequant(lp["router"])))
        gaps.append(float(s[-k] - s[-k - 1]))

    with jax.default_matmul_precision("highest"):
        _hidden(cfg, params, tokens, note)
    return gaps


def logits_at(cfg: dict, params: dict, tokens, positions):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(_hidden(cfg, params, tokens)[jnp.asarray(positions)],
                      params["final_norm"].astype(jnp.float32),
                      float(cfg["rms_norm_eps"]))
        head = (params["embed"].astype(jnp.float32).T
                if cfg.get("tie_word_embeddings")
                else params["lm_head"].astype(jnp.float32))
        return x @ head
