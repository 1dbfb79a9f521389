"""The plain reference of a sparse-expert decoder: ``forward.py``'s
pre-norm rotary grouped-query attention, then a mixture of experts in
place of the one feed-forward network, in float32 ``jax.numpy`` at
``highest`` matmul precision: no kernels, no cache, no batching, no
dispatch buffers.

The feed-forward layer, from Mixtral's published description: a linear
router scores ``num_local_experts`` experts, softmax over all of them; a
token goes to its ``num_experts_per_tok`` best, weighted by their scores
renormalised to sum to one; each expert is a SwiGLU network of width
``intermediate_size``. The experts are a plain loop, each over every
token, with weight zero where a token is not routed to it.

A configuration's file names this module under ``"reference"``. Weights
come in the layout the program's seeded generators emit (layer-stacked;
experts stacked behind the layer axis; int8 matrices carry a scale and are
dequantized here, one layer at a time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.forward import _dequant, _layer, _rms_norm

_ATTENTION = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm")


def _attention(x, lp, **kw):
    """x + attention(x): ``forward.py``'s block with a feed-forward
    network of width zero, which adds nothing to the residual stream, so
    that the attention is that file's own and not a copy of it."""
    d = x.shape[-1]
    none = jnp.zeros((d, 0), jnp.float32)
    return _layer(x, dict({k: lp[k] for k in _ATTENTION}, w_gate=none,
                          w_up=none, w_down=none.T), **kw)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _experts(x, lp, *, top_k, eps):
    h = _rms_norm(x, _dequant(lp["mlp_norm"]), eps)
    scores = jax.nn.softmax(h @ _dequant(lp["router"]), axis=-1)   # [T, E]
    best, chosen = jax.lax.top_k(scores, top_k)
    best = best / jnp.sum(best, axis=-1, keepdims=True)
    n_experts = scores.shape[-1]
    # weight[t, e]: the renormalised score where t is routed to e, else 0
    weight = jnp.sum(jax.nn.one_hot(chosen, n_experts) * best[..., None], 1)
    w_gate, w_up, w_down = (_dequant(lp[k])
                            for k in ("w_gate", "w_up", "w_down"))
    for e in range(n_experts):
        y = (jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e]
        x = x + weight[:, e:e + 1] * y
    return x


def logits_at(cfg: dict, params: dict, tokens, positions):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end)."""
    if cfg.get("hidden_act", "silu") != "silu":
        raise NotImplementedError("the reference knows SwiGLU with SiLU")
    experts = int(cfg["num_local_experts"])
    eps = float(cfg["rms_norm_eps"])
    kw = dict(n_heads=cfg["num_attention_heads"],
              n_kv=cfg["num_key_value_heads"], eps=eps,
              theta=float(cfg["rope_theta"]),
              window=int(cfg.get("sliding_window") or 0))
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            if lp["router"].shape[-1] != experts:
                raise ValueError(f"{lp['router'].shape[-1]} experts in the "
                                 f"weights, {experts} in the configuration")
            x = _attention(x, lp, **kw)
            x = _experts(x, lp, top_k=int(cfg["num_experts_per_tok"]),
                         eps=eps)
        x = _rms_norm(x[jnp.asarray(positions)],
                      params["final_norm"].astype(jnp.float32), eps)
        head = (params["embed"].astype(jnp.float32).T
                if cfg.get("tie_word_embeddings")
                else params["lm_head"].astype(jnp.float32))
        return x @ head
