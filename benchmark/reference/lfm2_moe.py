"""The plain reference of the LFM2-MoE block (``model_type: lfm2_moe``), in
float32 ``jax.numpy`` at ``highest`` matmul precision: no kernels, no cache,
no state carried between calls, no batching, no sorting of tokens.

From the published ``config.json`` keys and the family's description. A
layer ``l`` with input ``x`` (RMSNorm is ``x * w / rms(x)``, ``norm_eps``):

    u = RMSNorm_op(x);  h = x + Op_l(u);  g = RMSNorm_ffn(h);  y = h + FF_l(g)

- ``layer_types[l] == "conv"``, a gated short convolution:
  ``[B, C, X] = split3(u W_in)``; ``z_t = B_t * X_t``;
  ``c_t = sum_j w[:, j] * z_{t-(L-1)+j}`` with ``L = conv_L_cache`` and ``z``
  zero before the first token (a causal depthwise convolution, one filter
  a channel, cross-correlation as ``torch.nn.Conv1d`` computes it);
  ``Op = (C_t * c_t) W_out``. No bias (``conv_bias`` false), no activation
  besides the two gates.
- ``layer_types[l] == "full_attention"``: grouped-query attention, a
  learned RMSNorm over each head of ``q`` and of ``k`` BEFORE rotate-half
  rope (``rope_parameters.rope_theta``, no scaling), causal softmax at
  scale ``head_dim ** -0.5`` over the whole context.
- ``l < num_dense_layers``: ``FF = (silu(g W_1) * (g W_3)) W_2`` of width
  ``intermediate_size``.
- otherwise ``num_experts`` routed experts of width
  ``moe_intermediate_size``, no shared expert: ``s = sigmoid(g W_r)``; the
  ``num_experts_per_tok`` best of ``s + b`` are chosen (``use_expert_bias``:
  ``b`` selects, it never weighs); ``a = s[sel] / (sum s[sel] + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``;
  ``FF = sum_i a_i expert_i(g)``. The experts are a plain loop, each over
  every token, with weight zero where a token is not routed to it.
- one RMSNorm after the last layer, then logits against the input
  embedding (tied).

Departures from the published file, each ``assumed`` in the configuration's
own file: the 1e-6 and the tied embedding are the family's implementation's
and not the file's; the tap order (``w[:, L-1]`` multiplies the current
position) is ``torch.nn.Conv1d``'s on a left-padded sequence.

A configuration's file names this module under ``"reference"``. Weights come
in the layout the program's seeded generator emits for a stack of several
kinds of layer: ``params["layers"]`` is a tuple with one layer-stacked dict
for each run of consecutive layers of one kind (operator and feed-forward),
widened to float32 here one layer, and one expert, at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

RENORM_EPS = 1e-6


def _f32(w):
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


@functools.partial(jax.jit, static_argnames=("eps", "taps"))
def _conv_operator(x, lp, *, eps, taps):
    """x + Op(RMSNorm(x)) for a gated short convolution. x: [T, D]."""
    T = x.shape[0]
    u = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    b, c, xg = jnp.split(u @ _f32(lp["conv_in"]), 3, axis=-1)
    z = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), jnp.float32),
                         b * xg])                     # zero before token 0
    w = _f32(lp["conv_w"])                            # [D, taps]
    conv = sum(w[:, j] * z[j:j + T] for j in range(taps))
    return x + (c * conv) @ _f32(lp["conv_out"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "theta"))
def _attention_operator(x, lp, *, n_heads, n_kv, eps, theta):
    """x + Op(RMSNorm(x)) for grouped-query attention with q/k norms."""
    T = x.shape[0]
    pos = jnp.arange(T)
    u = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", u, _f32(lp["wq"]))
    k = jnp.einsum("td,dhk->thk", u, _f32(lp["wk"]))
    v = jnp.einsum("td,dhk->thk", u, _f32(lp["wv"]))
    q = _rope(_rms_norm(q, _f32(lp["q_norm"]), eps), pos, theta)
    k = _rope(_rms_norm(k, _f32(lp["k_norm"]), eps), pos, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) * (q.shape[-1] ** -0.5)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    a = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    return x + jnp.einsum("thk,hkd->td", a, _f32(lp["wo"]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_network(x, lp, *, eps):
    g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    return x + (jax.nn.silu(g @ _f32(lp["w_gate"]))
                * (g @ _f32(lp["w_up"]))) @ _f32(lp["w_down"])


def route(g, router, bias, *, top_k, use_bias, renorm, scale):
    """weight[t, e]: the weight of expert e in token t's sum, zero where t
    is not routed to e. g: [T, D] (normed)."""
    s = jax.nn.sigmoid(g @ router)                              # [T, E]
    _, chosen = jax.lax.top_k(s + bias if use_bias else s, top_k)
    a = jnp.take_along_axis(s, chosen, axis=-1)                 # unbiased
    if renorm:
        a = a / (jnp.sum(a, axis=-1, keepdims=True) + RENORM_EPS)
    a = a * scale
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]) * a[..., None], 1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "use_bias",
                                             "renorm", "scale"))
def _experts(x, lp, *, eps, top_k, use_bias, renorm, scale):
    g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    weight = route(g, _f32(lp["router"]), _f32(lp["router_bias"]),
                   top_k=top_k, use_bias=use_bias, renorm=renorm, scale=scale)

    def one(e, acc):
        gate, up, down = (_f32(jax.lax.dynamic_index_in_dim(lp[k], e, 0,
                                                            False))
                          for k in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(g @ gate) * (g @ up)) @ down
        return acc + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) * y

    return jax.lax.fori_loop(0, weight.shape[-1], one, x)


def layer_kinds(cfg: dict) -> list:
    """(operator, feed-forward) of every layer, from the published keys."""
    dense = int(cfg.get("num_dense_layers", 0))
    return [("conv" if t == "conv" else "attn",
             "dense" if i < dense else "moe")
            for i, t in enumerate(cfg["layer_types"])]


def layers_of(cfg: dict, params: dict):
    """Each layer's (kind, parameters), cut out of the stacked run of
    consecutive layers of its kind that holds it."""
    kinds = layer_kinds(cfg)
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{cfg['num_hidden_layers']} layers")
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
    """The residual stream after the last layer; ``before_experts``
    (layer index, its normed feed-forward input g, its parameters) is
    called at every expert layer on the way."""
    eps = float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    experts = int(cfg["num_experts"])
    x = _f32(params["embed"])[jnp.asarray(tokens)]
    for i, ((op, ff), lp) in enumerate(layers_of(cfg, params)):
        if op == "conv":
            x = _conv_operator(x, lp, eps=eps, taps=int(cfg["conv_L_cache"]))
        else:
            x = _attention_operator(
                x, lp, n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"], eps=eps, theta=theta)
        if ff == "dense":
            x = _dense_network(x, lp, eps=eps)
            continue
        if lp["router"].shape[-1] != experts:
            raise ValueError(
                f"{lp['router'].shape[-1]} experts in the weights, "
                f"{experts} in the configuration")
        if before_experts is not None:
            before_experts(i, _rms_norm(x, _f32(lp["mlp_norm"]), eps), lp)
        x = _experts(
            x, lp, eps=eps, top_k=int(cfg["num_experts_per_tok"]),
            use_bias=bool(cfg.get("use_expert_bias")),
            renorm=bool(cfg.get("norm_topk_prob")),
            scale=float(cfg.get("routed_scaling_factor", 1.0)))
    return x


def router_margins(cfg: dict, params: dict, tokens, position: int) -> list:
    """At ``position``, for each expert layer: the gap between the last
    score the router chooses and the first it does not (selection scores,
    bias included). Where it is within a lower precision's rounding of the
    scores, that precision may send the token to another expert: a flip."""
    k = int(cfg["num_experts_per_tok"])
    gaps = []

    def note(_i, g, lp):
        s = jax.nn.sigmoid(g[position] @ _f32(lp["router"]))
        if cfg.get("use_expert_bias"):
            s = s + _f32(lp["router_bias"])
        s = jnp.sort(s)
        gaps.append(float(s[-k] - s[-k - 1]))

    with jax.default_matmul_precision("highest"):
        _hidden(cfg, params, tokens, note)
    return gaps


def logits_at(cfg: dict, params: dict, tokens, positions):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end)."""
    if cfg.get("conv_bias"):
        raise NotImplementedError("the reference knows conv_bias false")
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(_hidden(cfg, params, tokens)[jnp.asarray(positions)],
                      _f32(params["final_norm"]), float(cfg["norm_eps"]))
        return x @ _f32(params["embed"]).T
