"""The plain reference of the DeepSeek-V3 block (``model_type: deepseek_v3``),
in float32 ``jax.numpy`` at ``highest`` matmul precision: the EXPANDED form of
latent attention only, no kernels, no cache, no batching, no sorting of
tokens, and nothing of the absorbed form the program decodes with.

From the published ``config.json`` keys and the paper's equations. A layer
with input ``x`` [T, hidden] (RMSNorm is ``x * w / rms(x)``, ``rms_norm_eps``):

    h = x + MLA(RMSNorm_1(x));   y = h + FFN(RMSNorm_2(h))

- **MLA.** ``cq = RMSNorm_q(u W_qa)`` [q_lora_rank]; each of the
  ``num_attention_heads`` heads takes ``[q_n | q_r] = cq W_qb`` (``qk_nope_
  head_dim`` un-roped and ``qk_rope_head_dim`` roped dimensions).
  ``[ckv | kr] = u W_kva``; ``c = RMSNorm_kv(ckv)`` [kv_lora_rank];
  ``k_r = rope(kr)``: ONE roped key a token, shared by all heads. Per head
  ``k_n = c W_UK``, ``v = c W_UV`` (``v_head_dim``);
  ``score = (q_n . k_n + rope(q_r) . k_r) s``, causal softmax,
  ``o = softmax . v``; the heads' outputs are concatenated and multiplied by
  ``W_o``. ``s = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 m^2`` with
  ``m = 0.1 mscale_all_dim ln(factor) + 1`` (YaRN; 1.36889 as published).
- **YaRN** over the roped dimensions ``d``: ``theta_i = rope_theta^(-2i/d)``;
  ``corr(n) = d ln(orig / (2 pi n)) / (2 ln rope_theta)``;
  ``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` (10 and
  23 as published); ``r_i = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq_i = theta_i (1 - r_i) + theta_i / factor r_i``. The factor the
  scheme puts on cos and sin is m(mscale) / m(mscale_all_dim) = 1 here.
- **FFN.** The first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``. The others: ``shared(g)`` (SwiGLU of
  ``n_shared_experts x moe_intermediate_size``) ``+ sum_{e in sel} w_e
  E_e(g)``. Routing, in float32: ``s = sigmoid(g W_r)`` over ALL the routed
  experts; ``p = s + b``; a group's score is the sum of its two largest
  ``p`` (``n_group`` groups of consecutive experts); the ``topk_group`` best
  groups are kept and the rest masked to -inf; ``sel`` = the
  ``num_experts_per_tok`` largest ``p`` left; ``w = s[sel] / (sum s[sel] +
  1e-20) x routed_scaling_factor`` (``norm_topk_prob``).
- **The share** (``share`` in the configuration's file): this chip holds
  experts ``[first_expert, first_expert + n_routed_experts)`` of the
  ``routed_experts`` the router scores. Routing and the weights' sum run over
  all of them; the layer's result is ``shared(g) + sum_{e in sel and held}
  w_e E_e(g)``, and that partial result goes on to the next layer. Nothing
  stands in for the other chips. ``vocab_size`` is the slice's.
- one RMSNorm after the last layer, then the output head (not tied).

Departures from the published code, each ``assumed`` in the configuration's
file: rope rotates the two halves of the roped dimensions (the published
code rotates interleaved pairs; with seeded weights the two differ by a fixed
permutation of columns of ``W_qb`` and ``W_kva``); groups that are not kept
are masked to -inf (the published code masks to 0: the same unless a kept
``p`` is negative); the multi-token-prediction module is not part of the
model's logits and is not built.

A configuration's file names this module under ``"reference"``. Weights come
in the layout the program's seeded generator emits: ``params["layers"]`` is a
tuple with one layer-stacked dict for each run of layers of one kind (the
dense run, then the expert run); ``w_qn`` [heads x nope, q_lora] and ``w_qr``
[heads x rope, q_lora] are ``W_qb``'s un-roped and roped outputs, output-
major; ``w_uk`` and ``w_uv`` [heads, kv_lora, .] are ``W_kvb``'s two halves;
the expert stacks hold the held experts only. Widened to float32 here one
layer, and one expert, at a time; attention runs a block of heads at a time,
so that the published widths fit the chip beside the weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference.forward import _rms_norm

RENORM_EPS = 1e-20
HEAD_BLOCK = 16


def _f32(w):
    return w.astype(jnp.float32)


def yarn_inv_freq(cfg: dict):
    """Inverse frequencies of the roped dimensions, [rope / 2] float32."""
    d, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    ys = cfg["rope_scaling"]
    if ys.get("type", ys.get("rope_type")) != "yarn":
        raise NotImplementedError("the reference knows yarn")
    orig = float(ys["original_max_position_embeddings"])

    def corr(n):
        return d * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(ys["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(ys["beta_slow"]))), d - 1)
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / d)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / float(ys["factor"]) * ramp


def softmax_scale(cfg: dict) -> float:
    ys = cfg["rope_scaling"]
    if float(ys.get("mscale", 1.0)) != float(ys.get("mscale_all_dim", 0.0)):
        raise NotImplementedError("mscale and mscale_all_dim differ")
    m = 0.1 * float(ys["mscale_all_dim"]) * math.log(float(ys["factor"])) + 1
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, positions, inv_freq):
    """Rotate-half rotary embedding. x: [T, H, d]."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv_freq        # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "heads", "lat"))
def _project(x, lp, inv_freq, *, eps, heads, lat):
    """(q_n [T, H, nope], rope(q_r) [T, H, rope], c [T, lat], k_r [T, rope])
    of the normed input."""
    T = x.shape[0]
    pos = jnp.arange(T)
    u = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    cq = _rms_norm(u @ _f32(lp["w_qa"]), _f32(lp["q_a_norm"]), eps)
    q_n = (cq @ _f32(lp["w_qn"]).T).reshape(T, heads, -1)
    q_r = (cq @ _f32(lp["w_qr"]).T).reshape(T, heads, -1)
    ckv = u @ _f32(lp["w_kva"])
    c = _rms_norm(ckv[:, :lat], _f32(lp["kv_a_norm"]), eps)
    k_r = _rope(ckv[:, None, lat:], pos, inv_freq)[:, 0]
    return q_n, _rope(q_r, pos, inv_freq), c, k_r


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_heads(q_n, q_r, c, k_r, w_uk, w_uv, wo, *, scale):
    """A block of heads, expanded: their part of W_o's product, [T, hidden].
    q_n [T, h, nope]; q_r [T, h, rope]; c [T, lat]; k_r [T, rope];
    w_uk/w_uv [h, lat, .]; wo [h, v, hidden]."""
    T = c.shape[0]
    k_n = jnp.einsum("sr,hrk->shk", c, _f32(w_uk))
    v = jnp.einsum("sr,hrk->shk", c, _f32(w_uv))
    s = (jnp.einsum("thk,shk->hts", q_n, k_n)
         + jnp.einsum("thk,sk->hts", q_r, k_r)) * scale
    pos = jnp.arange(T)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("thk,hkd->td", o, _f32(wo))


def _mla(x, lp, inv_freq, *, eps, heads, lat, scale):
    q_n, q_r, c, k_r = _project(x, lp, inv_freq, eps=eps, heads=heads,
                                lat=lat)
    for h0 in range(0, heads, HEAD_BLOCK):
        hb = slice(h0, h0 + HEAD_BLOCK)
        x = x + _attend_heads(q_n[:, hb], q_r[:, hb], c, k_r, lp["w_uk"][hb],
                              lp["w_uv"][hb], lp["wo"][hb], scale=scale)
    return x


def _swiglu(g, gate, up, down):
    return (jax.nn.silu(g @ _f32(gate)) * (g @ _f32(up))) @ _f32(down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_network(x, lp, *, eps):
    g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    return x + _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])


def route(g, router, bias, *, top_k, n_group, topk_group, renorm, scale):
    """weight[t, e] over ALL routed experts: the weight of expert e in token
    t's sum, zero where t is not routed to e. g: [T, D] (normed)."""
    s = jax.nn.sigmoid(g @ router)                              # [T, E]
    p = s + bias
    T, E = p.shape
    per_group = p.reshape(T, n_group, E // n_group)
    two_best = jnp.sort(per_group, axis=-1)[..., -2:].sum(-1)   # [T, groups]
    _, kept = jax.lax.top_k(two_best, topk_group)
    keep = jnp.sum(jax.nn.one_hot(kept, n_group), axis=1) > 0   # [T, groups]
    p = jnp.where(jnp.repeat(keep, E // n_group, axis=1), p, -jnp.inf)
    _, chosen = jax.lax.top_k(p, top_k)
    a = jnp.take_along_axis(s, chosen, axis=-1)                 # unbiased
    if renorm:
        a = a / (jnp.sum(a, axis=-1, keepdims=True) + RENORM_EPS)
    return jnp.sum(jax.nn.one_hot(chosen, E) * (a * scale)[..., None], 1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "n_group", "topk_group", "renorm", "scale", "first"))
def _experts(x, lp, *, eps, top_k, n_group, topk_group, renorm, scale, first):
    """x + shared(g) + the held experts' part of the routed sum."""
    g = _rms_norm(x, _f32(lp["mlp_norm"]), eps)
    weight = route(g, _f32(lp["router"]), _f32(lp["router_bias"]),
                   top_k=top_k, n_group=n_group, topk_group=topk_group,
                   renorm=renorm, scale=scale)
    out = x
    if "ws_gate" in lp:
        out = out + _swiglu(g, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def one(e, acc):
        gate, up, down = (jax.lax.dynamic_index_in_dim(lp[k], e, 0, False)
                          for k in ("w_gate", "w_up", "w_down"))
        return acc + (jax.lax.dynamic_slice_in_dim(weight, first + e, 1, 1)
                      * _swiglu(g, gate, up, down))

    return jax.lax.fori_loop(0, lp["w_gate"].shape[0], one, out)


def share_of(cfg: dict) -> tuple:
    """(first expert held, experts the router scores)."""
    share = cfg.get("share") or {}
    return (int(share.get("first_expert", 0)),
            int(share.get("routed_experts", cfg["n_routed_experts"])))


def layers_of(cfg: dict, params: dict):
    """Each layer's (feed-forward kind, parameters), cut out of the stacked
    run that holds it: the dense run, then the expert run."""
    dense = int(cfg.get("first_k_dense_replace", 0))
    runs = params["layers"]
    want = (1 if dense else 0) + (1 if cfg["num_hidden_layers"] > dense else 0)
    if len(runs) != want:
        raise ValueError(f"{len(runs)} runs in the weights, {want} in the "
                         f"configuration")
    for i in range(cfg["num_hidden_layers"]):
        run, at = (0, i) if i < dense else (want - 1, i - dense)
        yield ("dense" if i < dense else "moe",
               jax.tree_util.tree_map(lambda a: a[at], runs[run]))


def _hidden(cfg: dict, params: dict, tokens, before_experts=None):
    eps = float(cfg["rms_norm_eps"])
    first, routed = share_of(cfg)
    inv_freq = yarn_inv_freq(cfg)
    attn = dict(eps=eps, heads=int(cfg["num_attention_heads"]),
                lat=int(cfg["kv_lora_rank"]), scale=softmax_scale(cfg))
    x = _f32(params["embed"])[jnp.asarray(tokens)]
    for i, (ff, lp) in enumerate(layers_of(cfg, params)):
        x = _mla(x, lp, inv_freq, **attn)
        if ff == "dense":
            x = _dense_network(x, lp, eps=eps)
            continue
        if lp["router"].shape[-1] != routed \
                or lp["w_gate"].shape[0] != cfg["n_routed_experts"]:
            raise ValueError(
                f"{lp['w_gate'].shape[0]} of {lp['router'].shape[-1]} "
                f"experts in the weights, {cfg['n_routed_experts']} of "
                f"{routed} in the configuration")
        if before_experts is not None:
            before_experts(i, _rms_norm(x, _f32(lp["mlp_norm"]), eps), lp)
        x = _experts(
            x, lp, eps=eps, top_k=int(cfg["num_experts_per_tok"]),
            n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
            renorm=bool(cfg.get("norm_topk_prob", True)),
            scale=float(cfg.get("routed_scaling_factor", 1.0)), first=first)
    return x


def router_margins(cfg: dict, params: dict, tokens, position: int) -> list:
    """At ``position``, for each expert layer: the gap between the last
    selection score the router chooses and the first it does not, among the
    experts of the kept groups. Where it is within a lower precision's
    rounding of the scores, that precision may route the token otherwise."""
    k = int(cfg["num_experts_per_tok"])
    n_group, topk = int(cfg["n_group"]), int(cfg["topk_group"])
    gaps = []

    def note(_i, g, lp):
        p = (jax.nn.sigmoid(g[position] @ _f32(lp["router"]))
             + _f32(lp["router_bias"]))
        groups = p.reshape(n_group, -1)
        best = jnp.argsort(-jnp.sort(groups, axis=-1)[:, -2:].sum(-1))[:topk]
        left = jnp.sort(groups[best].reshape(-1))
        gaps.append(float(left[-k] - left[-k - 1]))

    with jax.default_matmul_precision("highest"):
        _hidden(cfg, params, tokens, note)
    return gaps


def logits_at(cfg: dict, params: dict, tokens, positions):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end)."""
    if cfg.get("hidden_act", "silu") != "silu" \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid" \
            or cfg.get("tie_word_embeddings"):
        raise NotImplementedError(
            "the reference knows SiLU, sigmoid scores and an untied head")
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(_hidden(cfg, params, tokens)[jnp.asarray(positions)],
                      _f32(params["final_norm"]), float(cfg["rms_norm_eps"]))
        return x @ _f32(params["lm_head"])
