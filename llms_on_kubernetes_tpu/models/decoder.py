"""Unified decoder-only transformer: the serving engine's model core.

One functional implementation drives every family in ``configs.REGISTRY``
(Llama 2/3/3.1, TinyLlama, Mistral, Mixtral-MoE, Phi-3, Qwen2/3, Gemma-2/3) —
the differences (GQA ratio, RoPE theta/scaling, qk-norm, post-norms, softcaps,
MoE) are config-driven, mirroring how the reference stack served arbitrary
``huggingfaceId``s through one vLLM engine (reference
vllm-models/helm-chart/templates/model-deployments.yaml:26-39).

TPU-first choices:
- Parameters are plain pytrees with layers STACKED on a leading axis and the
  layer loop is ``lax.scan`` — one layer's HLO compiled once, so a 32-layer
  8B and an 80-layer 70B compile in the same time as a 2-layer test model.
  A stack of more than one KIND of layer (``cfg.layer_runs``: a gated short
  convolution or attention, a dense network or experts) is a tuple of such
  stacks, one for each run of one kind, scanned one after the other; a
  stack of one kind is one run and ``params["layers"]`` is its dict.
- Head dims are explicit in weight shapes ([D, H, hd] not [D, H*hd]) so
  sharding rules can target the head axis directly (mesh axis "model").
- All shapes static; prefill is bucketed by the caller; decode is a fixed
  slot batch. No data-dependent Python control flow under jit.
- KV is written to the paged pool (engine/cache.py) inside each layer;
  decode attends via paged attention, prefill attends within its chunk.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from llms_on_kubernetes_tpu.configs import ModelConfig
from llms_on_kubernetes_tpu.ops.cp import dispatch_write_tokens as write_tokens
from llms_on_kubernetes_tpu.ops.attention import (
    conv_token_step, dispatch_chunk_attention, dispatch_conv_step,
    dispatch_paged_attention, dispatch_prefill_attention, dispatch_ssm_step,
    layer_kind, live_first, live_tiles_first, record_choice, softcap,
)
from llms_on_kubernetes_tpu.ops.lora import lora_qeinsum
from llms_on_kubernetes_tpu.ops.moe import moe_block
from llms_on_kubernetes_tpu.ops.norms import rms_norm
from llms_on_kubernetes_tpu.ops.quant import qeinsum
from llms_on_kubernetes_tpu.ops.rope import (
    apply_rope, rope_frequencies, yarn_attention_factor, yarn_cos_sin_factor,
)

Params = dict[str, Any]


def _lqe(eq: str, x: jnp.ndarray, lp: Params, name: str, idx):
    """``qeinsum`` of layer weight ``name`` plus, when the layer carries a
    LoRA stack for it AND a per-row adapter index is given, the batch's
    per-slot adapter deltas (ops/lora.py). Adapter-free engines never
    attach stacks, so every existing trace is unchanged."""
    return lora_qeinsum(eq, x, lp[name], lp.get("lora_" + name), idx)


def _act(cfg: ModelConfig):
    if cfg.hidden_act == "gelu_tanh":
        return functools.partial(jax.nn.gelu, approximate=True)
    return jax.nn.silu


def _unroll_layers() -> bool:
    """LLMK_UNROLL_LAYERS = auto | 1 | 0.

    auto (default): unroll on TPU, rolled scan elsewhere. Why unroll: a
    multi-GB KV pool riding a lax.scan (while-loop) carry pays a full
    boundary copy every call on TPU (measured ~12 ms/step at 8B scale) —
    XLA cannot alias a donated parameter into a while-loop working buffer.
    A fully unrolled layer chain keeps the pool in straight-line DUS
    updates, which ARE in-place. The price is larger HLO (slower first
    compile); CPU tests and tiny models keep the rolled scan."""
    impl = os.environ.get("LLMK_UNROLL_LAYERS", "auto")
    if impl == "auto":
        return jax.default_backend() == "tpu"
    return impl not in ("0", "false", "no")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype: Optional[str] = None) -> Params:
    """Random-init parameters (layer-stacked). Layout matches weights.py loading."""
    dt = jnp.dtype(dtype or cfg.dtype)
    if len(cfg.layer_runs) > 1 or cfg.is_mla:
        return _init_runs(cfg, key, dt)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.expert_width
    H, KV, hd, V = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size
    keys = iter(jax.random.split(key, 32))

    def init(*shape, scale=None):
        s = scale if scale is not None else 1.0 / np.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
        return (jax.random.normal(next(keys), shape, jnp.float32) * s).astype(dt)

    layers: Params = {
        "attn_norm": jnp.ones((L, D), dt) if cfg.norm_style == "llama" else jnp.zeros((L, D), dt),
        "wq": init(L, D, H, hd, scale=D ** -0.5),
        "wk": init(L, D, KV, hd, scale=D ** -0.5),
        "wv": init(L, D, KV, hd, scale=D ** -0.5),
        "wo": init(L, H, hd, D, scale=(H * hd) ** -0.5),
        "mlp_norm": jnp.ones((L, D), dt) if cfg.norm_style == "llama" else jnp.zeros((L, D), dt),
    }
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, H, hd), dt)
        layers["bk"] = jnp.zeros((L, KV, hd), dt)
        layers["bv"] = jnp.zeros((L, KV, hd), dt)
    if cfg.qk_norm:
        one = jnp.ones((L, hd), dt) if cfg.norm_style == "llama" else jnp.zeros((L, hd), dt)
        layers["q_norm"] = one
        layers["k_norm"] = one
    if cfg.post_norms:
        zero_or_one = jnp.ones((L, D), dt) if cfg.norm_style == "llama" else jnp.zeros((L, D), dt)
        layers["attn_post_norm"] = zero_or_one
        layers["mlp_post_norm"] = zero_or_one
    if cfg.is_moe:
        E = cfg.num_experts
        layers["router"] = init(L, D, E, scale=D ** -0.5)
        layers["w_gate"] = init(L, E, D, F, scale=D ** -0.5)
        layers["w_up"] = init(L, E, D, F, scale=D ** -0.5)
        layers["w_down"] = init(L, E, F, D, scale=F ** -0.5)
    else:
        layers["w_gate"] = init(L, D, F, scale=D ** -0.5)
        layers["w_up"] = init(L, D, F, scale=D ** -0.5)
        layers["w_down"] = init(L, F, D, scale=F ** -0.5)

    params: Params = {
        "embed": init(V, D, scale=1.0),
        "final_norm": jnp.ones((D,), dt) if cfg.norm_style == "llama" else jnp.zeros((D,), dt),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(D, V, scale=D ** -0.5)
    if cfg.vision is not None:
        from llms_on_kubernetes_tpu.models.vision import (
            init_qwen3vl_vision_params, init_vision_params,
        )

        if cfg.vision.family == "qwen3vl":
            params["vision"] = init_qwen3vl_vision_params(
                cfg.vision, next(keys), dtype=dt)
        else:
            params["vision"] = init_vision_params(
                cfg.vision, D, next(keys), dtype=dt)
    return params


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_stack(keys, shape, scale, dtype):
    """[len(keys), *shape] normal draws x scale in ``dtype``, one layer at
    a time: the float32 draw of ONE layer's tensor is the largest float32
    array there ever is (5.2 G parameters of bfloat16 fit a 16 GB chip;
    a float32 copy of one run's expert stacks would not)."""
    return jax.lax.map(
        lambda k: (jax.random.normal(k, shape, jnp.float32)
                   * scale).astype(dtype), keys)


def _init_runs(cfg: ModelConfig, key: jax.Array, dt) -> Params:
    """Random-init parameters of a stack of more than one kind of layer:
    ``params["layers"]`` is a tuple with one layer-stacked dict for each
    run of ``cfg.layer_runs``. A layer's operator is attention (wq, wk, wv,
    wo, q_norm, k_norm), a gated short convolution (conv_in [D, 3D]: the
    gates B and C and the input X, in that order; conv_w [D, taps], one
    filter a channel; conv_out [D, D]) or latent attention (w_qa [D,
    q_lora], q_a_norm, w_qn [H * nope, q_lora] and w_qr [H * rope,
    q_lora]: the published q_b matrix's un-roped and roped outputs, output-
    major as the checkpoint stores a linear layer; w_kva [D,
    kv_lora + rope], kv_a_norm, w_uk [H, kv_lora, nope] and w_uv [H,
    kv_lora, v]: the published kv_b matrix's two halves, head-major. Each
    is the array a product reads as it lies: a matrix whose columns are
    split after the product, or whose heads are 192 wide, is re-laid out
    by the compiler before every step. wo [H, v, D]) or a Mamba mixer
    (``_mamba``: in_proj [D, 2 Di], conv_w [taps, Di] and A_log [N, Di]
    with the channels on the lanes, conv_b, x_proj [Di, R + 2 N], dt_norm,
    b_norm, c_norm, dt_proj [R, Di], dt_bias, D, out_proj [Di, D]); its
    feed-forward is a dense SwiGLU network or routed experts (router,
    router_bias, w_gate/w_up/w_down stacked over the experts HELD here,
    ``cfg.num_held_experts`` of the router's ``num_experts``; ws_gate,
    ws_up, ws_down: the shared expert, where there is one). ``attn_norm``
    is the operator's norm whatever the operator. The selection bias is
    NOT zero, so that a test can tell selecting from weighing, and small
    beside the scores' own spread (0.01 against 0.2): a trained bias evens
    the experts' load out, and a random one as wide as the scores would
    pile the rows on a few."""
    if cfg.attention_bias or cfg.post_norms or cfg.vision is not None \
            or cfg.norm_style != "llama" or not (
                cfg.qk_norm or cfg.is_mla or cfg.num_mamba_layers
                or cfg.names_window_layers):
        raise NotImplementedError(
            f"{cfg.name}: a stack of several kinds of layer is built for "
            f"the LFM2 block (llama norms, q/k norms, no biases), the "
            f"DeepSeek block (latent attention), the Jamba block (Mamba "
            f"layers, attention without norms or positions) and the Mellum "
            f"block (window and full attention layers without q/k norms) "
            f"only")
    D, F, Fm = cfg.hidden_size, cfg.intermediate_size, cfg.expert_width
    H, KV, hd, V = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size
    E, held, taps = cfg.num_experts, cfg.num_held_experts, cfg.conv_L_cache
    Fs = cfg.n_shared_experts * Fm
    keys = iter(jax.random.split(key, 16 * len(cfg.layer_runs) + 4))

    def init(n, *shape, scale):
        return _normal_stack(jax.random.split(next(keys), n), shape,
                             float(scale), dt)

    runs = []
    for op, ff, _first, n in cfg.layer_runs:
        lp: Params = {"attn_norm": jnp.ones((n, D), dt),
                      "mlp_norm": jnp.ones((n, D), dt)}
        if op == "mla":
            ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
            nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
            lp.update(
                w_qa=init(n, D, ql, scale=D ** -0.5),
                q_a_norm=jnp.ones((n, ql), dt),
                w_qn=init(n, H * nope, ql, scale=ql ** -0.5),
                w_qr=init(n, H * (hd - nope), ql, scale=ql ** -0.5),
                w_kva=init(n, D, cfg.latent_width, scale=D ** -0.5),
                kv_a_norm=jnp.ones((n, kl), dt),
                w_uk=init(n, H, kl, nope, scale=kl ** -0.5),
                w_uv=init(n, H, kl, vd, scale=kl ** -0.5),
                wo=init(n, H, vd, D, scale=(H * vd) ** -0.5))
        elif op in ("attn", "swa"):
            lp.update(
                wq=init(n, D, H, hd, scale=D ** -0.5),
                wk=init(n, D, KV, hd, scale=D ** -0.5),
                wv=init(n, D, KV, hd, scale=D ** -0.5),
                wo=init(n, H, hd, D, scale=(H * hd) ** -0.5))
            if cfg.qk_norm:
                lp.update(q_norm=jnp.ones((n, hd), dt),
                          k_norm=jnp.ones((n, hd), dt))
        elif op == "mamba":
            Di, N, R = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
            # the family's own start for the two that set the state's
            # memory: A = -(1 .. N) a channel, and step sizes log-uniform
            # in [1e-3, 1e-1] (dt_bias their inverse softplus), so that the
            # state forgets over tens to thousands of tokens. Normal draws
            # here would forget in one step or never, and a state carried
            # wrongly would read the same as one carried rightly
            step = jnp.exp(jax.random.uniform(next(keys), (n, Di), jnp.float32)
                           * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
            lp.update(
                in_proj=init(n, D, 2 * Di, scale=D ** -0.5),
                conv_w=init(n, cfg.mamba_d_conv, Di,
                            scale=cfg.mamba_d_conv ** -0.5),
                conv_b=init(n, Di, scale=0.1),
                x_proj=init(n, Di, R + 2 * N, scale=Di ** -0.5),
                dt_norm=jnp.ones((n, R), dt), b_norm=jnp.ones((n, N), dt),
                c_norm=jnp.ones((n, N), dt),
                dt_proj=init(n, R, Di, scale=R ** -0.5),
                dt_bias=(step + jnp.log(-jnp.expm1(-step))).astype(dt),
                A_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                    (n, N, Di)).astype(dt),
                D=jnp.ones((n, Di), dt),
                out_proj=init(n, Di, D, scale=Di ** -0.5))
        else:
            lp.update(
                conv_in=init(n, D, 3 * D, scale=D ** -0.5),
                conv_w=init(n, D, taps, scale=taps ** -0.5),
                conv_out=init(n, D, D, scale=D ** -0.5))
        if ff == "moe":
            lp.update(router=init(n, D, E, scale=D ** -0.5))
            if cfg.use_expert_bias:
                lp.update(router_bias=jax.random.normal(
                    next(keys), (n, E), jnp.float32) * 0.01)
            lp.update(
                w_gate=init(n, held, D, Fm, scale=D ** -0.5),
                w_up=init(n, held, D, Fm, scale=D ** -0.5),
                w_down=init(n, held, Fm, D, scale=Fm ** -0.5))
            if Fs:
                lp.update(
                    ws_gate=init(n, D, Fs, scale=D ** -0.5),
                    ws_up=init(n, D, Fs, scale=D ** -0.5),
                    ws_down=init(n, Fs, D, scale=Fs ** -0.5))
        else:
            lp.update(
                w_gate=init(n, D, F, scale=D ** -0.5),
                w_up=init(n, D, F, scale=D ** -0.5),
                w_down=init(n, F, D, scale=F ** -0.5))
        runs.append(lp)
    params: Params = {
        # rows of unit norm: a tied table of unit-variance entries would
        # put sqrt(D) on the token's own logit and bury what the layers add
        "embed": init(1, V, D, scale=D ** -0.5 if cfg.tie_word_embeddings
                      else 1.0)[0],
        "final_norm": jnp.ones((D,), dt),
        "layers": tuple(runs),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = init(1, D, V, scale=D ** -0.5)[0]
    return params


def layer_runs(cfg: ModelConfig, params: Params) -> tuple:
    """``params["layers"]`` as the stacked dicts of ``cfg.layer_runs``: the
    tuple itself, or the one dict of a stack of one kind."""
    layers = params["layers"]
    return tuple(layers) if isinstance(layers, (tuple, list)) else (layers,)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayerAux:
    """What a forward pass is handed and hands back beside the KV pools,
    for a model that keeps per-slot state or routes to experts.

    ``conv``: the per-slot state of the layers that keep one beside the KV
    pool, ONE object donated and returned like the pools (the name is the
    first such layer's). For a model with conv layers an array
    [n_conv_layers, slots + 1, taps - 1, D] in the activation type: each
    conv layer's short-convolution state of every slot (the last
    ``taps - 1`` gated inputs). For a model with Mamba layers a
    ``MambaState``. Either way the last row is trash, where rows that are
    padding write, and a token step (decode: row i is slot i) shifts a
    live row's window by one input and leaves an idle row's state as it
    was, by ONE select made where the window is convolved
    (``attention.conv_token_step``, or the Mamba layers' kernel over live
    rows). None where the model has no such layer, or for a pass from an
    empty state whose state nobody keeps (scoring).
    ``slots`` [B]: the slot of each row (prefill, chunk); None where row i
    IS slot i (decode).
    ``moe_rows`` [n_moe_layers, E] int32, handed BACK: the (token, expert)
    pairs each expert got in this pass; None in, and None back where no
    layer routes."""
    conv: "jnp.ndarray | None" = None
    slots: "jnp.ndarray | None" = None
    moe_rows: "jnp.ndarray | None" = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MambaState:
    """What the Mamba layers keep for every slot (and the trash row).

    ``conv`` [n_mamba_layers, slots + 1, (taps - 1) * Di], in the
    activation type: the convolution's last ``taps - 1`` inputs, oldest
    first, side by side on one row (three rows of a 16-row tile would
    hold five times the bytes).
    ``ssm`` [n_mamba_layers, slots + 1, N, Di] float32: the state-space
    state h, a running sum over the whole sequence. The channels lie on
    the lanes (the published h is [Di, N]: with 16 states on a 128-lane
    row the TPU's tiled layout would hold eight times the bytes).

    A prompt's pass takes a layer's rows out of both by slot and writes
    them back. A token step hands both arrays WHOLE to the layer and gets
    them back (``_mamba``): a live slot's window drops its oldest input
    and takes the token's as its newest, in one pass over the row where
    it lies; an idle slot's window and h, and the trash row, are not
    written."""
    conv: jnp.ndarray
    ssm: jnp.ndarray


def init_conv_state(cfg: ModelConfig, slots: int, dtype=None):
    """Zeroed per-slot state for ``slots`` decode slots (and the trash
    row): the conv layers' array, the Mamba layers' ``MambaState``, or
    None for a model with neither."""
    if cfg.num_mamba_layers:
        n, Di = cfg.num_mamba_layers, cfg.mamba_d_inner
        return MambaState(
            conv=jnp.zeros((n, slots + 1, (cfg.mamba_d_conv - 1) * Di),
                           jnp.dtype(dtype or cfg.dtype)),
            ssm=jnp.zeros((n, slots + 1, cfg.mamba_d_state, Di), jnp.float32))
    if not cfg.num_conv_layers:
        return None
    return jnp.zeros((cfg.num_conv_layers, slots + 1, cfg.conv_L_cache - 1,
                      cfg.hidden_size), jnp.dtype(dtype or cfg.dtype))


# ---------------------------------------------------------------------------
# Layer
# ---------------------------------------------------------------------------

def _qkv(lp: Params, cfg: ModelConfig, h: jnp.ndarray, adapter_idx=None):
    q = _lqe("btd,dhk->bthk", h, lp, "wq", adapter_idx)
    k = _lqe("btd,dhk->bthk", h, lp, "wk", adapter_idx)
    v = _lqe("btd,dhk->bthk", h, lp, "wv", adapter_idx)
    if cfg.attention_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    return q, k, v


_EXPERT_STACKS = ("w_gate", "w_up", "w_down")
# the most tokens an expert layer takes in one piece (a 2,048-token bucket;
# four of them, 65,536 pairs of 7,168, would be 3.8 GB of rows)
_MOE_TOKEN_BLOCK = 2048


def _mlp(lp: Params, cfg: ModelConfig, h: jnp.ndarray, token_valid: jnp.ndarray,
         adapter_idx=None, ff: str = "dense", experts=None):
    """The layer's feed-forward: (out [B, T, D], the rows each expert got
    [E] or None for a dense network). ``experts``: (the run's expert
    weights, stacked over its layers, this layer's index in them)."""
    act = _act(cfg)
    if ff == "moe":
        B, T, D = h.shape
        stacks, layer = experts

        def routed(x, valid):
            return moe_block(
                x, lp["router"],
                *(stacks[k] for k in _EXPERT_STACKS), layer=layer,
                top_k=cfg.num_experts_per_tok, act=act, valid=valid,
                bias=lp["router_bias"] if cfg.use_expert_bias else None,
                scores=cfg.moe_router, renorm=cfg.norm_topk_prob,
                eps=cfg.moe_renorm_eps, scale=cfg.routed_scaling_factor,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                first_expert=(None if cfg.experts_held is None
                              else cfg.first_expert))

        N = B * T
        x, valid = h.reshape(N, D), token_valid.reshape(N)
        if N > _MOE_TOKEN_BLOCK and N % _MOE_TOKEN_BLOCK == 0:
            # the grouped product's rows are (token, choice) pairs, top_k
            # times the tokens, in and out: a block of tokens at a time
            nb = N // _MOE_TOKEN_BLOCK
            out, rows = jax.lax.map(
                lambda a: routed(*a), (x.reshape(nb, -1, D),
                                       valid.reshape(nb, -1)))
            out, rows = out.reshape(N, D), rows.sum(axis=0)
        else:
            out, rows = routed(x, valid)
        out = out.reshape(B, T, D)
        if cfg.n_shared_experts:
            with jax.named_scope("moe.shared"):
                gate = act(qeinsum("btd,df->btf", h, lp["ws_gate"]))
                up = qeinsum("btd,df->btf", h, lp["ws_up"])
                out = out + qeinsum("btf,fd->btd", gate * up, lp["ws_down"])
        return out, rows
    gate = act(_lqe("btd,df->btf", h, lp, "w_gate", adapter_idx))
    up = _lqe("btd,df->btf", h, lp, "w_up", adapter_idx)
    return _lqe("btf,fd->btd", gate * up, lp, "w_down", adapter_idx), None


def _short_conv(lp: Params, cfg: ModelConfig, u: jnp.ndarray,
                state: jnp.ndarray, n_valid: jnp.ndarray):
    """LFM2's gated short convolution. u [B, T, D] (normed); ``state``
    [B, taps - 1, D]: the gated inputs z at the row's last taps - 1
    positions before u's first (zeros for a fresh sequence); ``n_valid``
    [B]: how many of the T positions are real, from the left.

      [Bg, Cg, X] = split3(u W_in);  z = Bg * X
      c_t = sum_j w[:, j] * z_{t - (taps-1) + j}     (causal, depthwise)
      out = (Cg * c) W_out

    Returns (out [B, T, D], the state after the row's last real position
    [B, taps - 1, D]). Padding lies to the right of every real position,
    so it reaches neither a real position's sum nor the state."""
    taps = cfg.conv_L_cache
    T = u.shape[1]
    with jax.named_scope("lfm2.conv"):
        bg, cg, xg = jnp.split(qeinsum("btd,de->bte", u, lp["conv_in"]), 3,
                               axis=-1)
        z = bg * xg
        w = lp["conv_w"].astype(jnp.float32)                  # [D, taps]
        if T == 1:
            c, kept = conv_token_step(
                [state[:, j] for j in range(taps - 1)], z[:, 0], w.T,
                (n_valid > 0)[:, None])
            c, new_state = c[:, None], jnp.stack(kept, axis=1)
        else:
            zext = jnp.concatenate([state.astype(u.dtype), z], axis=1)
            c = sum(zext[:, j:j + T].astype(jnp.float32) * w[:, j]
                    for j in range(taps))
            at = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)
            new_state = jnp.take_along_axis(zext, at[:, :, None], axis=1)
        out = qeinsum("btd,de->bte", cg * c.astype(u.dtype), lp["conv_out"])
    return out, new_state


# time steps of the state-space scan between two stores of h: the steps of
# one block are unrolled into one loop body, so h crosses HBM once a block
# and not once a step (measured on a v5e: PERF.md section 6, PR 46)
_SSM_SCAN_BLOCK = 8


def _ssm_scan(delta, A, x, Bm, Cm, h0):
    """The selective scan, h_t = exp(delta_t A) h_(t-1) + (delta_t x_t) B_t
    and y_t = h_t C_t, sequential in time: delta, x [B, T, Di] float32,
    A [N, Di], Bm, Cm [B, T, N], h0 [B, N, Di] float32 -> (y [B, T, Di]
    float32, h_T). Nothing of size [B, T, N, Di] exists: the carry is one
    h, and a step reads its four inputs and writes its y. Decay is per
    channel AND per state, so no matrix product expresses the
    recurrence."""

    def step(h, at):
        d, xv, b, c = at                              # [B, Di] x2, [B, N] x2
        h = jnp.exp(d[:, None] * A) * h + (d * xv)[:, None] * b[:, :, None]
        return h, (h * c[:, :, None]).sum(axis=1)

    if x.shape[1] == 1:                 # a decode step: no loop around it
        h, y = step(h0, (delta[:, 0], x[:, 0], Bm[:, 0], Cm[:, 0]))
        return y[:, None], h
    h, y = jax.lax.scan(
        step, h0, tuple(jnp.swapaxes(a, 0, 1) for a in (delta, x, Bm, Cm)),
        unroll=min(_SSM_SCAN_BLOCK, x.shape[1]))
    return jnp.swapaxes(y, 0, 1), h


def _mamba_conv(window, x, w, bias, n_valid):
    """The Mamba layers' convolution at T positions, the general code:
    window [B, (taps-1) Di] the rows' last taps - 1 inputs, oldest first;
    x [B, T, Di]; w [taps, Di], bias [Di] float32; n_valid [B]. Returns
    (silu(the causal depthwise sums + bias) [B, T, Di] in x's type, the
    window after each row's last real position). A token step does the
    same in one pass (``attention.dispatch_conv_step``)."""
    taps, Di = w.shape
    T = x.shape[1]
    # the window's taps lie side by side on a row: lane slices in and out
    # (a reshape to [B, taps - 1, Di] makes the compiler keep the whole
    # state array transposed inside the decode window and copy it at both
    # ends)
    past = [window[:, j * Di:(j + 1) * Di] for j in range(taps - 1)]
    xext = jnp.concatenate(
        [jnp.stack(past, axis=1).astype(x.dtype), x], axis=1)
    c = sum(xext[:, j:j + T].astype(jnp.float32) * w[j] for j in range(taps))
    xc = jax.nn.silu(c + bias).astype(x.dtype)
    at = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)
    kept = jnp.take_along_axis(xext, at[:, :, None], axis=1)
    return xc, jnp.concatenate([kept[:, j] for j in range(taps - 1)], axis=-1)


class TokenStep(NamedTuple):
    """Where a Mamba layer's token step finds its state in the whole
    arrays, and which of their rows it visits."""
    layer: jnp.ndarray        # the layer's index among the Mamba layers
    live_slots: tuple         # ``live_first`` of the rows
    live_tiles: tuple         # ``live_tiles_first`` of the rows


def _mamba(lp: Params, cfg: ModelConfig, u: jnp.ndarray,
           state: "MambaState", n_valid: jnp.ndarray, step=None):
    """Jamba's Mamba-1 mixer. u [B, T, D] (normed); ``state``, the rows'
    own: ``conv`` [B, (taps-1) Di] the last taps - 1 convolution inputs
    before u's first, ``ssm`` [B, N, Di] the state-space state h, zeros for
    a fresh sequence; ``n_valid`` [B]: how many of the T positions are
    real, from the left. In a token step (``step``, a ``TokenStep``; T = 1)
    ``state`` is the WHOLE arrays, [n_mamba_layers, slots + 1, ...], row i
    is slot i, and both halves are updated where they lie, the live slots
    alone where the kernels run: the window read, convolved, shifted by
    one tap and written back in one pass (``dispatch_conv_step``), h's
    blocks likewise (``dispatch_ssm_step``); an idle slot's window and h
    stay as they were.

      [x, z] = split2(u W_in)
      x_t <- silu(b_c + sum_j w_c[j] * x_(t - (taps-1) + j))   (depthwise)
      [dt, B, C] = split(x W_x), each through its own RMS norm
      delta_t = softplus(dt_t W_dt + b_dt);  A = -exp(A_log)
      h_t = exp(delta_t A) h_(t-1) + (delta_t x_t) B_t;  y_t = h_t C_t + D x_t
      out = (y * silu(z)) W_out

    Returns (out [B, T, D], the rows' state after their last real
    position; in a token step the whole arrays again). Padding lies to the
    right of every real position and is given delta = 0: exp(0 A) = 1 and
    0 x B = 0, so h passes through a padded step exactly as it was (the
    other way, a select of old against new h a step, would read h
    twice)."""
    taps, N, R = cfg.mamba_d_conv, cfg.mamba_d_state, cfg.mamba_dt_rank
    B, T, _ = u.shape
    Di, eps, f32 = cfg.mamba_d_inner, cfg.rms_norm_eps, jnp.float32
    if T > 1:
        record_choice(
            "ssm_scan", "xla",
            f"lax.scan over time, {_SSM_SCAN_BLOCK} steps unrolled a body, "
            f"h [{N}, {Di}] float32 the carry: no kernel yet")
    window, h = state.conv, state.ssm
    with jax.named_scope("jamba.mamba"):
        xz = qeinsum("btd,de->bte", u, lp["in_proj"])
        x, z = jnp.split(xz, 2, axis=-1)
        w = lp["conv_w"].astype(f32)                          # [taps, Di]
        bias = lp["conv_b"].astype(f32)
        if step is None:
            xc, window = _mamba_conv(window, x, w, bias, n_valid)
            xs = xc.astype(f32)
        else:
            # xs: xc as the state-space step reads it (of an idle row
            # anything: that step visits none)
            xc, xs, window = dispatch_conv_step(
                xz[:, 0], w, bias, window, step.layer, n_valid > 0,
                step.live_tiles)
            xc, xs = xc[:, None], xs[:, None]
        dt, Bm, Cm = jnp.split(qeinsum("bte,er->btr", xc, lp["x_proj"]),
                               [R, R + N], axis=-1)
        dt = rms_norm(dt, lp["dt_norm"], eps)
        Bm = rms_norm(Bm, lp["b_norm"], eps).astype(f32)
        Cm = rms_norm(Cm, lp["c_norm"], eps).astype(f32)
        delta = jax.nn.softplus(
            qeinsum("btr,re->bte", dt, lp["dt_proj"]).astype(f32)
            + lp["dt_bias"].astype(f32))
        real = jnp.arange(T, dtype=jnp.int32)[None, :] < n_valid[:, None]
        delta = jnp.where(real[:, :, None], delta, 0.0)
        A = -jnp.exp(lp["A_log"].astype(f32))
        if step is None:
            y, h = _ssm_scan(delta, A, xs, Bm, Cm, h.astype(f32))
        else:
            y, h = dispatch_ssm_step(delta, A, xs, Bm, Cm, h, step.layer,
                                     n_valid > 0, step.live_slots)
        y = (y + lp["D"].astype(f32) * xc.astype(f32)) \
            * jax.nn.silu(z.astype(f32))
        out = qeinsum("bte,ed->btd", y.astype(u.dtype), lp["out_proj"])
    return out, MambaState(conv=window, ssm=h)


def _layer_step(
    cfg: ModelConfig,
    inv_freq: jnp.ndarray,
    page_table: jnp.ndarray,
    positions: jnp.ndarray,       # [B, T] rope/write positions
    write_positions: jnp.ndarray,  # [B, T], negative => trash page
    lengths: jnp.ndarray,          # [B]
    mode: str,                     # "prefill" | "decode"
    x: jnp.ndarray,                # [B, T, D]
    lp: Params,
    k_pages: jnp.ndarray,          # [KV, P, page, hd] (head-major)
    v_pages: jnp.ndarray,
    layer_idx: "jnp.ndarray | None" = None,
    inv_freq_local: "jnp.ndarray | None" = None,
    mm_groups: "jnp.ndarray | None" = None,
    mm_pos3: "jnp.ndarray | None" = None,  # [B, 3, T] qwen3vl mrope
    rope_positions: "jnp.ndarray | None" = None,  # [B, T] mrope-shifted
    token_valid: "jnp.ndarray | None" = None,  # [B, T]; default: writes>=0
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
    kind: tuple = ("attn", "dense"),   # cfg.layer_kind: trace-time structure
    conv_state=None,   # the rows' state: [B, taps-1, D] (conv layer), or
                       # a MambaState of rows (Mamba layer)
    n_valid: "jnp.ndarray | None" = None,      # [B] real positions of T
    experts=None,                              # _mlp's, for an expert layer
    token_step=None,   # _mamba's TokenStep: conv_state is the whole arrays
):
    """One layer of ``kind`` (operator, feed-forward). Returns (x, k_pages,
    v_pages, a conv or Mamba layer's new state rows or None, the rows each
    expert got or None)."""
    op, ff = kind
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    if op == "conv":
        out, conv_state = _short_conv(lp, cfg, h, conv_state, n_valid)
    elif op == "mamba":
        out, conv_state = _mamba(lp, cfg, h, conv_state, n_valid, token_step)
    elif op == "mla":
        out, k_pages = _latent_attention(
            cfg, inv_freq, page_table, positions, write_positions, lengths,
            mode, h, lp, k_pages, rope_positions)
    else:
        out, k_pages, v_pages = _attention(
            cfg, inv_freq, page_table, positions, write_positions, lengths,
            mode, h, lp, k_pages, v_pages, layer_idx, inv_freq_local,
            mm_groups, mm_pos3, rope_positions, adapter_idx, op)
    if cfg.post_norms:
        out = rms_norm(out, lp["attn_post_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    x = x + out

    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    m, moe_rows = _mlp(lp, cfg, h,
                       token_valid=(write_positions >= 0 if token_valid is None
                                    else token_valid),
                       adapter_idx=adapter_idx, ff=ff, experts=experts)
    if cfg.post_norms:
        m = rms_norm(m, lp["mlp_post_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    x = x + m
    return x, k_pages, v_pages, conv_state, moe_rows


def _latent_attention(cfg, inv_freq, page_table, positions, write_positions,
                      lengths, mode, h, lp, pool, rope_positions):
    """DeepSeek's latent attention (MLA) on the normed input ``h`` [B, T,
    D]: (W_o of the attended values, the latent pool).

      cq = norm_q(h W_qa);  [q_n | q_r]_h = cq [W_qn | W_qr]_h
      [ckv | kr] = h W_kva;  c = norm_kv(ckv);  k_r = rope(kr);  q_r = rope
      the cache row is [c | k_r]: one a token, shared by all heads

    Prefill and chunks expand rows to heads (k_n,h = c W_UK,h, v_h = c
    W_UV,h) and take score_h = (q_n,h . k_n,h + q_r,h . k_r) s; a decode
    step absorbs the two into the query (q_n,h W_UK,h^T) and the output
    (o_lat,h W_UV,h) and attends the rows as they lie: the same numbers
    (ops/attention.py). s = head_dim^-0.5 m^2, m yarn's factor."""
    from llms_on_kubernetes_tpu.engine.cache import write_latent
    from llms_on_kubernetes_tpu.ops.attention import (
        dispatch_latent_chunk, dispatch_latent_decode,
        dispatch_latent_prefill,
    )

    nope, lat = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    scale = cfg.head_dim ** -0.5 * yarn_attention_factor(cfg.rope_scaling) ** 2
    with jax.named_scope("mla.project"):
        cq = rms_norm(qeinsum("btd,dr->btr", h, lp["w_qa"]), lp["q_a_norm"],
                      eps)
        q_n = qeinsum("btr,fr->btf", cq, lp["w_qn"]).reshape(
            *h.shape[:2], cfg.num_heads, nope)
        q_r = qeinsum("btr,fr->btf", cq, lp["w_qr"]).reshape(
            *h.shape[:2], cfg.num_heads, cfg.qk_rope_head_dim)
        ckv = qeinsum("btd,dw->btw", h, lp["w_kva"])
        c = rms_norm(ckv[..., :lat], lp["kv_a_norm"], eps)
        q_r, k_r = apply_rope(
            q_r, ckv[:, :, None, lat:],
            positions if rope_positions is None else rope_positions, inv_freq)
        rows = jnp.concatenate([c, k_r[:, :, 0]], axis=-1)
    pool = write_latent(pool, rows, page_table, write_positions)
    w_uk, w_uv = lp["w_uk"].astype(h.dtype), lp["w_uv"].astype(h.dtype)
    with jax.named_scope("mla.attend"):
        if mode == "decode":
            q_lat = jnp.einsum("bhk,hrk->bhr", q_n[:, 0], w_uk)
            o_lat = dispatch_latent_decode(
                jnp.concatenate([q_lat, q_r[:, 0]], axis=-1), pool,
                page_table, lengths, scale=scale, lat=lat)
            attn = jnp.einsum("bhr,hrk->bhk", o_lat, w_uv)[:, None]
        elif mode == "prefill":
            attn = dispatch_latent_prefill(q_n, q_r, rows, w_uk, w_uv,
                                           lengths, scale=scale)
        else:
            attn = dispatch_latent_chunk(
                q_n, q_r, pool, page_table, w_uk, w_uv, positions[:, 0],
                lengths, scale=scale)
    with jax.named_scope("mla.out"):
        return qeinsum("bthk,hkd->btd", attn, lp["wo"]), pool


def _attention(cfg, inv_freq, page_table, positions, write_positions,
               lengths, mode, h, lp, k_pages, v_pages, layer_idx,
               inv_freq_local, mm_groups, mm_pos3, rope_positions,
               adapter_idx, op="attn"):
    """The attention operator on the normed input ``h``: (W_o of the
    attended values, k_pages, v_pages). ``op`` is the run's kind ("attn" |
    "swa"): its window is a Python int or None (``cfg.attn_window``), so
    every dispatcher sees it static and may take a kernel."""
    scale = (cfg.query_pre_attn_scalar or cfg.head_dim) ** -0.5
    window = cfg.attn_window(op)
    # a stack that names its window layers (``layer_types``): a window
    # layer rotates by the unscaled theta (``inv_freq_local``), a full
    # layer by the scaled frequencies, with yarn's factor on cosine and
    # sine served as its square on the scale; each kind records its own
    # choice of kernel
    kind = None
    if cfg.names_window_layers:
        kind = "sliding" if op == "swa" else "full"
        if op == "swa":
            inv_freq = inv_freq_local
        else:
            scale *= yarn_cos_sin_factor(cfg.rope_scaling) ** 2
    # Gemma-2/3 interleaved attention: layer is global iff (i+1) % pattern == 0;
    # local layers use sliding_window + rope_local_theta. The window becomes a
    # traced scalar so one scanned layer body serves both layer kinds.
    if cfg.sliding_window_pattern is not None and layer_idx is not None:
        is_global = (layer_idx + 1) % cfg.sliding_window_pattern == 0
        window = jnp.where(is_global, jnp.int32(2 ** 30), jnp.int32(cfg.sliding_window))
        inv_freq = jnp.where(is_global, inv_freq, inv_freq_local)
    with layer_kind(kind):
        return _attend(cfg, inv_freq, page_table, positions, write_positions,
                       lengths, mode, h, lp, k_pages, v_pages, mm_groups,
                       mm_pos3, rope_positions, adapter_idx, scale, window)


def _attend(cfg, inv_freq, page_table, positions, write_positions, lengths,
            mode, h, lp, k_pages, v_pages, mm_groups, mm_pos3,
            rope_positions, adapter_idx, scale, window):
    """``_attention`` with the layer's scale, window and frequencies
    chosen."""
    q, k, v = _qkv(lp, cfg, h, adapter_idx=adapter_idx)
    if mm_pos3 is not None:
        # multimodal prompt on an mrope model (Qwen3-VL): interleaved
        # 3-axis rotary; for text-only rows all three axes are equal and
        # this matches apply_rope exactly
        from llms_on_kubernetes_tpu.ops.rope import apply_mrope

        q, k = apply_mrope(q, k, mm_pos3, inv_freq, cfg.mrope_section)
    elif cfg.use_rope:
        # rope_positions may be shifted by an mrope delta; ``positions``
        # stays token-indexed for attention masking / chunk history
        q, k = apply_rope(
            q, k,
            positions if rope_positions is None else rope_positions,
            inv_freq)
    if mode == "decode":
        # decode: the current token's KV append rides INSIDE the paged
        # attention dispatch (fused Pallas write on the fast path — no
        # per-slot DUS loop; plain write+attend elsewhere)
        from llms_on_kubernetes_tpu.ops.attention import (
            dispatch_paged_attention_write,
        )

        attn, k_pages, v_pages = dispatch_paged_attention_write(
            q[:, 0], k_pages, v_pages, page_table, lengths,
            k[:, 0], v[:, 0], write_positions,
            scale=scale, sliding_window=window,
            attn_softcap=cfg.attn_softcap,
        )
        attn = attn[:, None]
    else:
        k_pages, v_pages = write_tokens(k_pages, v_pages, k, v, page_table,
                                        write_positions)
        if mode == "prefill":
            attn = dispatch_prefill_attention(
                q, k, v, lengths,
                scale=scale, sliding_window=window,
                attn_softcap=cfg.attn_softcap, mm_groups=mm_groups,
            )
        else:  # "chunk": queries attend to previous chunks' cached KV
            # plus this chunk, through the page table (history = global
            # position of the chunk's first token)
            attn = dispatch_chunk_attention(
                q, k_pages, v_pages, page_table,
                positions[:, 0], lengths,
                scale=scale, sliding_window=window,
                attn_softcap=cfg.attn_softcap,
            )
    out = _lqe("bthk,hkd->btd", attn, lp, "wo", adapter_idx)
    return out, k_pages, v_pages


def _run_layers(
    cfg: ModelConfig,
    params: Params,
    x: jnp.ndarray,
    k_pages: jnp.ndarray,          # [KV, A*P, page, hd] flat pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,       # [B, pages_per_seq] per-layer-LOCAL ids
    positions: jnp.ndarray,
    write_positions: jnp.ndarray,
    lengths: jnp.ndarray,
    mode: str,
    mm_groups: "jnp.ndarray | None" = None,
    mm_pos3: "jnp.ndarray | None" = None,
    deepstack: "jnp.ndarray | None" = None,   # [n_taps, B, n_img*t_img, D]
    mm_idx: "jnp.ndarray | None" = None,      # [B, T] soft-token index
    mm_is_img: "jnp.ndarray | None" = None,   # [B, T] image-token mask
    rope_positions: "jnp.ndarray | None" = None,  # [B, T] mrope-shifted
    token_valid: "jnp.ndarray | None" = None,  # [B, T] MoE routing mask
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
    aux: "LayerAux | None" = None,
):
    """The layer stack, run by run (``cfg.layer_runs``): each run is one
    ``lax.scan`` over its stacked parameters, with the body of its kind
    chosen at trace time. The pools hold the ATTENTION layers only, in
    stack order; the per-slot state (``aux.conv``) the conv or Mamba
    layers, in theirs. Returns
    (x, k_pages, v_pages, aux with the new state and the experts' rows)."""
    # None: attention without positions (``_attention`` rotates nothing)
    inv_freq = jnp.asarray(rope_frequencies(
        cfg.rope_dim, cfg.rope_theta, cfg.rope_scaling)) \
        if cfg.use_rope else None
    # the window layers' frequencies: theta of their own where the config
    # has one, never scaled
    inv_freq_local = (
        jnp.asarray(rope_frequencies(
            cfg.head_dim, cfg.rope_local_theta or cfg.rope_theta))
        if cfg.rope_local_theta is not None or cfg.names_window_layers
        else None
    )
    # flat-pool layer folding. Default (layer-major): layer l's pages live
    # in the block [l*P, (l+1)*P). Context parallelism (seq>1 mesh)
    # numbers PAGE-MAJOR (flat = page_id * L + l) instead, so a contiguous
    # 1/R shard of the flat axis holds 1/R of every layer's pages — see
    # ops/cp.py. Trace-time switch: one executable per mesh, as always.
    from llms_on_kubernetes_tpu.parallel.mesh import seq_parallelism

    cp = seq_parallelism() > 1
    n_attn = cfg.num_attn_layers
    pages_per_layer = k_pages.shape[1] // max(n_attn, 1)
    conv = aux.conv if aux is not None else None
    B, T = x.shape[:2]
    if cfg.keeps_slot_state:
        if conv is None and mode != "prefill":
            raise ValueError(
                f"{cfg.name}: a {mode} pass continues a sequence and needs "
                f"its conv state (aux.conv)")
        n_valid = (lengths > 0).astype(jnp.int32) if mode == "decode" \
            else lengths
        slots = None if aux is None else aux.slots
        trash = None if conv is None else \
            jax.tree.leaves(conv)[0].shape[1] - 1
        # the state a row starts a fresh sequence from, shaped as
        # conv_rows'
        if cfg.num_mamba_layers:
            fresh = MambaState(
                conv=jnp.zeros(
                    (B, (cfg.mamba_d_conv - 1) * cfg.mamba_d_inner), x.dtype),
                ssm=jnp.zeros((B, cfg.mamba_d_state, cfg.mamba_d_inner),
                              jnp.float32))
            # a token step walks the live rows of the whole arrays, the
            # same ones in every layer
            live_rows = (live_first(lengths > 0),
                         live_tiles_first(lengths > 0)) \
                if mode == "decode" else None
        else:
            fresh = jnp.zeros((B, cfg.conv_L_cache - 1, x.shape[-1]),
                              x.dtype)

    def per_row(flags, a):
        return flags.reshape(B, *[1] * (a.ndim - 1))

    def conv_rows(conv, ci):
        """The state each row's operator starts from, in conv (or Mamba)
        layer ci: ``conv``'s arrays with the layer and slot axes taken."""
        if mode == "prefill":       # a fresh sequence: never a slot's past
            return fresh

        def rows(a):
            a = jax.lax.dynamic_index_in_dim(a, ci, 0, False)
            if mode == "decode":    # row i is slot i
                return a[:B]
            # a chunk continues its slot's sequence; a prompt's first chunk
            # starts one, whatever the slot's last occupant left
            return jnp.where(per_row(positions[:, 0] > 0, a), a[slots], 0)

        return jax.tree.map(rows, conv)

    def conv_write(conv, ci, new):
        if conv is None:
            return None

        def write(arr, new):
            if mode == "decode":
                # row i is slot i; an idle row's ``new`` is its slot's state
                # as it was (the operator's token step selected it)
                return jax.lax.dynamic_update_slice(
                    arr, new.astype(arr.dtype)[None],
                    (ci, *[0] * (arr.ndim - 1)))
            # padding rows carry no slot: they write the trash row
            return arr.at[ci, jnp.where(lengths > 0, slots, trash)].set(
                new.astype(arr.dtype))

        return jax.tree.map(write, conv, new)

    def run_body(kind, stacks, first, a0, c0):
        """The scan body of one run: its kind, its expert stacks, and the
        stack / attention / conv index of its first layer."""
        op, _ff = kind

        def body(carry, per_layer):
            xc, kp, vp, cv = carry
            i, lp = per_layer
            idx, a_idx, c_idx = first + i, a0 + i, c0 + i
            # pools ride the CARRY (aliased buffer -> in-place scatter), never
            # the xs/ys path (which would rewrite the whole pool every step)
            if cp:
                pt = page_table * n_attn + a_idx
            else:
                pt = page_table + a_idx * pages_per_layer
            keeps = op in ("conv", "mamba")
            # a Mamba layer's token step takes its state arrays whole and
            # hands them back (dispatch_conv_step, dispatch_ssm_step); a
            # conv layer's state goes by rows
            whole = op == "mamba" and mode == "decode"
            if whole:
                old = cv
            else:
                old = conv_rows(cv, c_idx) if keeps else None
            xc, kp, vp, new, rows = _layer_step(
                cfg, inv_freq, pt, positions, write_positions, lengths, mode,
                xc, lp, kp, vp, layer_idx=idx, inv_freq_local=inv_freq_local,
                mm_groups=mm_groups, mm_pos3=mm_pos3,
                rope_positions=rope_positions, token_valid=token_valid,
                adapter_idx=adapter_idx, kind=kind, conv_state=old,
                n_valid=n_valid if keeps else None,
                experts=(stacks, i) if stacks else None,
                token_step=TokenStep(c_idx, *live_rows) if whole else None,
            )
            if whole:
                cv = new
            elif keeps:
                cv = conv_write(cv, c_idx, new)
            if deepstack is not None:
                # DeepStack (Qwen3-VL): intermediate vision features are ADDED
                # to the first n_taps decoder layers' outputs at image-token
                # positions
                n_taps = deepstack.shape[0]
                tap = jnp.take(deepstack, jnp.clip(idx, 0, n_taps - 1), axis=0)
                gathered = jnp.take_along_axis(tap, mm_idx[:, :, None], axis=1)
                inject = mm_is_img[:, :, None] & (idx < n_taps)
                xc = xc + jnp.where(inject, gathered.astype(xc.dtype), 0)
            return (xc, kp, vp, cv), rows

        return body

    moe_rows = []
    a0 = c0 = 0
    for (op, ff, first, n), run in zip(cfg.layer_runs,
                                       layer_runs(cfg, params)):
        # an expert layer's weights stay in their stack, whole, beside
        # the scan: the grouped product takes the stack and the layer's
        # index, never a slice of it (ops/moe._grouped_dot)
        stacks = ({k: run[k] for k in _EXPERT_STACKS} if ff == "moe" else {})
        (x, k_pages, v_pages, conv), rows = jax.lax.scan(
            run_body((op, ff), stacks, first, a0, c0),
            (x, k_pages, v_pages, conv),
            (jnp.arange(n, dtype=jnp.int32),
             {k: v for k, v in run.items() if k not in stacks}),
            # full unroll on TPU: no while loop may ever carry the pool (its
            # boundary copy costs more than the whole rest of the step)
            unroll=n if _unroll_layers() else 1,
        )
        if op in ("conv", "mamba"):
            c0 += n
        else:
            a0 += n
        if rows is not None:
            moe_rows.append(rows)
    if aux is not None:
        aux = LayerAux(conv=conv, slots=aux.slots,
                       moe_rows=jnp.concatenate(moe_rows) if moe_rows else None)
    return x, k_pages, v_pages, aux

def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["embed"][tokens]
    if cfg.embedding_multiplier is not None:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
    return x


def _logits(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps, style=cfg.norm_style)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    # bf16 operands + f32 accumulation: native MXU path. Casting the head
    # to f32 would stream the whole [D, V] matrix (the model's biggest
    # tensor) through a convert on every step for no accuracy gain — TPU
    # f32 matmuls decompose into bf16 passes anyway.
    logits = jnp.einsum("bd,dv->bv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return softcap(logits, cfg.logit_softcap)


# ---------------------------------------------------------------------------
# Public forward passes
# ---------------------------------------------------------------------------

def _with_aux(out: tuple, aux: "LayerAux | None") -> tuple:
    return out if aux is None else (*out, aux)


def forward_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T] padded prompt bucket
    lengths: jnp.ndarray,     # [B] true lengths (<= T); 0 => inactive row
    k_pages: jnp.ndarray,     # [KV, L*P, page, hd] flat pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, pages_per_seq]
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
    aux: "LayerAux | None" = None,
):
    """Process whole prompts; returns (last-token logits [B, V], new cache)
    and, where ``aux`` is given, the new ``LayerAux`` as a fourth result.
    A prompt starts from an empty conv state, never from its slot's."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    write_positions = jnp.where(positions < lengths[:, None], positions, -1)
    x = _embed(params, cfg, tokens)
    x, k_pages, v_pages, aux = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        positions, write_positions, lengths, "prefill",
        adapter_idx=adapter_idx, aux=aux,
    )
    last = jnp.clip(lengths - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [B, D]
    return _with_aux((_logits(params, cfg, x_last), k_pages, v_pages), aux)


def forward_score(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [1, T] padded prompt bucket
    lengths: jnp.ndarray,     # [1]
    top_k: int = 8,
):
    """Score a prompt: per-position logprob of the NEXT prompt token and
    the top-k alternatives at every position — the OpenAI ``echo`` +
    ``logprobs`` surface (prompt-token logprobs; vLLM ``prompt_logprobs``),
    which the serving prefill cannot provide (it keeps only the LAST
    position's logits).

    Cache-free: the causal attention runs over the in-flight k/v only, and
    writes are routed to a caller-provided single-page dummy pool (every
    write position is -1 = the trash page), so scoring never touches — and
    cannot corrupt — the serving engine's paged pool. The [T, V] logits
    reduce to [T] + [T, k] ON DEVICE; only those small arrays cross the
    host boundary.

    Returns (next_logprob [1, T] f32 — entry t scores tokens[t+1]; the
    last valid entry and padding are 0 —, top_ids [1, T, k] int32,
    top_logprobs [1, T, k] f32).
    """
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    write_positions = jnp.full((B, T), -1, jnp.int32)  # all writes -> trash
    # MoE routing validity must NOT come from write_positions here (every
    # write is routed to trash): all -1 would mask every expert claim and
    # zero the whole MLP on MoE models — round-4 review finding
    token_valid = positions < lengths[:, None]
    from llms_on_kubernetes_tpu.engine.cache import KVPool

    heads, width = cfg.cache_row
    dummy_shape = (heads, cfg.num_attn_layers, 1, width)
    k_pages = KVPool(jnp.zeros(dummy_shape, jnp.float32))
    v_pages = KVPool(jnp.zeros(dummy_shape, jnp.float32))
    page_table = jnp.zeros((B, 1), jnp.int32)
    x = _embed(params, cfg, tokens)
    x, _, _, _ = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        positions, write_positions, lengths, "prefill",
        token_valid=token_valid,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                 style=cfg.norm_style)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)  # shift
    # slab the head projection: a monolithic [T, V] f32 logits buffer is a
    # multi-GB transient at long buckets x 128k vocab (round-4 review
    # finding); 512-token slabs bound it to ~256 MB while each slab
    # reduces to [t] + [t, k] before the next is computed
    slab = min(512, T)
    nxt_lps, tids, tlps = [], [], []
    for s in range(0, T, slab):
        logits = jnp.einsum("btd,dv->btv", x[:, s:s + slab],
                            head.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        logits = softcap(logits, cfg.logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)                   # [B, t]
        nxt_lps.append(jnp.take_along_axis(
            logits, nxt[:, s:s + slab, None], axis=-1)[..., 0] - lse)
        lp, ids = jax.lax.top_k(logits, top_k)                    # exact
        tids.append(ids.astype(jnp.int32))
        tlps.append(lp - lse[..., None])
    nxt_lp = jnp.concatenate(nxt_lps, axis=1)
    valid = positions < (lengths[:, None] - 1)
    nxt_lp = jnp.where(valid, nxt_lp, 0.0)
    return (nxt_lp, jnp.concatenate(tids, axis=1),
            jnp.concatenate(tlps, axis=1))


def forward_prefill_mm(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T]: image runs hold cfg.image_token_id
    lengths: jnp.ndarray,     # [B]
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    img_embeds: jnp.ndarray,  # [B, n_img_max, tokens_per_image, D] projected
    deepstack: "jnp.ndarray | None" = None,  # [n_taps, B, n_img*t_img, D]
    pos3: "jnp.ndarray | None" = None,       # [B, 3, T] qwen3vl mrope
    prompt_len: "jnp.ndarray | None" = None,  # [B] image-region bound
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
):
    """Multimodal prefill: image soft tokens' embeddings are substituted at
    ``image_token_id`` positions (row-major across the prompt's images),
    and soft tokens of the same image attend bidirectionally. Qwen3-VL
    additionally passes ``pos3`` (3-axis mrope positions) and
    ``deepstack`` features added to the first decoder layers at image
    positions. Everything else matches ``forward_prefill``."""
    B, T = tokens.shape
    n_img, t_img = img_embeds.shape[1], img_embeds.shape[2]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    write_positions = jnp.where(positions < lengths[:, None], positions, -1)
    x = _embed(params, cfg, tokens)

    is_img = tokens == cfg.image_token_id                       # [B, T]
    if prompt_len is not None:
        # only the PROMPT region holds real image runs: a resumed
        # (preempted) request replays its generated tokens through this
        # path, and a SAMPLED id that collides with the placeholder must
        # stay ordinary text
        is_img = is_img & (positions < prompt_len[:, None])
    # row-major soft-token index -> (image, offset); image features are
    # NOT scaled by the embedding multiplier (HF gemma3 scales only the
    # text embeddings before the masked scatter)
    idx = jnp.clip(jnp.cumsum(is_img.astype(jnp.int32), axis=1) - 1,
                   0, n_img * t_img - 1)
    flat = img_embeds.reshape(B, n_img * t_img, -1)
    gathered = jnp.take_along_axis(flat, idx[:, :, None], axis=1)
    x = jnp.where(is_img[:, :, None], gathered.astype(x.dtype), x)
    mm_groups = jnp.where(is_img, idx // t_img, -1)
    # bidirectional attention within an image block is a GEMMA-3 semantic;
    # Qwen3-VL keeps plain causal attention over image tokens
    bidir = mm_groups if cfg.vision.family == "siglip" else None

    x, k_pages, v_pages, _ = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        positions, write_positions, lengths, "prefill", mm_groups=bidir,
        mm_pos3=pos3, deepstack=deepstack, mm_idx=idx, mm_is_img=is_img,
        adapter_idx=adapter_idx,
    )
    last = jnp.clip(lengths - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return _logits(params, cfg, x_last), k_pages, v_pages


def forward_chunk(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T] one padded CHUNK of a longer prompt
    history: jnp.ndarray,     # [B] tokens already cached before this chunk
    lengths: jnp.ndarray,     # [B] valid tokens in THIS chunk; 0 => idle row
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    pos_delta: "jnp.ndarray | None" = None,  # [B] mrope position offset
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
    aux: "LayerAux | None" = None,
):
    """Chunked prefill: process one chunk of a prompt whose earlier chunks
    are already in the paged cache. Returns the chunk's last-token logits
    [B, V] and the updated cache (and the new ``aux`` where one is given:
    a conv layer continues from its slot's state when history > 0, and
    from an empty one at history 0). With history=0 this is semantically
    ``forward_prefill`` (pinned by tests), but attends through the page
    pool — the engine uses it only for out-of-bucket prompts.

    ``pos_delta`` shifts the ROTARY position only, exactly as in
    ``forward_decode``: a Qwen3-VL prompt whose image region was adopted
    from the prefix cache replays its TEXT remainder through this path,
    and those tokens' rope positions lag their token index by the
    request's mrope delta (text after an image: all three mrope axes
    equal token_index + delta, which equals plain rope at that shifted
    position). Cache write positions stay token-indexed."""
    B, T = tokens.shape
    offs = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    positions = history[:, None] + offs
    write_positions = jnp.where(offs < lengths[:, None], positions, -1)
    rope_positions = (None if pos_delta is None
                      else positions + pos_delta[:, None])
    x = _embed(params, cfg, tokens)
    x, k_pages, v_pages, aux = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        positions, write_positions, lengths, "chunk",
        rope_positions=rope_positions, adapter_idx=adapter_idx, aux=aux,
    )
    last = jnp.clip(lengths - 1, 0, T - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return _with_aux((_logits(params, cfg, x_last), k_pages, v_pages), aux)


def forward_verify(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B, T] candidate window: [committed, drafts...]
    history: jnp.ndarray,     # [B] tokens already cached before this window
    lengths: jnp.ndarray,     # [B] valid tokens in THIS window; 0 => idle row
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    pos_delta: "jnp.ndarray | None" = None,  # [B] mrope position offset
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
):
    """Speculative-decoding verify pass: ``forward_chunk`` over a short
    candidate window, but returning logits at EVERY window position
    [B, T, V] instead of only the last. Position t's logits are the
    target model's distribution for the token FOLLOWING tokens[:, t] —
    one dispatch scores a committed token plus up to T-1 drafted
    continuations. KV for all T positions is written to the paged pool;
    a rejected suffix is simply overwritten by the next dispatch, which
    starts at the accepted length (the same tail-discard contract the
    fused decode window relies on). T is the fused window size (<= 8),
    so the [B, T, V] f32 buffer stays small."""
    B, T = tokens.shape
    offs = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    positions = history[:, None] + offs
    write_positions = jnp.where(offs < lengths[:, None], positions, -1)
    rope_positions = (None if pos_delta is None
                      else positions + pos_delta[:, None])
    x = _embed(params, cfg, tokens)
    x, k_pages, v_pages, _ = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        positions, write_positions, lengths, "chunk",
        rope_positions=rope_positions, adapter_idx=adapter_idx,
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                 style=cfg.norm_style)
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return softcap(logits, cfg.logit_softcap), k_pages, v_pages


def forward_decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,      # [B] one new token per slot
    lengths: jnp.ndarray,     # [B] length INCLUDING the new token; 0 => idle slot
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    pos_delta: "jnp.ndarray | None" = None,  # [B] mrope position offset
    adapter_idx: "jnp.ndarray | None" = None,  # [B] LoRA slot; -1 = base
    aux: "LayerAux | None" = None,
):
    """One decode step for every active slot; returns (logits [B, V], cache)
    and the new ``aux`` where one is given (row i is slot i: an idle row
    leaves its slot's conv state as it was).

    ``pos_delta`` shifts the ROTARY position only (Qwen3-VL mrope: an
    image's soft tokens advance the position index by its merged grid
    side, not by its token count, so text continuation positions lag the
    token index by a per-request delta). Cache write positions stay
    token-indexed."""
    positions = jnp.maximum(lengths - 1, 0)[:, None]                   # [B, 1]
    write_positions = jnp.where(lengths[:, None] > 0, positions, -1)
    rope_positions = (positions if pos_delta is None
                      else positions + pos_delta[:, None])
    x = _embed(params, cfg, tokens[:, None])
    x, k_pages, v_pages, aux = _run_layers(
        cfg, params, x, k_pages, v_pages, page_table,
        rope_positions, write_positions, lengths, "decode",
        adapter_idx=adapter_idx, aux=aux,
    )
    return _with_aux((_logits(params, cfg, x[:, 0]), k_pages, v_pages), aux)
