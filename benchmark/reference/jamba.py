"""The plain reference of the Jamba block (``model_type: jamba``), in float32
``jax.numpy`` at ``highest`` matmul precision: no kernels, no cache, no state
carried between calls, no batching; the state-space recurrence is a
``lax.scan`` over single time steps.

From the published ``config.json`` keys and the family's public
implementation. A layer ``l`` with input ``x`` (RMSNorm is ``x * w / rms(x)``,
``rms_norm_eps``):

    h = x + Mixer_l(RMSNorm_1(x));  y = h + (silu(g W_1) * (g W_3)) W_2,
    g = RMSNorm_2(h)

- layer ``l`` is attention iff ``l % attn_layer_period == attn_layer_offset``:
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
  heads of width ``hidden_size / num_attention_heads``, no biases, causal
  softmax at scale ``head ** -0.5`` over the whole context, and NO positional
  encoding of any kind.
- every other layer is a Mamba-1 mixer on ``u = RMSNorm_1(x)`` [T, D], with
  ``Di = mamba_expand * hidden_size`` channels, ``N = mamba_d_state``,
  ``R = mamba_dt_rank``:
  ``[x, z] = split2(u W_in)``;
  ``x_t <- silu(b_c + sum_j w_c[j] * x_(t-(L-1)+j))`` with ``L = mamba_d_conv``
  and ``x`` zero before the first token (a causal depthwise convolution,
  cross-correlation as ``torch.nn.Conv1d`` computes it on a left-padded
  sequence);
  ``[dt, B, C] = split(x W_x)`` of widths ``R, N, N``, EACH through its own
  learned RMSNorm (this family's addition to Mamba-1);
  ``delta_t = softplus(dt_t W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``h_t = exp(delta_t (x) A) * h_(t-1) + (delta_t * x_t) (x) B_t``, ``h`` one
  ``[N, Di]`` float32 array, zero before the first token;
  ``y_t = h_t C_t + D * x_t``; ``Mixer = (y * silu(z)) W_out``.
- ``num_experts`` is 1: every layer's network is the dense SwiGLU above.
- one RMSNorm after the last layer, then logits against the input embedding
  (tied), no scale.

Each thing that is not a key of the published file is ``assumed`` in the
configuration's own file. Weights come in the layout the program's seeded
generator emits for a stack of several kinds of layer: ``params["layers"]`` is
a tuple with one layer-stacked dict for each run of consecutive layers of one
kind, widened to float32 here one layer at a time. The depthwise filter is
``conv_w [L, Di]`` and ``A_log`` is ``[N, Di]`` (the published tensors are the
transposes). The norm is ``forward.py``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from reference.forward import _rms_norm


def _f32(w):
    return w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "taps", "states", "rank",
                                             "state_dtype"))
def _mamba_operator(x, lp, forget_at, *, eps, taps, states, rank,
                    state_dtype):
    """x + Mixer(RMSNorm(x)) for a Mamba-1 layer. x: [T, D]. ``forget_at``:
    the position before which h is set to zero (a control; -1: none)."""
    T = x.shape[0]
    u = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    xs, z = jnp.split(u @ _f32(lp["in_proj"]), 2, axis=-1)
    xs = jnp.concatenate([jnp.zeros((taps - 1, xs.shape[1]), jnp.float32),
                          xs])                        # zero before token 0
    w = _f32(lp["conv_w"])                            # [taps, Di]
    xs = jax.nn.silu(_f32(lp["conv_b"])
                     + sum(w[j] * xs[j:j + T] for j in range(taps)))
    dt, b, c = jnp.split(xs @ _f32(lp["x_proj"]), [rank, rank + states],
                         axis=-1)
    dt = _rms_norm(dt, _f32(lp["dt_norm"]), eps)
    b = _rms_norm(b, _f32(lp["b_norm"]), eps)
    c = _rms_norm(c, _f32(lp["c_norm"]), eps)
    delta = jax.nn.softplus(dt @ _f32(lp["dt_proj"]) + _f32(lp["dt_bias"]))
    a = -jnp.exp(_f32(lp["A_log"]))                   # [N, Di]

    kept = jnp.finfo(state_dtype)

    def step(h, at):
        t, d, xt, bt, ct = at                         # [Di], [Di], [N], [N]
        h = jnp.where(t == forget_at, 0.0, h)
        h = jnp.exp(d[None, :] * a) * h + (d * xt)[None, :] * bt[:, None]
        # the state as the type it is KEPT in between two steps (float32;
        # anything lower is a control). reduce_precision and not a pair of
        # casts, which the compiler is free to drop
        # (xla_allow_excess_precision) and on a TPU does
        h = jax.lax.reduce_precision(h, kept.nexp, kept.nmant)
        return h, ct @ h

    _, y = jax.lax.scan(step, jnp.zeros_like(a),
                        (jnp.arange(T), delta, xs, b, c))
    y = (y + _f32(lp["D"]) * xs) * jax.nn.silu(z)
    return x + y @ _f32(lp["out_proj"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps"))
def _attention_operator(x, lp, *, n_heads, n_kv, eps):
    """x + Attention(RMSNorm(x)): grouped-query, causal, no positions."""
    T = x.shape[0]
    pos = jnp.arange(T)
    u = _rms_norm(x, _f32(lp["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", u, _f32(lp["wq"]))
    k = jnp.einsum("td,dhk->thk", u, _f32(lp["wk"]))
    v = jnp.einsum("td,dhk->thk", u, _f32(lp["wv"]))
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


def layer_kinds(cfg: dict) -> list:
    """The operator of every layer, from the published keys."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attn" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def layers_of(cfg: dict, params: dict):
    """Each layer's (operator, parameters), cut out of the stacked run of
    consecutive layers of its kind that holds it."""
    kinds = layer_kinds(cfg)
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


def logits_at(cfg: dict, params: dict, tokens, positions,
              state_dtype="float32", forget_at=-1):
    """Float32 logits [len(positions), vocab] of the next token after each
    of ``positions`` for the one sequence ``tokens`` (causal: tokens past a
    position do not reach it, so a sequence may be padded at its end).
    The two controls a check of the state has to see: ``state_dtype`` other
    than float32 rounds h to that type after every step, and ``forget_at``
    sets every Mamba layer's h to zero before that position (a state lost
    where a chunk or a decode window hands it on)."""
    if int(cfg.get("num_experts", 1)) != 1 or cfg.get("mamba_proj_bias") \
            or not cfg.get("mamba_conv_bias", True) \
            or cfg.get("hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "the reference knows the dense Jamba block: num_experts 1, a "
            "convolution bias, no projection biases, SiLU")
    eps = float(cfg["rms_norm_eps"])
    heads = cfg["num_attention_heads"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(tokens)]
        for op, lp in layers_of(cfg, params):
            if op == "mamba":
                x = _mamba_operator(
                    x, lp, forget_at, eps=eps, taps=int(cfg["mamba_d_conv"]),
                    states=int(cfg["mamba_d_state"]),
                    rank=int(cfg["mamba_dt_rank"]), state_dtype=state_dtype)
            else:
                if lp["wq"].shape[-1] * heads != cfg["hidden_size"]:
                    raise ValueError("head width is hidden_size / heads")
                x = _attention_operator(
                    x, lp, n_heads=heads, n_kv=cfg["num_key_value_heads"],
                    eps=eps)
            x = _dense_network(x, lp, eps=eps)
        x = _rms_norm(x[jnp.asarray(positions)], _f32(params["final_norm"]),
                      eps)
        return x @ _f32(params["embed"]).T
