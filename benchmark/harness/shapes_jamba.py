"""What a step must read and compute for the Jamba block: the block
``reference/jamba.py`` computes, counted from the published keys
(``attn_layer_period``, ``attn_layer_offset``, ``mamba_expand``,
``mamba_d_state``, ``mamba_d_conv``, ``mamba_dt_rank``, ``intermediate_size``).

A layer's operator is a Mamba-1 mixer (an input projection to twice
``mamba_expand`` x the hidden size, a ``mamba_d_conv``-tap filter and a bias
a channel, a projection to the step size's bottleneck and to B and C with a
norm each, the step size's projection and bias, ``A_log``, the skip ``D``, an
output projection; it keeps ``mamba_d_conv - 1`` inputs and one
``[channels, mamba_d_state]`` float32 state a sequence and NO keys or
values) or grouped-query attention without positions (four projections;
keys and values in the paged pool). Every layer's network is the dense
SwiGLU (``num_experts`` 1). The embedding is tied: one table, read as rows
at the input and whole at the output.

A configuration's file names this module under ``"shapes"``; the interface
is ``shapes.py``'s.
"""

from __future__ import annotations

from .shapes import _BYTES, head_dim

_F32 = 4


def kinds(cfg: dict) -> list:
    """The operator of every layer."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attn" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def count(cfg: dict, what: str) -> int:
    return kinds(cfg).count(what)


def channels(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def attention_params(cfg: dict) -> int:
    """The four projections of one attention layer."""
    return cfg["hidden_size"] * head_dim(cfg) * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def mamba_matmul_params(cfg: dict) -> int:
    """The four matrices a token is multiplied with in one Mamba layer."""
    d, di = cfg["hidden_size"], channels(cfg)
    low = cfg["mamba_dt_rank"] + 2 * cfg["mamba_d_state"]
    return 2 * d * di + di * low + cfg["mamba_dt_rank"] * di + di * d


def mamba_params(cfg: dict) -> int:
    """One Mamba layer's mixer: the matrices, the filter and its bias, the
    three norms, the step size's bias, A_log and D."""
    di = channels(cfg)
    return (mamba_matmul_params(cfg) + (cfg["mamba_d_conv"] + 1) * di
            + cfg["mamba_dt_rank"] + 2 * cfg["mamba_d_state"]
            + di + cfg["mamba_d_state"] * di + di)


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def norm_params(cfg: dict) -> int:
    """Every layer's two norms and the one after the last layer."""
    return cfg["hidden_size"] * (2 * cfg["num_hidden_layers"] + 1)


def _layer_params(cfg: dict) -> int:
    return (count(cfg, "attn") * attention_params(cfg)
            + count(cfg, "mamba") * mamba_params(cfg)
            + cfg["num_hidden_layers"] * dense_params(cfg)
            + norm_params(cfg))


def _table_params(cfg: dict) -> int:
    tables = 1 if cfg.get("tie_word_embeddings", True) else 2
    return tables * cfg["vocab_size"] * cfg["hidden_size"]


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: every tensor in the served
    weight type (A_log, D and the biases too: widened where they are
    used)."""
    return ((_layer_params(cfg) + _table_params(cfg))
            * _BYTES[cfg["served_as"]["weights"]])


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one cached token: the attention layers' alone."""
    return (2 * count(cfg, "attn") * cfg["num_key_value_heads"]
            * head_dim(cfg) * _BYTES[cfg["served_as"]["kv"]])


def slot_state_bytes(cfg: dict) -> int:
    """What the Mamba layers keep for ONE sequence: the float32
    state-space state and the convolution's last inputs."""
    return count(cfg, "mamba") * channels(cfg) * (
        cfg["mamba_d_state"] * _F32 + (cfg["mamba_d_conv"] - 1)
        * _BYTES[cfg["served_as"]["activations"]])


def ssm_state_bytes(cfg: dict, slots: int) -> int:
    """The state of ``slots`` sequences (and the trash row the program
    keeps beside them)."""
    return (slots + 1) * slot_state_bytes(cfg)


def pool_bytes(cfg: dict) -> int:
    """The paged pool: pages x page size x the attention layers' keys and
    values."""
    flags = cfg["serve_flags"]
    return (kv_bytes_per_token(cfg) * int(flags["--num-pages"])
            * int(flags["--page-size"]))


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Bytes one decode step must move through HBM: every weight once (the
    tied table as the output head), ``batch`` rows of the table, the state
    of the ``batch`` sequences it updates, read and written, and the
    cached keys and values of the attention layers.

    NEEDED bytes, which is what a roofline is a share of. As of PR 46 the
    program's step MOVES more: it reads and writes the state of every
    slot, live or idle ([Mamba layers, slots + 1, ...], both ways), so at
    55 live rows of 128 ``decode_hbm_share`` reads lower than the step's
    share of the bandwidth by the bytes it actually moved (PERF.md section
    5 gives that beside it, from the traced run). A step that skips idle
    rows will raise the share for that reason; this count stays as it is,
    and only a ``benchmark`` PR changes it."""
    act = _BYTES[cfg["served_as"]["activations"]]
    return (weight_bytes(cfg) + batch * cfg["hidden_size"] * act
            + 2 * batch * slot_state_bytes(cfg)
            + contexts_sum * kv_bytes_per_token(cfg))


def _token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with over the whole stack."""
    return (count(cfg, "attn") * attention_params(cfg)
            + count(cfg, "mamba") * mamba_matmul_params(cfg)
            + cfg["num_hidden_layers"] * dense_params(cfg))


def _attention_flops(cfg: dict, pairs: float) -> float:
    """Multiply-adds of scores and values over ``pairs`` (query, key)
    pairs, in the attention layers."""
    return (count(cfg, "attn") * cfg["num_attention_heads"] * head_dim(cfg)
            * 2 * pairs)


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 of one decode step's matrix products. The
    recurrence's elementwise work is not in it."""
    mat = _token_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
    return 2.0 * (mat * batch + _attention_flops(cfg, contexts_sum))


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 of the matrix products that prefill one prompt
    (causal attention: half the square), with the output head applied at
    the last position only. The selective scan's elementwise work (about
    9 operations on each of channels x mamba_d_state state elements a token
    a Mamba layer, an exponential among them) is NOT in it: no matrix
    product expresses it, and a share of the MXU's peak built on this
    count says nothing of the scan."""
    n = float(prompt_tokens)
    return 2.0 * (_token_matmul_params(cfg) * n
                  + _attention_flops(cfg, n * (n + 1) / 2)
                  + cfg["vocab_size"] * cfg["hidden_size"])
