"""What a step must read and compute for the LFM2-MoE block: the block
``reference/lfm2_moe.py`` computes, counted from the published keys
(``layer_types``, ``num_dense_layers``, ``conv_L_cache``, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``intermediate_size``).

A layer's operator is a gated short convolution (an input projection to
three times the hidden size, one ``conv_L_cache``-tap filter a channel, an
output projection; it keeps ``conv_L_cache - 1`` gated inputs a sequence
and NO keys or values) or grouped-query attention (four projections and two
per-head norms; keys and values in the paged pool). Its feed-forward is a
dense SwiGLU network (the first ``num_dense_layers`` layers) or
``num_experts`` routed SwiGLU experts behind a sigmoid router with a
selection bias. The embedding is tied: one table, read as rows at the input
and whole at the output.

A configuration's file names this module under ``"shapes"``; the interface
is ``shapes.py``'s. The server HOLDS every expert; a token COMPUTES with the
experts it is routed to; a decode step READS each expert that at least one
of its rows is routed to, once. These functions get ``batch`` and nothing
of the routing, so the last is an expectation, as ``shapes_moe.py`` counts
it and says: at even routing, each row choosing ``k`` of ``E`` experts
independently, a step of ``batch`` rows touches
``E * (1 - (1 - k/E) ** batch)`` of them. A router that is not even touches
fewer, so a share of the roofline built on this count reads HIGH for a
skewed router (the program's own count of touched experts is a counter
metric's to read).
"""

from __future__ import annotations

from .shapes import _BYTES, head_dim

_F32 = 4


def kinds(cfg: dict) -> list:
    """(operator, feed-forward) of every layer."""
    dense = int(cfg.get("num_dense_layers", 0))
    return [("conv" if t == "conv" else "attn",
             "dense" if i < dense else "moe")
            for i, t in enumerate(cfg["layer_types"])]


def count(cfg: dict, what: str) -> int:
    """Layers whose operator or feed-forward is ``what``."""
    return sum(1 for kind in kinds(cfg) if what in kind)


def experts(cfg: dict) -> int:
    return int(cfg["num_experts"])


def experts_per_token(cfg: dict) -> int:
    return int(cfg["num_experts_per_tok"])


def attention_params(cfg: dict) -> int:
    """The four projections of one attention layer."""
    return cfg["hidden_size"] * head_dim(cfg) * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def conv_params(cfg: dict) -> int:
    """One conv layer's input projection (to the two gates and the input),
    its filters and its output projection."""
    d = cfg["hidden_size"]
    return 3 * d * d + cfg["conv_L_cache"] * d + d * d


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * experts(cfg)


def norm_params(cfg: dict) -> int:
    """Every layer's two norms, an attention layer's two per-head norms,
    and the one after the last layer."""
    d = cfg["hidden_size"]
    return (2 * d * cfg["num_hidden_layers"]
            + 2 * head_dim(cfg) * count(cfg, "attn") + d)


def experts_touched(cfg: dict, batch: float) -> float:
    """Experts a step of ``batch`` rows is expected to read at even
    routing (see the module's note); all of them as ``batch`` grows."""
    e, k = experts(cfg), experts_per_token(cfg)
    return e * (1.0 - (1.0 - k / e) ** batch)


def _matrix_bytes(cfg: dict, experts_read: float) -> float:
    """Every layer's matrices, norms and routers with ``experts_read`` of
    each expert layer's experts: all in the served weight type but the
    selection bias, which is float32."""
    w = _BYTES[cfg["served_as"]["weights"]]
    moe = count(cfg, "moe")
    return ((count(cfg, "attn") * attention_params(cfg)
             + count(cfg, "conv") * conv_params(cfg)
             + count(cfg, "dense") * dense_params(cfg)
             + moe * (router_params(cfg)
                      + experts_read * expert_params(cfg))
             + norm_params(cfg)) * w
            + moe * experts(cfg) * _F32)


def _table_bytes(cfg: dict) -> int:
    tables = 1 if cfg.get("tie_word_embeddings", True) else 2
    return (tables * cfg["vocab_size"] * cfg["hidden_size"]
            * _BYTES[cfg["served_as"]["activations"]])


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: every expert of every layer,
    every norm and router, and the one embedding table."""
    return int(_matrix_bytes(cfg, experts(cfg))) + _table_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one cached token: the attention layers' alone."""
    return (2 * count(cfg, "attn") * cfg["num_key_value_heads"]
            * head_dim(cfg) * _BYTES[cfg["served_as"]["kv"]])


def conv_state_bytes(cfg: dict, slots: int) -> int:
    """The conv layers' state of ``slots`` sequences (and the trash row the
    program keeps beside them)."""
    return (count(cfg, "conv") * (slots + 1) * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * _BYTES[cfg["served_as"]["activations"]])


def pool_bytes(cfg: dict) -> int:
    """The paged pool: pages x page size x the attention layers' keys and
    values, unpadded (a 64-wide head is stored 64 wide)."""
    flags = cfg["serve_flags"]
    return (kv_bytes_per_token(cfg) * int(flags["--num-pages"])
            * int(flags["--page-size"]))


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Bytes one decode step must read from HBM: every operator, dense
    network, router and norm once, the experts its rows are expected to
    touch once each, the embedding table once (as the output head; tied)
    and ``batch`` of its rows, each sequence's conv state (read and
    written), and the cached keys and values of the attention layers."""
    act = _BYTES[cfg["served_as"]["activations"]]
    state = (2 * batch * count(cfg, "conv") * (cfg["conv_L_cache"] - 1)
             * cfg["hidden_size"] * act)
    return (_matrix_bytes(cfg, experts_touched(cfg, batch))
            + cfg["vocab_size"] * cfg["hidden_size"] * act
            + batch * cfg["hidden_size"] * act + state
            + contexts_sum * kv_bytes_per_token(cfg))


def _token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with over the whole stack."""
    moe = count(cfg, "moe")
    return (count(cfg, "attn") * attention_params(cfg)
            + count(cfg, "conv") * conv_params(cfg)
            + count(cfg, "dense") * dense_params(cfg)
            + moe * (router_params(cfg)
                     + experts_per_token(cfg) * expert_params(cfg)))


def _attention_flops(cfg: dict, pairs: float) -> float:
    """Multiply-adds of scores and values over ``pairs`` (query, key)
    pairs, in the attention layers."""
    return (count(cfg, "attn") * cfg["num_attention_heads"] * head_dim(cfg)
            * 2 * pairs)


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 one decode step needs."""
    mat = _token_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
    return 2.0 * (mat * batch + _attention_flops(cfg, contexts_sum))


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 to prefill one prompt (causal: half the square),
    with the output head applied at the last position only."""
    n = float(prompt_tokens)
    return 2.0 * (_token_matmul_params(cfg) * n
                  + _attention_flops(cfg, n * (n + 1) / 2)
                  + cfg["vocab_size"] * cfg["hidden_size"])
