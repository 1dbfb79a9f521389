"""What a step must read and compute for a decoder whose feed-forward
layer is a mixture of experts: the block ``reference/moe.py`` computes
(pre-norm rotary GQA attention as in ``shapes.py``, then a softmax router
that sends each token to ``num_experts_per_tok`` of ``num_local_experts``
SwiGLU experts of width ``intermediate_size``, Mixtral's published keys).

A configuration's file names this module under ``"shapes"``; the interface
is ``shapes.py``'s. The server HOLDS every expert, a token COMPUTES with
the experts it is routed to, and a decode step READS each expert that at
least one of its rows is routed to, once. These functions get ``batch``
and nothing of the routing, so the last is an expectation: at even
routing, each row choosing ``k`` of ``E`` experts independently, a step of
``batch`` rows touches ``E * (1 - (1 - k/E) ** batch)`` of them. A router
that is not even touches fewer, so a share of the roofline built on this
count reads HIGH for a skewed router; a metric that reads the program's
own count of touched experts is a new reader's to add.
"""

from __future__ import annotations

from .shapes import (_BYTES, attended, head_dim,  # noqa: F401
                     kv_bytes_per_token, pool_bytes)


def experts(cfg: dict) -> int:
    return int(cfg["num_local_experts"])


def experts_per_token(cfg: dict) -> int:
    return int(cfg["num_experts_per_tok"])


def attention_params(cfg: dict) -> int:
    """Weights of one layer's four attention projections."""
    return cfg["hidden_size"] * head_dim(cfg) * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """Weights of ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * experts(cfg)


def experts_touched(cfg: dict, batch: float) -> float:
    """Experts a step of ``batch`` rows is expected to read at even
    routing (see the module's note); all of them as ``batch`` grows."""
    e, k = experts(cfg), experts_per_token(cfg)
    return e * (1.0 - (1.0 - k / e) ** batch)


def _layer_bytes(cfg: dict, experts_read: float) -> float:
    """One layer's matrices with ``experts_read`` of its experts: the
    attention projections and the experts in the served weight type, the
    router (never quantized) in the activation type."""
    served = cfg["served_as"]
    return ((attention_params(cfg) + experts_read * expert_params(cfg))
            * _BYTES[served["weights"]]
            + router_params(cfg) * _BYTES[served["activations"]])


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: every expert of every layer."""
    tables = 1 if cfg.get("tie_word_embeddings") else 2
    return int(cfg["num_hidden_layers"] * _layer_bytes(cfg, experts(cfg))
               + tables * cfg["vocab_size"] * cfg["hidden_size"]
               * _BYTES[cfg["served_as"]["activations"]])


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Bytes one decode step must read from HBM: attention projections,
    router and output head once, the experts the step's rows are expected
    to touch once each, ``batch`` embedding rows, and the cached keys and
    values inside each sequence's window."""
    act = _BYTES[cfg["served_as"]["activations"]]
    w = (cfg["num_hidden_layers"]
         * _layer_bytes(cfg, experts_touched(cfg, batch))
         + cfg["vocab_size"] * cfg["hidden_size"] * act)
    mean_ctx = contexts_sum / batch if batch else 0.0
    kv = batch * attended(cfg, mean_ctx) * kv_bytes_per_token(cfg)
    return w + batch * cfg["hidden_size"] * act + kv


def _token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with in one layer: attention, the
    router, and the experts it is routed to."""
    return (attention_params(cfg) + router_params(cfg)
            + experts_per_token(cfg) * expert_params(cfg))


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 one decode step needs."""
    mat = (cfg["num_hidden_layers"] * _token_matmul_params(cfg)
           + cfg["vocab_size"] * cfg["hidden_size"])
    mean_ctx = contexts_sum / batch if batch else 0.0
    attn = (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * 2 * batch * attended(cfg, mean_ctx))
    return 2.0 * (mat * batch + attn)


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 to prefill one prompt (causal: half the square),
    with the output head applied at the last position only."""
    mat = cfg["num_hidden_layers"] * _token_matmul_params(cfg)
    n = float(prompt_tokens)
    w = cfg.get("sliding_window")
    pairs = n * (n + 1) / 2 if not w or n <= w else (
        w * (w + 1) / 2 + (n - w) * w)
    attn = (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * 2 * pairs)
    return 2.0 * (mat * n + attn + cfg["vocab_size"] * cfg["hidden_size"])
