"""What a step must read and compute, from a configuration's shapes alone.

The inputs are the published ``config.json`` keys a configuration file
carries at its top level, plus how it is served (weight and KV types).
These functions are the yardstick for every share of a peak, so they count
only what the algorithm needs: each weight once per step, each cached key
and value the step attends to once, nothing for padding or recomputation.
"""

from __future__ import annotations

_BYTES = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer's matrix multiplications."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    attn = d * hd * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])
    return attn + 3 * d * f


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: layer matrices in the served
    weight type, embedding and output head in the activation type."""
    served = cfg["served_as"]
    layers = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
              * _BYTES[served["weights"]])
    tables = 1 if cfg.get("tie_word_embeddings") else 2
    return layers + (tables * cfg["vocab_size"] * cfg["hidden_size"]
                     * _BYTES[served["activations"]])


def kv_bytes_per_token(cfg: dict) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * _BYTES[cfg["served_as"]["kv"]])


def pool_bytes(cfg: dict) -> int:
    flags = cfg["serve_flags"]
    return (kv_bytes_per_token(cfg) * int(flags["--num-pages"])
            * int(flags["--page-size"]))


def attended(cfg: dict, context: float) -> float:
    """Keys one query position attends to at ``context`` tokens."""
    w = cfg.get("sliding_window")
    return min(context, w) if w else context


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Bytes one decode step (one token for each of ``batch`` sequences,
    whose contexts sum to ``contexts_sum``) must read from HBM: every
    layer matrix and the output head once, ``batch`` embedding rows, and
    the cached keys and values inside each sequence's window."""
    served = cfg["served_as"]
    act = _BYTES[served["activations"]]
    w = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
         * _BYTES[served["weights"]]
         + cfg["vocab_size"] * cfg["hidden_size"] * act)
    mean_ctx = contexts_sum / batch if batch else 0.0
    kv = batch * attended(cfg, mean_ctx) * kv_bytes_per_token(cfg)
    return w + batch * cfg["hidden_size"] * act + kv


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 one decode step needs."""
    mat = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
           + cfg["vocab_size"] * cfg["hidden_size"])
    mean_ctx = contexts_sum / batch if batch else 0.0
    attn = (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * 2 * batch * attended(cfg, mean_ctx))
    return 2.0 * (mat * batch + attn)


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 to prefill one prompt (causal: half the square),
    with the output head applied at the last position only."""
    mat = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    n = float(prompt_tokens)
    w = cfg.get("sliding_window")
    pairs = n * (n + 1) / 2 if not w or n <= w else (
        w * (w + 1) / 2 + (n - w) * w)
    attn = (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg) * 2 * pairs)
    return 2.0 * (mat * n + attn + cfg["vocab_size"] * cfg["hidden_size"])
