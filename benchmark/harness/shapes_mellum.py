"""What a step must read and compute for the Mellum 2 block, the one
``reference/mellum.py`` computes: grouped-query attention of two kinds,
named layer by layer in the published ``layer_types`` (``sliding_attention``
sees the last ``sliding_window`` positions, ``full_attention`` all of
them), and in every layer a softmax router that sends each token to
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of width
``moe_intermediate_size`` (``intermediate_size`` is published and used by
no layer; no shared expert).

A configuration's file names this module under ``"shapes"``; the interface
is ``shapes.py``'s. Two expectations stand in for what the interface is
not told. The routing: a decode step READS each expert that at least one
of its rows is routed to, once; at even routing, each row choosing
independently, a step of ``batch`` rows touches ``E (1 - (1 - k/E)^batch)``
of them (``shapes_moe.py``'s rule, and its caveat: a skewed router touches
fewer, so a share built on this count reads HIGH). A reader that has the
program's own count of the experts a step touched hands it to
``decode_step_bytes`` as ``experts_read_share``, the share of a layer's
experts a token step touched (``layer_metrics/readers/
decode_hbm_share_routed.py``), and the rule is not used.
The contexts: a window layer reads ``min(context, sliding_window)``
keys of each row and the interface gives the contexts' SUM, so the MEAN
context stands in for the rows' own lengths; rows shorter than the window
beside rows longer than it read fewer keys than the mean says, so the
count is an upper bound of the window layers' reads (by under a tenth of a
token step's bytes at 9 window layers, 30 rows and a mean of 3,000).
"""

from __future__ import annotations

from .shapes import _BYTES, head_dim


def layer_counts(cfg: dict) -> tuple[int, int]:
    """(window layers, full layers) of the layers held."""
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - {
            "sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(kinds))} over "
                         f"{len(kinds)} of {cfg['num_hidden_layers']} layers")
    window = kinds.count("sliding_attention")
    return window, len(kinds) - window


def attention_params(cfg: dict) -> int:
    """Weights of one layer's four attention projections."""
    return cfg["hidden_size"] * head_dim(cfg) * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """Weights of ONE expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * int(cfg["num_experts"])


def experts_touched(cfg: dict, batch: float) -> float:
    """Experts a step of ``batch`` rows is expected to read at even
    routing, each row choosing independently; all of them as that
    grows."""
    e, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    return e * (1.0 - (1.0 - k / e) ** batch)


def _layer_bytes(cfg: dict, experts_read: float) -> float:
    """One layer's arrays with ``experts_read`` of its experts: the
    attention projections and the experts in the served weight type, the
    router and the two norms in the activation type."""
    served = cfg["served_as"]
    return ((attention_params(cfg) + experts_read * expert_params(cfg))
            * _BYTES[served["weights"]]
            + (router_params(cfg) + 2 * cfg["hidden_size"])
            * _BYTES[served["activations"]])


def _tables_bytes(cfg: dict, tables: int) -> int:
    return (tables * cfg["vocab_size"] * cfg["hidden_size"]
            * _BYTES[cfg["served_as"]["activations"]])


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: every expert of every layer,
    the embedding, the output head and every norm (the seeded tree's
    leaves sum to this)."""
    tables = 1 if cfg.get("tie_word_embeddings") else 2
    return int(cfg["num_hidden_layers"]
               * _layer_bytes(cfg, int(cfg["num_experts"]))
               + _tables_bytes(cfg, tables)
               + cfg["hidden_size"] * _BYTES[cfg["served_as"]["activations"]])


def kv_bytes_per_layer_token(cfg: dict) -> int:
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * _BYTES[cfg["served_as"]["kv"]])


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes a token keeps in the pool: keys and values in EVERY layer,
    the window layers too (the pool gives no page back while its sequence
    lives)."""
    return cfg["num_hidden_layers"] * kv_bytes_per_layer_token(cfg)


def pool_bytes(cfg: dict) -> int:
    flags = cfg["serve_flags"]
    return (kv_bytes_per_token(cfg) * int(flags["--num-pages"])
            * int(flags["--page-size"]))


def keys_read(cfg: dict, context: float) -> float:
    """Cached positions one query at ``context`` reads, summed over the
    layers: the window layers inside the window, the full layers all."""
    window, full = layer_counts(cfg)
    return (window * min(context, float(cfg["sliding_window"]))
            + full * context)


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float,
                      experts_read_share: "float | None" = None) -> float:
    """Bytes one decode step must read from HBM: attention projections,
    router, norms and output head once, ``experts_read_share`` of each
    layer's experts once each (where the caller has no count of them: as
    many as the step's rows are expected to touch at even routing), ``batch``
    embedding rows, and the cached keys and values each layer's kind of
    attention reads at the MEAN context (the module's note)."""
    act = _BYTES[cfg["served_as"]["activations"]]
    if experts_read_share is None:
        experts_read = experts_touched(cfg, batch)
    else:
        experts_read = experts_read_share * int(cfg["num_experts"])
    w = (cfg["num_hidden_layers"] * _layer_bytes(cfg, experts_read)
         + _tables_bytes(cfg, 1) + cfg["hidden_size"] * act)
    mean_ctx = contexts_sum / batch if batch else 0.0
    kv = batch * keys_read(cfg, mean_ctx) * kv_bytes_per_layer_token(cfg)
    return w + batch * cfg["hidden_size"] * act + kv


def _token_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with in one layer: attention, the
    router, and the experts it is routed to."""
    return (attention_params(cfg) + router_params(cfg)
            + int(cfg["num_experts_per_tok"]) * expert_params(cfg))


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 one decode step needs."""
    mat = (cfg["num_hidden_layers"] * _token_matmul_params(cfg)
           + cfg["vocab_size"] * cfg["hidden_size"])
    mean_ctx = contexts_sum / batch if batch else 0.0
    attn = (cfg["num_attention_heads"] * head_dim(cfg) * 2 * batch
            * keys_read(cfg, mean_ctx))
    return 2.0 * (mat * batch + attn)


def attended_pairs(n: float, window: "float | None") -> float:
    """(query, key) pairs of a causal pass over ``n`` positions, inside
    ``window`` if there is one."""
    if not window or n <= window:
        return n * (n + 1) / 2
    return window * (window + 1) / 2 + (n - window) * window


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 to prefill one prompt, with the output head
    applied at the last position only."""
    window, full = layer_counts(cfg)
    n = float(prompt_tokens)
    mat = cfg["num_hidden_layers"] * _token_matmul_params(cfg)
    pairs = (window * attended_pairs(n, float(cfg["sliding_window"]))
             + full * attended_pairs(n, None))
    attn = cfg["num_attention_heads"] * head_dim(cfg) * 2 * pairs
    return 2.0 * (mat * n + attn + cfg["vocab_size"] * cfg["hidden_size"])


def chunk_attention_flops(cfg: dict, history: int, tokens: int,
                          windowed: bool) -> float:
    """Multiply-adds x 2 of ONE layer's attention for a chunk of
    ``tokens`` queries behind ``history`` cached positions (the program's
    ``flash_chunk_attention``): q.k and p.v over the pairs the queries
    see."""
    w = float(cfg["sliding_window"]) if windowed else None
    pairs = (attended_pairs(float(history + tokens), w)
             - attended_pairs(float(history), w))
    return 2.0 * cfg["num_attention_heads"] * head_dim(cfg) * 2 * pairs


def chunk_attention_bytes(cfg: dict, history: int, tokens: int,
                          windowed: bool) -> float:
    """Bytes ONE layer's chunk attention must move: the queries in and
    the result out, and each cached key and value the chunk's queries see
    once."""
    act = _BYTES[cfg["served_as"]["activations"]]
    seen = float(history + tokens)
    if windowed:
        seen = min(seen, tokens + float(cfg["sliding_window"]) - 1)
    return (2 * tokens * cfg["num_attention_heads"] * head_dim(cfg) * act
            + seen * kv_bytes_per_layer_token(cfg))
