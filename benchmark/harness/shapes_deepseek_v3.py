"""What a step must read and compute for the DeepSeek-V3 block: the block
``reference/deepseek_v3.py`` computes, counted from the published keys
(``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``first_k_dense_replace``, ``n_routed_experts``,
``n_shared_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``intermediate_size``) and the configuration's ``share``.

Every layer's operator is latent attention: two query matrices through a
``q_lora_rank`` bottleneck, one matrix to the latent and the shared roped
key, the latent's expansion to keys and values (``W_UK``, ``W_UV``), the
output matrix, four norms. A token CACHES one row a layer, the normalised
latent and the roped key: ``kv_lora_rank + qk_rope_head_dim`` values and no
V. Its feed-forward is a dense SwiGLU network (the first
``first_k_dense_replace`` layers) or a shared SwiGLU expert that every token
passes beside routed experts, of which this chip HOLDS ``n_routed_experts``
(its share of the ``share.routed_experts`` the router scores), behind a
sigmoid router with a float32 selection bias. The output head is not tied.

A configuration's file names this module under ``"shapes"``; the interface
is ``shapes.py``'s. A token COMPUTES with those of its
``num_experts_per_tok`` experts that are held here, ``held / routed`` of
them at even routing; a decode step READS each held expert that at least one
of its rows is routed to, once. These functions get ``batch`` and nothing of
the routing, so both are expectations at even routing, each row choosing
independently: a step of ``batch`` rows touches
``held * (1 - (1 - k / routed) ** batch)`` held experts. (Group-limited
selection correlates a row's choices; the program's own count of touched
experts is a counter metric's to read.)

``kv_bytes_per_token`` is what the algorithm needs, 2 bytes a cached value.
``pool_bytes`` is what the program's pool takes: it pads a row to whole
128-lane tiles (576 -> 640 values), as the device's tiled layout would.
"""

from __future__ import annotations

from .shapes import _BYTES

_F32 = 4
LANES = 128


def dense_layers(cfg: dict) -> int:
    return min(int(cfg.get("first_k_dense_replace", 0)),
               int(cfg["num_hidden_layers"]))


def expert_layers(cfg: dict) -> int:
    return int(cfg["num_hidden_layers"]) - dense_layers(cfg)


def held(cfg: dict) -> int:
    """Routed experts whose weights are on this chip."""
    return int(cfg["n_routed_experts"])


def routed(cfg: dict) -> int:
    """Routed experts the router scores (the deployment's, on all chips)."""
    return int((cfg.get("share") or {}).get("routed_experts", held(cfg)))


def experts_per_token(cfg: dict) -> int:
    return int(cfg["num_experts_per_tok"])


def latent_row(cfg: dict) -> int:
    """Values one token caches in one layer."""
    return int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * ql + ql * h * (nope + rope) + d * (kl + rope)
            + kl * h * (nope + v) + h * v * d)


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """ONE expert of ``moe_intermediate_size``: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * routed(cfg)


def norm_params(cfg: dict) -> int:
    """Every layer's two norms and its two latent norms, and the one after
    the last layer."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"]
            * (2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) + d)


def experts_touched(cfg: dict, batch: float) -> float:
    """Held experts a step of ``batch`` rows is expected to read at even
    routing (see the module's note); all of them as ``batch`` grows."""
    return held(cfg) * (1.0 - (1.0 - experts_per_token(cfg) / routed(cfg))
                        ** batch)


def _matrix_bytes(cfg: dict, experts_read: float) -> float:
    """Every layer's matrices, norms, routers and shared experts with
    ``experts_read`` of each expert layer's held experts: all in the served
    weight type but the selection bias, which is float32."""
    w = _BYTES[cfg["served_as"]["weights"]]
    moe = expert_layers(cfg)
    shared = int(cfg.get("n_shared_experts") or 0)
    return ((cfg["num_hidden_layers"] * attention_params(cfg)
             + dense_layers(cfg) * dense_params(cfg)
             + moe * (router_params(cfg)
                      + (shared + experts_read) * expert_params(cfg))
             + norm_params(cfg)) * w
            + moe * routed(cfg) * _F32)


def _table_bytes(cfg: dict) -> int:
    """The input embedding and the output head, over the held vocabulary."""
    return (2 * cfg["vocab_size"] * cfg["hidden_size"]
            * _BYTES[cfg["served_as"]["activations"]])


def weight_bytes(cfg: dict) -> int:
    """Bytes the server holds for weights: every held expert of every
    layer, the shared experts, norms, routers, and the two tables."""
    return int(_matrix_bytes(cfg, held(cfg))) + _table_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent rows of one cached token, one a layer, as the algorithm
    needs them (unpadded)."""
    return (cfg["num_hidden_layers"] * latent_row(cfg)
            * _BYTES[cfg["served_as"]["kv"]])


def pool_bytes(cfg: dict) -> int:
    """The latent pool as the program holds it: pages x page size x layers
    x a row padded to whole 128-lane tiles."""
    flags = cfg["serve_flags"]
    padded = -(-latent_row(cfg) // LANES) * LANES
    return (cfg["num_hidden_layers"] * padded
            * _BYTES[cfg["served_as"]["kv"]] * int(flags["--num-pages"])
            * int(flags["--page-size"]))


def decode_step_bytes(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Bytes one decode step must read from HBM: every matrix outside the
    routed experts once (attention, dense networks, routers, shared
    experts, norms), the held experts its rows are expected to touch once
    each, the output head once and ``batch`` embedding rows, and the latent
    rows of the contexts."""
    act = _BYTES[cfg["served_as"]["activations"]]
    return (_matrix_bytes(cfg, experts_touched(cfg, batch))
            + cfg["vocab_size"] * cfg["hidden_size"] * act
            + batch * cfg["hidden_size"] * act
            + contexts_sum * kv_bytes_per_token(cfg))


def _token_matmul_params(cfg: dict) -> float:
    """Weights one token is multiplied with over the whole stack, on this
    chip: of its routed experts, the expected share that is held here."""
    shared = int(cfg.get("n_shared_experts") or 0)
    here = experts_per_token(cfg) * held(cfg) / routed(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense_layers(cfg) * dense_params(cfg)
            + expert_layers(cfg) * (router_params(cfg)
                                    + (shared + here) * expert_params(cfg)))


def _attention_flops(cfg: dict, pairs: float, absorbed: bool) -> float:
    """Multiply-adds of scores and values over ``pairs`` (query, key)
    pairs: expanded, a head's score is ``nope + rope`` wide and its value
    ``v``; absorbed (a decode step), both run over the latent, ``kv_lora +
    rope`` and ``kv_lora`` wide."""
    if absorbed:
        per_pair = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    else:
        per_pair = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"])
    return (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * per_pair * pairs)


def decode_step_flops(cfg: dict, batch: float, contexts_sum: float) -> float:
    """Multiply-adds x 2 one decode step needs: absorbed attention, so a
    row pays W_UK on its query and W_UV on its output (the same weights an
    expanded token pays on its latent) and the cached rows none."""
    mat = _token_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
    return 2.0 * (mat * batch
                  + _attention_flops(cfg, contexts_sum, absorbed=True))


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """Multiply-adds x 2 to prefill one prompt (expanded attention, causal:
    half the square), with the output head applied at the last position
    only."""
    n = float(prompt_tokens)
    return 2.0 * (_token_matmul_params(cfg) * n
                  + _attention_flops(cfg, n * (n + 1) / 2, absorbed=False)
                  + cfg["vocab_size"] * cfg["hidden_size"])
