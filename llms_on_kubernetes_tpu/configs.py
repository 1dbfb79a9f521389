"""Model configurations and registry.

The reference stack configures models purely through the Helm ``models[]``
values list (reference vllm-models/helm-chart/values.yaml:1-27) and lets the
pulled vLLM image resolve the architecture from the HuggingFace repo. Here the
engine is in-repo, so the architecture configs live here: one frozen dataclass
covering the decoder families the BASELINE configs demand (Llama-3 8B/70B,
TinyLlama, Mistral-7B, Mixtral-8x7B MoE) plus the families the reference's
default values deploy (Gemma-3, Qwen — values.yaml:2-12) and Phi-3 (ramalama
local path, ramalama-models/README.md:102-106).

``from_hf_config`` maps a HuggingFace ``config.json`` to a ``ModelConfig`` so
``huggingfaceId``-driven deployment (the reference's contract) works without a
hand-written registry entry.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    attention_bias: bool = False            # Qwen2-style qkv bias
    sliding_window: Optional[int] = None    # Mistral-style SWA
    # Gemma-2/3 interleaved attention: layer i is GLOBAL iff (i+1) % pattern == 0,
    # else local (sliding_window). None => all layers use `sliding_window` as-is.
    sliding_window_pattern: Optional[int] = None
    rope_local_theta: Optional[float] = None  # theta for local layers (gemma3: 1e4)
    # attention logit scale = query_pre_attn_scalar**-0.5 if set, else head_dim**-0.5
    query_pre_attn_scalar: Optional[float] = None
    # MoE (Mixtral)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # the router (ops/moe.route): "softmax" over all experts (Mixtral,
    # Qwen3-MoE) or "sigmoid" per expert (LFM2-MoE); a selection bias that
    # picks experts and never weighs them (``use_expert_bias``); whether
    # the chosen scores are renormalised (/(sum + eps)); a route scale
    moe_router: str = "softmax"
    use_expert_bias: bool = False
    norm_topk_prob: bool = True
    moe_renorm_eps: float = 0.0
    routed_scaling_factor: float = 1.0
    # width of ONE routed expert where it differs from the dense network's
    # (``intermediate_size``); None => intermediate_size
    moe_intermediate_size: Optional[int] = None
    # layers [0, num_dense_layers) keep a dense network in an expert model
    num_dense_layers: int = 0
    # a stack of more than one kind of layer (LFM2, Jamba, Mellum): per layer
    # "conv" (a gated short convolution, state beside the KV pool),
    # "mamba" (a selective state-space mixer, state beside the KV pool),
    # "sliding_attention" (attention inside ``sliding_window``, rotated by
    # the unscaled ``rope_theta``) or "full_attention" (no window where the
    # stack names its window layers; ``rope_scaling`` is these layers').
    # None => every layer is attention, inside ``sliding_window`` if set
    layer_types: Optional[tuple] = None
    conv_L_cache: int = 3                   # taps of the short convolution
    conv_bias: bool = False
    # the Mamba-1 mixer of a "mamba" layer: channels ``mamba_expand`` x
    # hidden_size (``mamba_d_inner``), ``mamba_d_state`` states a channel,
    # a causal depthwise convolution of ``mamba_d_conv`` taps before the
    # scan, the step size through a ``mamba_dt_rank`` bottleneck
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    # False: attention without positional encoding of any kind (Jamba: the
    # state-space layers carry the order)
    use_rope: bool = True
    # latent attention (DeepSeek MLA; ``kv_lora_rank`` > 0 makes every
    # layer's operator "mla"): queries through a ``q_lora_rank`` bottleneck
    # to ``num_heads`` heads of ``qk_nope_head_dim`` un-roped and
    # ``qk_rope_head_dim`` roped dimensions (``head_dim`` is their sum);
    # keys and values from ONE normalised ``kv_lora_rank``-wide latent a
    # token plus one roped key shared by all heads, which is all the cache
    # keeps (``latent_width`` values a token a layer); values
    # ``v_head_dim`` wide
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # group-limited routing (DeepSeek-V3): the experts lie in ``n_group``
    # groups of consecutive experts, and a token chooses among the
    # ``topk_group`` groups whose two best selection scores sum highest
    n_group: int = 1
    topk_group: int = 1
    # SwiGLU experts of ``expert_width`` that every token passes through,
    # beside the routed ones (one network of n_shared_experts x the width)
    n_shared_experts: int = 0
    # one chip's share of an expert-parallel deployment: the expert stacks
    # hold experts [first_expert, first_expert + experts_held) of the
    # ``num_experts`` the router scores. None => every expert is held
    experts_held: Optional[int] = None
    first_expert: int = 0
    # activation / norm variants
    hidden_act: str = "silu"                # silu | gelu_tanh
    norm_style: str = "llama"               # llama: x*w ; gemma: x*(1+w)
    post_norms: bool = False                # gemma2/3 post-attn/post-mlp norms
    qk_norm: bool = False                   # qwen3 / gemma3 per-head q/k RMSNorm
    logit_softcap: Optional[float] = None   # gemma2
    attn_softcap: Optional[float] = None    # gemma2
    embedding_multiplier: Optional[float] = None  # gemma: sqrt(hidden_size)
    # excluded from __hash__ (dicts are unhashable; configs are jit static args)
    rope_scaling: Optional[dict] = dataclasses.field(default=None, hash=False)
    dtype: str = "bfloat16"
    # multimodal (gemma-3-style): a vision tower + projector produce
    # `vision.mm_tokens_per_image` soft tokens per image, substituted at
    # `image_token_id` positions in the prompt; the chat server splices
    # boi -> [boi, soft*N, eoi] (models/vision.py). Frozen dataclass, so
    # the config stays hashable for jit static args.
    vision: "Optional[Any]" = None          # models.vision.VisionConfig
    image_token_id: Optional[int] = None    # the soft-token placeholder id
    boi_token_id: Optional[int] = None      # begin-of-image marker
    eoi_token_id: Optional[int] = None      # end-of-image marker
    # Qwen3-VL interleaved multimodal RoPE: per-axis (t, h, w) frequency
    # channel counts; None => standard 1-D rope
    mrope_section: Optional[tuple] = None

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches for one token: the normalised
        latent and the shared roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def rope_dim(self) -> int:
        """Dimensions of a head that are rotated."""
        return self.qk_rope_head_dim if self.is_mla else self.head_dim

    @property
    def cache_row(self) -> tuple:
        """(heads, width) of what one token keeps in one layer's pages: K
        and V heads, or the one latent row of a latent layer (no V), padded
        with zeros to whole 128-lane tiles (576 -> 640: the TPU's tiled
        layout would pad a page's rows to that anyway, and a compiler left
        to hide the padding re-lays the whole pool out around every
        step)."""
        if self.is_mla:
            return 1, -(-self.latent_width // 128) * 128
        return self.num_kv_heads, self.head_dim

    @property
    def num_held_experts(self) -> int:
        """Experts whose weights are here (``experts_held``)."""
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    def layer_kind(self, i: int) -> tuple:
        """(operator, feed-forward) of layer ``i``: ("attn" | "swa" |
        "conv" | "mamba" | "mla", "dense" | "moe"). "swa" is attention
        inside the window, a kind of its own so that a run of such layers
        is traced with the window as a Python int."""
        kind = None if self.layer_types is None else self.layer_types[i]
        op = ("mla" if self.is_mla
              else kind if kind in ("conv", "mamba")
              else "swa" if kind == "sliding_attention" else "attn")
        ff = "moe" if self.is_moe and i >= self.num_dense_layers else "dense"
        return op, ff

    def attn_window(self, op: str) -> Optional[int]:
        """The window of an attention layer of operator ``op``, static: a
        "swa" layer's is ``sliding_window``; an "attn" layer has none in a
        stack that names its layers, and ``sliding_window`` (every layer's)
        in one that does not."""
        if op == "swa" or self.layer_types is None:
            return self.sliding_window
        return None

    @property
    def names_window_layers(self) -> bool:
        """The stack spells its window layers in ``layer_types``: runs of
        "swa" beside runs of "attn", each kind with a rotary scheme of its
        own."""
        return any(op == "swa" for op, *_ in self.layer_runs)

    @property
    def num_window_layers(self) -> int:
        """Layers that can never read a cached row again once it is
        ``sliding_window`` positions behind (what
        llm_attn_window_rows_total counts by)."""
        if self.sliding_window is None or self.is_mla:
            return 0
        if self.sliding_window_pattern is not None:
            return sum(1 for i in range(self.num_layers)
                       if (i + 1) % self.sliding_window_pattern)
        return sum(n for op, _ff, _i, n in self.layer_runs
                   if op in ("attn", "swa")
                   and self.attn_window(op) is not None)

    @functools.cached_property
    def layer_runs(self) -> tuple:
        """The stack as runs of one kind: ((operator, feed-forward, first
        layer, count), ...). A stack of one kind is one run."""
        runs: list = []
        for i in range(self.num_layers):
            kind = self.layer_kind(i)
            if runs and runs[-1][:2] == kind:
                runs[-1] = (*kind, runs[-1][2], runs[-1][3] + 1)
            else:
                runs.append((*kind, i, 1))
        return tuple(runs)

    @property
    def attention_summary(self) -> Optional[str]:
        """One line for the start-up log of a stack that names its window
        layers: the pattern, the window, each kind's rotary scheme. None
        for every other model."""
        if not self.names_window_layers:
            return None
        runs = [(op, n) for op, _ff, _i, n in self.layer_runs
                if op in ("attn", "swa")]
        pattern = " ".join(f"{n}x{'sliding' if op == 'swa' else 'full'}"
                           for op, n in runs)
        scaling = self.rope_scaling or {}
        kind = scaling.get("rope_type", scaling.get("type", "default"))
        full = f"{kind} rotary, theta {self.rope_theta:g}"
        if kind == "yarn":
            full += (f", factor {scaling['factor']} over "
                     f"{scaling['original_max_position_embeddings']}, "
                     f"attention_factor {scaling.get('attention_factor')} "
                     f"(served as its square on the softmax scale)")
        return (f"{self.name}: attention layers {pattern}; sliding layers "
                f"see {self.sliding_window} positions (a static window in "
                f"every attention kernel), default rotary, theta "
                f"{self.rope_local_theta or self.rope_theta:g}; full layers "
                f"see all, {full}")

    @property
    def num_attn_layers(self) -> int:
        """Layers that keep keys and values (or a latent row of them):
        what the KV pool is sized by."""
        return sum(n for op, _ff, _i, n in self.layer_runs
                   if op not in ("conv", "mamba"))

    @property
    def num_conv_layers(self) -> int:
        """Layers that keep a short-convolution state for each slot."""
        return sum(n for op, _ff, _i, n in self.layer_runs if op == "conv")

    @property
    def num_mamba_layers(self) -> int:
        """Layers that keep a convolution window and a state-space state
        for each slot."""
        return sum(n for op, _ff, _i, n in self.layer_runs if op == "mamba")

    @property
    def keeps_slot_state(self) -> bool:
        """Some layer keeps per-slot state beside the KV pool: a sequence
        cannot be continued from its cached pages alone."""
        return bool(self.num_conv_layers or self.num_mamba_layers)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def num_moe_layers(self) -> int:
        return sum(n for _op, ff, _i, n in self.layer_runs if ff == "moe")

    @property
    def num_params(self) -> int:
        """Approximate parameter count (for memory budgeting)."""
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        attn = d * self.q_dim * 2 + d * self.kv_dim * 2
        if self.is_mla:
            h = self.num_heads
            attn = (d * self.q_lora_rank + self.q_lora_rank * h * self.head_dim
                    + d * self.latent_width + self.kv_lora_rank * h
                    * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        conv = 4 * d * d + self.conv_L_cache * d
        di, ns, r = self.mamba_d_inner, self.mamba_d_state, self.mamba_dt_rank
        # in/out projections, taps and bias, x_proj and its three norms,
        # dt_proj and bias, A_log, D
        mamba = (3 * d * di + (self.mamba_d_conv + 1) * di
                 + (di + 1) * (r + 2 * ns) + (r + 1) * di + ns * di + di)
        moe = (3 * d * self.expert_width * (self.num_held_experts
                                            + self.n_shared_experts)
               + d * self.num_experts)
        total = v * d * (1 if self.tie_word_embeddings else 2)
        for op, ff, _first, n in self.layer_runs:
            total += n * ({"conv": conv, "mamba": mamba}.get(op, attn)
                          + (moe if ff == "moe" else 3 * d * f))
        return total


# ---------------------------------------------------------------------------
# Registry. Keys are the short `modelName`s a chart would use; aliases map
# HuggingFace repo ids onto them.
# ---------------------------------------------------------------------------

REGISTRY: dict[str, ModelConfig] = {}
ALIASES: dict[str, str] = {}
# registry name -> first registered HF repo id, original case (repo ids are
# case-sensitive on the Hub; ALIASES keys are lowercased for lookup only)
CANONICAL_HF_IDS: dict[str, str] = {}


def _register(cfg: ModelConfig, *hf_ids: str) -> ModelConfig:
    REGISTRY[cfg.name] = cfg
    for hf_id in hf_ids:
        ALIASES[hf_id.lower()] = cfg.name
    if hf_ids:
        CANONICAL_HF_IDS[cfg.name] = hf_ids[0]
    return cfg


def hf_repo_for(model_ref: str) -> Optional[str]:
    """Canonical HF repo id for a model reference, or None.

    A ref shaped like a repo id (exactly ``namespace/name``, no path
    syntax) is returned as-is; a registry name resolves through its first
    registered alias. Filesystem-looking refs (absolute paths, ``./``,
    deeper nesting) return None — a missing local checkpoint must surface
    as a mount problem, not as a bogus Hub repo-id error."""
    import re

    if model_ref.startswith((".", "/", "~")):
        return None
    # known aliases first, so a non-canonical-case repo id maps onto the
    # canonical cache entry instead of re-downloading under a duplicate dir
    key = model_ref if model_ref in REGISTRY else ALIASES.get(model_ref.lower())
    if key:
        return CANONICAL_HF_IDS.get(key)
    if re.fullmatch(r"[\w.\-]+/[\w.\-]+", model_ref):
        return model_ref
    return None


LLAMA3_ROPE_SCALING = {
    "rope_type": "llama3",
    "factor": 8.0,
    "low_freq_factor": 1.0,
    "high_freq_factor": 4.0,
    "original_max_position_embeddings": 8192,
}

_register(
    ModelConfig(
        "llama-3-8b",
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_position_embeddings=8192,
    ),
    "meta-llama/Meta-Llama-3-8B", "meta-llama/Meta-Llama-3-8B-Instruct",
)

_register(
    ModelConfig(
        "llama-3-70b",
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_position_embeddings=8192,
    ),
    "meta-llama/Meta-Llama-3-70B", "meta-llama/Meta-Llama-3-70B-Instruct",
)

_register(
    ModelConfig(
        "llama-3.1-8b",
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=500000.0, max_position_embeddings=131072,
        rope_scaling=LLAMA3_ROPE_SCALING,
    ),
    "meta-llama/Llama-3.1-8B", "meta-llama/Llama-3.1-8B-Instruct",
)

_register(
    ModelConfig(
        "tinyllama-1.1b",
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=32, num_kv_heads=4, head_dim=64,
        rope_theta=10000.0, max_position_embeddings=2048,
    ),
    "TinyLlama/TinyLlama-1.1B-Chat-v1.0",
)

_register(
    ModelConfig(
        "mistral-7b",
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=10000.0, max_position_embeddings=32768,
        sliding_window=4096,
    ),
    "mistralai/Mistral-7B-v0.1", "mistralai/Mistral-7B-Instruct-v0.1",
)

# v0.2+ dropped sliding-window attention and raised rope_theta to 1e6.
_register(
    ModelConfig(
        "mistral-7b-v0.2",
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=32768,
    ),
    "mistralai/Mistral-7B-Instruct-v0.2", "mistralai/Mistral-7B-Instruct-v0.3",
)

_register(
    ModelConfig(
        "mixtral-8x7b",
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=32768,
        num_experts=8, num_experts_per_tok=2,
    ),
    "mistralai/Mixtral-8x7B-v0.1", "mistralai/Mixtral-8x7B-Instruct-v0.1",
)

_register(
    ModelConfig(
        "phi-3-mini",
        vocab_size=32064, hidden_size=3072, intermediate_size=8192,
        num_layers=32, num_heads=32, num_kv_heads=32, head_dim=96,
        rope_theta=10000.0, max_position_embeddings=4096,
        sliding_window=2047,
    ),
    "microsoft/Phi-3-mini-4k-instruct",
)

_register(
    ModelConfig(
        "qwen2.5-7b",
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=32768,
        attention_bias=True, tie_word_embeddings=False,
    ),
    "Qwen/Qwen2.5-7B-Instruct",
)

_register(
    ModelConfig(
        "qwen3-8b",
        vocab_size=151936, hidden_size=4096, intermediate_size=12288,
        num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=40960,
        qk_norm=True, rms_norm_eps=1e-6,
    ),
    "Qwen/Qwen3-8B",
)

# Text backbone family of the reference's second default model
# (Qwen3-VL-30B, reference vllm-models/helm-chart/values.yaml:7-12): the
# Qwen3-MoE decoder (128 experts, top-8, qk-norm). Deploying the FULL
# Qwen3-VL (vision tower + deepstack + mrope) goes through its
# config.json via from_hf_config (model_type qwen3_vl_moe).
_register(
    ModelConfig(
        "qwen3-30b-a3b",
        vocab_size=151936, hidden_size=2048, intermediate_size=768,
        num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=40960,
        qk_norm=True, rms_norm_eps=1e-6,
        num_experts=128, num_experts_per_tok=8,
    ),
    "Qwen/Qwen3-30B-A3B",  # (2507 revision has different rope/context — use its config.json)
)

_register(
    ModelConfig(
        "gemma-2-9b",
        vocab_size=256000, hidden_size=3584, intermediate_size=14336,
        num_layers=42, num_heads=16, num_kv_heads=8, head_dim=256,
        rope_theta=10000.0, max_position_embeddings=8192,
        hidden_act="gelu_tanh", norm_style="gemma", post_norms=True,
        logit_softcap=30.0, attn_softcap=50.0,
        embedding_multiplier=3584 ** 0.5, tie_word_embeddings=True,
        rms_norm_eps=1e-6,
        # alternating local(4096)/global layers; query scale 1/sqrt(256)
        sliding_window=4096, sliding_window_pattern=2, rope_local_theta=10000.0,
        query_pre_attn_scalar=256.0,
    ),
    "google/gemma-2-9b-it",
)

# The reference's first default model is gemma-3-27b-it
# (reference vllm-models/helm-chart/values.yaml:2-6).
_register(
    ModelConfig(
        "gemma-3-27b",
        vocab_size=262208, hidden_size=5376, intermediate_size=21504,
        num_layers=62, num_heads=32, num_kv_heads=16, head_dim=128,
        rope_theta=1000000.0, max_position_embeddings=131072,
        hidden_act="gelu_tanh", norm_style="gemma", post_norms=True,
        qk_norm=True, embedding_multiplier=5376 ** 0.5,
        tie_word_embeddings=True, rms_norm_eps=1e-6,
        # 5 local (SWA-1024, theta 1e4) layers per global layer;
        # query scale 1/sqrt(hidden/num_heads) = 1/sqrt(168)
        sliding_window=1024, sliding_window_pattern=6, rope_local_theta=10000.0,
        query_pre_attn_scalar=5376.0 / 32,
        # global layers use linearly-scaled RoPE (factor 8); local layers
        # keep unscaled rope_local_theta
        rope_scaling={"rope_type": "linear", "factor": 8.0},
    ),
    "google/gemma-3-27b-it",
)

# LFM2-MoE (LiquidAI): a stack of three kinds of layer. The operator is a
# gated short convolution (30 of 40 layers; per-slot state of the last
# conv_L_cache - 1 gated inputs, no keys or values) or GQA attention with
# 64-wide heads and q/k norms; the first two layers keep a dense network,
# the rest route every token to 4 of 64 sigmoid-scored experts.
_LFM2_PERIOD = ("conv", "conv", "full_attention", "conv")
_register(
    ModelConfig(
        "lfm2-24b-a2b",
        vocab_size=65536, hidden_size=2048, intermediate_size=11776,
        num_layers=40, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=1000000.0, max_position_embeddings=128000,
        qk_norm=True, tie_word_embeddings=True,
        layer_types=_LFM2_PERIOD * 10, conv_L_cache=3,
        num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
        moe_intermediate_size=1536, moe_router="sigmoid",
        use_expert_bias=True, norm_topk_prob=True, moe_renorm_eps=1e-6,
        routed_scaling_factor=1.0,
    ),
    "LiquidAI/LFM2-24B-A2B",
)



def _jamba_layers(n: int, period: int, offset: int) -> tuple:
    """The Jamba family's rule: layer i is attention iff i % period ==
    offset, else a Mamba mixer."""
    return tuple("full_attention" if i % period == offset else "mamba"
                 for i in range(n))


# Jamba2-3B (AI21): 26 Mamba-1 layers (per-slot state beside the KV pool:
# the convolution's last 3 inputs and a [5120, 16] float32 state-space
# state) and 2 multi-query attention layers (7 and 21: one 128-wide KV head
# under 20 query heads) WITHOUT positional encoding; every layer's network
# is the dense SwiGLU (num_experts 1); tied embeddings. head_dim = 2560 / 20
# is the family's convention, not a key of the published config.
_register(
    ModelConfig(
        "jamba2-3b",
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_layers=28, num_heads=20, num_kv_heads=1, head_dim=128,
        rms_norm_eps=1e-6, max_position_embeddings=262144,
        tie_word_embeddings=True, use_rope=False,
        layer_types=_jamba_layers(28, 14, 7),
        mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
    ),
    "ai21labs/AI21-Jamba2-3B",
)

def _mellum_layers(n: int) -> tuple:
    """Mellum 2's published ``layer_types``: layer i is full attention iff
    (i + 1) % 4 == 0, else attention inside the window."""
    return tuple("full_attention" if (i + 1) % 4 == 0
                 else "sliding_attention" for i in range(n))


# Mellum2-12B-A2.5B (JetBrains): three layers of attention inside a window
# of 1,024 to one of full attention (32 query heads over 4 KV heads of 128,
# no q/k norms); both kinds rotate all 128 dimensions with theta 500,000,
# the window layers plainly and the full layers with YaRN (factor 16 over
# 8,192; attention_factor on cosine and sine, served as its square on the
# softmax scale); every layer routes each token to 8 of 64 softmax-scored
# experts of width 896, renormalised, with no shared expert and no bias
# (intermediate_size 7,168 is published and used by no layer). The
# multi-token-prediction head the family describes is not in the config
# and is not built.
MELLUM_YARN = {
    "rope_type": "yarn", "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782,
}
_register(
    ModelConfig(
        "mellum2-12b",
        vocab_size=98304, hidden_size=2304, intermediate_size=7168,
        num_layers=28, num_heads=32, num_kv_heads=4, head_dim=128,
        rope_theta=500000.0, rms_norm_eps=1e-6,
        max_position_embeddings=131072, rope_scaling=MELLUM_YARN,
        sliding_window=1024, layer_types=_mellum_layers(28),
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
        moe_router="softmax", norm_topk_prob=True,
    ),
    "JetBrains/Mellum2-12B-A2.5B-Instruct",
)

# DeepSeek-V3: latent attention (MLA) in every layer, three leading dense
# layers, then 256 sigmoid-scored experts in 8 groups (a token chooses 8
# experts among its 4 best groups; a selection bias; the chosen scores
# renormalised and scaled by 2.5) beside one shared expert; YaRN rope over
# the 64 roped dimensions. The multi-token-prediction module of the
# published checkpoint is a draft head and is not built.
DEEPSEEK_YARN = {
    "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
    "beta_fast": 32, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
}
_register(
    ModelConfig(
        "deepseek-v3",
        vocab_size=129280, hidden_size=7168, intermediate_size=18432,
        num_layers=61, num_heads=128, num_kv_heads=128, head_dim=192,
        rope_theta=10000.0, rms_norm_eps=1e-6,
        max_position_embeddings=163840, rope_scaling=DEEPSEEK_YARN,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        num_dense_layers=3, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=2048, moe_router="sigmoid",
        use_expert_bias=True, norm_topk_prob=True, moe_renorm_eps=1e-20,
        routed_scaling_factor=2.5, n_group=8, topk_group=4,
        n_shared_experts=1,
    ),
    "deepseek-ai/DeepSeek-V3",
)

# Tiny configs for tests / local CPU smoke runs.
_register(
    ModelConfig(
        "debug-tiny",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512,
    ),
)
_register(
    ModelConfig(
        # debug-tiny sized, but the vocab covers the ByteTokenizer's full
        # id range (256 bytes + BOS + EOS) so EOS is SAMPLEABLE — grammar-
        # constrained smoke runs (response_format/tool_choice) need the
        # model able to terminate a constrained generation
        "debug-byte",
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512,
    ),
)
_register(
    ModelConfig(
        "debug-gemma",
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512,
        hidden_act="gelu_tanh", norm_style="gemma", post_norms=True,
        qk_norm=True, embedding_multiplier=8.0, tie_word_embeddings=True,
        sliding_window=8, sliding_window_pattern=2, rope_local_theta=10000.0,
        rope_theta=1000000.0, query_pre_attn_scalar=24.0,
    ),
)
_register(
    ModelConfig(
        "debug-moe",
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512, num_experts=4, num_experts_per_tok=2,
    ),
)
_register(
    ModelConfig(
        # lfm2-24b-a2b's three kinds of layer at a size the CPU tests hold:
        # conv + dense, then conv + experts, then attention + experts
        "debug-lfm2",
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512, qk_norm=True, tie_word_embeddings=True,
        layer_types=("conv", "conv", "full_attention"), conv_L_cache=3,
        num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=48, moe_router="sigmoid",
        use_expert_bias=True, norm_topk_prob=True, moe_renorm_eps=1e-6,
    ),
)
_register(
    ModelConfig(
        # jamba2-3b's stack at a size the CPU tests hold: Mamba, Mamba,
        # position-free multi-query attention, Mamba (three runs)
        "debug-jamba",
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=4, num_kv_heads=1, head_dim=16,
        rms_norm_eps=1e-6, max_position_embeddings=512,
        tie_word_embeddings=True, use_rope=False,
        layer_types=_jamba_layers(4, 4, 2),
        mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
    ),
)
_register(
    ModelConfig(
        # mellum2-12b's stack at a size the CPU tests hold: two periods of
        # three window layers (8 positions) and a full layer with YaRN
        # (factor 4 over 32), 8 softmax-routed experts top-2 in every layer
        "debug-mellum",
        vocab_size=258, hidden_size=64, intermediate_size=128,
        num_layers=8, num_heads=4, num_kv_heads=2, head_dim=16,
        rope_theta=10000.0, rms_norm_eps=1e-6, max_position_embeddings=512,
        rope_scaling={"rope_type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 32,
                      "beta_fast": 32, "beta_slow": 1,
                      "attention_factor": 1.1386294361119891},
        sliding_window=8, layer_types=_mellum_layers(8),
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        moe_router="softmax", norm_topk_prob=True,
    ),
)
_register(
    ModelConfig(
        # deepseek-v3's mechanisms at a size the CPU tests hold: latent
        # attention with both ranks and YaRN rope, two dense layers, then
        # 8 experts in 2 groups (top-2 of the best group) beside a shared
        # expert; a vocabulary wide enough to be sliced and still hold the
        # byte tokenizer's 258 ids
        # ("debug-deepseek@0,2-3+experts0-3+vocab0-259")
        "debug-deepseek",
        vocab_size=300, hidden_size=64, intermediate_size=128,
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=24,
        rope_theta=10000.0, rms_norm_eps=1e-6, max_position_embeddings=512,
        rope_scaling={"type": "yarn", "factor": 4,
                      "original_max_position_embeddings": 64,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
                      "mscale_all_dim": 1.0},
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=48, moe_router="sigmoid",
        use_expert_bias=True, norm_topk_prob=True, moe_renorm_eps=1e-20,
        routed_scaling_factor=2.5, n_group=2, topk_group=1,
        n_shared_experts=1,
    ),
)


def _debug_mm() -> ModelConfig:
    from llms_on_kubernetes_tpu.models.vision import VisionConfig

    return ModelConfig(
        "debug-mm",
        vocab_size=300, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512,
        vision=VisionConfig(hidden_size=16, intermediate_size=32,
                            num_layers=1, num_heads=2, image_size=16,
                            patch_size=4, mm_tokens_per_image=4),
        image_token_id=260, boi_token_id=258, eoi_token_id=259,
    )


_register(_debug_mm())


def _debug_qwen_mm() -> ModelConfig:
    from llms_on_kubernetes_tpu.models.vision import VisionConfig

    return ModelConfig(
        "debug-qwen-mm",
        vocab_size=300, hidden_size=64, intermediate_size=128,
        num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
        max_position_embeddings=512, qk_norm=True,
        mrope_section=(3, 3, 2),
        vision=VisionConfig(hidden_size=16, intermediate_size=32,
                            num_layers=2, num_heads=2, image_size=16,
                            patch_size=4, family="qwen3vl",
                            temporal_patch_size=2, spatial_merge_size=2,
                            out_hidden_size=64, num_grid_per_side=4,
                            deepstack_indexes=(0,),
                            mm_tokens_per_image=4),
        image_token_id=260, boi_token_id=258, eoi_token_id=259,
    )


_register(_debug_qwen_mm())


def cut_to_layers(cfg: ModelConfig, layers: tuple, name: str) -> ModelConfig:
    """``cfg`` with only the published ``layers`` (ascending indices): what
    ONE stage of a pipeline over depth holds. Every width stays; of the
    leading dense layers those among ``layers`` stay dense. Only for a
    model whose stack is more than one run of layers of one kind (conv and
    attention layers, or dense layers before expert layers): its cut has
    to keep every kind, so it is named layer by layer; no other entry can
    be served in part."""
    if len(cfg.layer_runs) == 1:
        raise KeyError(f"{name!r}: {cfg.name} has one kind of layer and is "
                       f"served whole")
    if not layers or list(layers) != sorted(set(layers)) \
            or not 0 <= layers[0] <= layers[-1] < cfg.num_layers:
        raise KeyError(f"{name!r}: layers must be ascending indices below "
                       f"{cfg.num_layers}")
    return dataclasses.replace(
        cfg, name=name, num_layers=len(layers),
        num_dense_layers=sum(1 for i in layers if i < cfg.num_dense_layers),
        layer_types=None if cfg.layer_types is None
        else tuple(cfg.layer_types[i] for i in layers))


def cut_to_share(cfg: ModelConfig, name: str, experts: "tuple | None" = None,
                 vocab: "tuple | None" = None) -> ModelConfig:
    """``cfg`` with ONE chip's share of each layer in a deployment that
    divides a layer over several chips: the routed ``experts`` (first,
    last) it holds, of the ``num_experts`` the router still scores, and the
    rows (first, last) of the ``vocab``ulary. A sliced vocabulary is a
    smaller vocabulary; a slice that starts past row 0 would matter to a
    checkpoint alone, and none is loaded for such a model."""
    if experts is not None:
        lo, hi = experts
        if not (cfg.is_moe and 0 <= lo <= hi < cfg.num_experts):
            raise KeyError(f"{name!r}: experts must lie in 0-"
                           f"{cfg.num_experts - 1} of a routed model")
        cfg = dataclasses.replace(cfg, experts_held=hi - lo + 1,
                                  first_expert=lo)
    if vocab is not None:
        lo, hi = vocab
        if lo != 0 or not 0 < hi < cfg.vocab_size:
            raise KeyError(f"{name!r}: a vocabulary slice is rows 0-<last> "
                           f"below {cfg.vocab_size}")
        cfg = dataclasses.replace(cfg, vocab_size=hi + 1)
    return dataclasses.replace(cfg, name=name)


def _span(text: str) -> tuple:
    lo, _, hi = text.partition("-")
    return int(lo), int(hi or lo)


def get_config(name: str) -> ModelConfig:
    """A registry entry by name or alias, or one chip's part of it:
    ``<entry>@<layers>[+experts<a>-<b>][+vocab0-<b>]``. ``<layers>``
    (``3-10``, ``0,3-10``) cuts the entry to those published layers
    (``cut_to_layers``): the depth one chip holds of a model that a
    pipeline spreads over several. ``+experts0-15`` and ``+vocab0-16159``
    give it this chip's share of each layer (``cut_to_share``):
    ``deepseek-v3@0,3-7+experts0-15+vocab0-16159``."""
    base, at, spec = name.partition("@")
    if at:
        layers, *shares = spec.split("+")
        cfg = get_config(base)
        try:
            cfg = cut_to_layers(cfg, tuple(
                i for part in layers.split(",")
                for lo, hi in [_span(part)]
                for i in range(lo, hi + 1)), name)
            share = {}
            for part in shares:
                key = next(k for k in ("experts", "vocab")
                           if part.startswith(k) and k not in share)
                share[key] = _span(part[len(key):])
        except (ValueError, StopIteration):
            raise KeyError(f"{name!r}: a cut is written <entry>@0,3-10 or "
                           f"<entry>@0,3-7+experts0-15+vocab0-16159") from None
        return cut_to_share(cfg, name, **share)
    key = name if name in REGISTRY else ALIASES.get(name.lower(), name)
    if key not in REGISTRY:
        raise KeyError(
            f"unknown model config {name!r}; known: {sorted(REGISTRY)} "
            f"(or pass a HuggingFace config.json via from_hf_config)"
        )
    return REGISTRY[key]


# ---------------------------------------------------------------------------
# HuggingFace config.json → ModelConfig
# ---------------------------------------------------------------------------

def from_hf_config(hf: dict | str, name: str = "hf-model") -> ModelConfig:
    """Build a ModelConfig from a HuggingFace ``config.json`` dict or path."""
    if isinstance(hf, str):
        with open(hf) as f:
            hf = json.load(f)
    outer = hf  # multimodal wrappers keep vision/image-token info out here
    # gemma3 wraps the text config
    if "text_config" in hf and isinstance(hf["text_config"], dict):
        merged = dict(hf["text_config"])
        merged.setdefault("model_type", hf.get("model_type", ""))
        hf = merged
    model_type = hf.get("model_type", "llama")
    hidden = int(hf["hidden_size"])
    heads = int(hf["num_attention_heads"])
    head_dim = int(hf.get("head_dim") or hidden // heads)
    kw: dict[str, Any] = dict(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=int(hf.get("num_key_value_heads") or heads),
        head_dim=head_dim,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        max_position_embeddings=int(hf.get("max_position_embeddings", 8192)),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        sliding_window=hf.get("sliding_window"),
    )
    scaling = hf.get("rope_scaling")
    if isinstance(scaling, dict):
        kind = scaling.get("rope_type", scaling.get("type"))
        if "mrope_section" in scaling:
            # Qwen3-VL multimodal rope: unscaled frequencies + interleaved
            # 3-axis application (ops/rope.py apply_mrope). A SCALING
            # scheme riding alongside (yarn long-context variants) is not
            # expressed — fail fast like every other dropped scheme.
            if kind not in (None, "default"):
                raise NotImplementedError(
                    f"rope_scaling type {kind!r} combined with "
                    f"mrope_section is not supported yet")
            if not scaling.get("mrope_interleaved", True):
                raise NotImplementedError(
                    "non-interleaved (sectioned) mrope is not supported "
                    "yet; only mrope_interleaved=true")
            kw["mrope_section"] = tuple(int(x) for x in scaling["mrope_section"])
        elif kind in ("llama3", "linear") or (
                kind == "yarn" and model_type == "deepseek_v3"):
            kw["rope_scaling"] = scaling
        elif kind is not None and kind != "default":
            # fail fast: serving with a dropped scaling scheme (longrope,
            # dynamic, ...) silently produces wrong positions. Supported:
            # llama3, linear, and yarn as deepseek_v3 applies it (on the
            # frequencies and the softmax scale; ops/rope.py)
            raise NotImplementedError(
                f"rope_scaling type {kind!r} is not supported yet for "
                f"model_type {model_type!r} (supported: llama3, linear, and "
                f"yarn for deepseek_v3)"
            )
    if model_type in ("qwen2",):
        kw["attention_bias"] = True
    if model_type in ("qwen3", "qwen3_vl", "qwen3_vl_text"):
        kw["qk_norm"] = True
    if model_type in ("mixtral",):
        kw["num_experts"] = int(hf.get("num_local_experts", 8))
        kw["num_experts_per_tok"] = int(hf.get("num_experts_per_tok", 2))
    if model_type in ("qwen3_moe", "qwen3_vl_moe", "qwen3_vl_moe_text"):
        # fail fast on layouts this decoder doesn't express (same policy
        # as the rope_scaling guard above): serving them silently would
        # produce wrong logits or a confusing mid-load KeyError
        # HF Qwen3MoeConfig DEFAULTS to False — an absent key means
        # no renormalization, which this MoE block cannot express
        if not hf.get("norm_topk_prob", False):
            raise NotImplementedError(
                "qwen3_moe with norm_topk_prob=false is not supported "
                "(the MoE block renormalizes top-k routing weights)")
        if int(hf.get("decoder_sparse_step", 1)) != 1 or hf.get("mlp_only_layers"):
            raise NotImplementedError(
                "qwen3_moe with dense layers interleaved "
                "(decoder_sparse_step != 1 or mlp_only_layers) is not supported")
        kw["qk_norm"] = True
        kw["num_experts"] = int(hf.get("num_experts", 128))
        kw["num_experts_per_tok"] = int(hf.get("num_experts_per_tok", 8))
        # experts use moe_intermediate_size, not the dense intermediate
        kw["intermediate_size"] = int(
            hf.get("moe_intermediate_size", hf["intermediate_size"]))
    if model_type == "lfm2_moe":
        # the published keys (LiquidAI/LFM2-24B-A2B config.json); tied
        # embeddings and the 1e-6 of the renormalisation are the family's
        # implementation's, not the file's
        rope = hf.get("rope_parameters") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"lfm2_moe with rope_type {rope.get('rope_type')!r} is "
                f"not supported yet")
        types = tuple(hf["layer_types"])
        if len(types) != kw["num_layers"] or set(types) - {
                "conv", "full_attention"}:
            raise NotImplementedError(
                f"lfm2_moe layer_types {sorted(set(types))} over "
                f"{len(types)} layers (num_hidden_layers "
                f"{kw['num_layers']}): only conv and full_attention")
        kw.update(
            layer_types=types,
            conv_L_cache=int(hf.get("conv_L_cache", 3)),
            conv_bias=bool(hf.get("conv_bias", False)),
            rope_theta=float(rope.get("rope_theta",
                                      hf.get("rope_theta", 1000000.0))),
            rms_norm_eps=float(hf.get("norm_eps", 1e-5)),
            qk_norm=True,
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
            num_dense_layers=int(hf.get("num_dense_layers", 0)),
            num_experts=int(hf["num_experts"]),
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            moe_router="sigmoid",
            use_expert_bias=bool(hf.get("use_expert_bias", False)),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            moe_renorm_eps=1e-6,
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        )
        if kw["conv_bias"]:
            raise NotImplementedError(
                "lfm2_moe with conv_bias=true is not supported")
    if model_type == "mellum":
        # the published keys (JetBrains/Mellum2-12B-A2.5B-Instruct
        # config.json): the kind of every layer, and a rotary scheme for
        # each kind of attention
        types = tuple(hf["layer_types"])
        sparse = hf.get("mlp_layer_types") or ["sparse"] * len(types)
        if len(types) != kw["num_layers"] or set(types) - {
                "sliding_attention", "full_attention"} \
                or len(sparse) != len(types) or set(sparse) != {"sparse"}:
            raise NotImplementedError(
                f"mellum layer_types {sorted(set(types))} and "
                f"mlp_layer_types {sorted(set(sparse))} over {len(types)} "
                f"layers (num_hidden_layers {kw['num_layers']}): only "
                f"sliding_attention and full_attention, every network "
                f"sparse")
        ropes = hf.get("rope_parameters") or {}
        local = ropes.get("sliding_attention") or {}
        full = ropes.get("full_attention") or {}
        if local.get("rope_type", "default") != "default" \
                or full.get("rope_type", "default") not in ("default", "yarn") \
                or local.get("rope_theta") != full.get("rope_theta"):
            raise NotImplementedError(
                "mellum is served with plain rotary window layers and "
                "plain or yarn full layers of one rope_theta")
        if "sliding_attention" in types and not (
                hf.get("use_sliding_window", True)
                and hf.get("sliding_window")):
            raise NotImplementedError(
                "mellum sliding_attention layers need sliding_window")
        kw.update(
            layer_types=types,
            rope_theta=float(full.get("rope_theta",
                                      hf.get("rope_theta", 10000.0))),
            rope_scaling=({k: v for k, v in full.items()
                           if k != "rope_theta"}
                          if full.get("rope_type") == "yarn" else None),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            num_experts=int(hf["num_experts"]),
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            moe_router="softmax",
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        )
    if model_type == "jamba":
        # the published keys (ai21labs/AI21-Jamba2-3B config.json); the
        # layer rule, the head size hidden / heads, the norms on the step
        # size and on B and C, and attention without positions are the
        # family's implementation's, not keys of the file
        if int(hf.get("num_experts", 1)) > 1:
            raise NotImplementedError(
                "jamba with num_experts > 1 is not supported: the family's "
                "expert layers (expert_layer_period/offset) are not built, "
                "and no configuration here would measure them")
        if hf.get("mamba_proj_bias", False) \
                or not hf.get("mamba_conv_bias", True):
            raise NotImplementedError(
                "jamba is served with mamba_conv_bias=true and "
                "mamba_proj_bias=false")
        kw.update(
            layer_types=_jamba_layers(
                kw["num_layers"], int(hf.get("attn_layer_period", 8)),
                int(hf.get("attn_layer_offset", 4))),
            use_rope=False,
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
            mamba_expand=int(hf.get("mamba_expand", 2)),
            mamba_d_state=int(hf.get("mamba_d_state", 16)),
            mamba_d_conv=int(hf.get("mamba_d_conv", 4)),
            mamba_dt_rank=int(hf.get("mamba_dt_rank")
                              or -(-hidden // 16)),
        )
    if model_type == "deepseek_v3":
        # the published keys (deepseek-ai/DeepSeek-V3 config.json). The
        # multi-token-prediction module (num_nextn_predict_layers) is a
        # draft head beside the model and is not built
        if hf.get("scoring_func", "sigmoid") != "sigmoid" \
                or hf.get("topk_method", "noaux_tc") != "noaux_tc" \
                or int(hf.get("moe_layer_freq", 1)) != 1:
            raise NotImplementedError(
                "deepseek_v3 is served with sigmoid scores, noaux_tc "
                "group-limited selection and experts in every layer past "
                "first_k_dense_replace")
        ys = kw.get("rope_scaling") or {}
        if ys and float(ys.get("mscale", 1.0)) != float(
                ys.get("mscale_all_dim", 0.0)):
            raise NotImplementedError(
                "yarn with mscale != mscale_all_dim (a factor on cos and "
                "sin) is not supported")
        nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
        kw.update(
            head_dim=nope + rope,
            q_lora_rank=int(hf["q_lora_rank"]),
            kv_lora_rank=int(hf["kv_lora_rank"]),
            qk_nope_head_dim=nope, qk_rope_head_dim=rope,
            v_head_dim=int(hf["v_head_dim"]),
            num_dense_layers=int(hf.get("first_k_dense_replace", 0)),
            num_experts=int(hf["n_routed_experts"]),
            num_experts_per_tok=int(hf["num_experts_per_tok"]),
            moe_intermediate_size=int(hf["moe_intermediate_size"]),
            moe_router="sigmoid", use_expert_bias=True,
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            moe_renorm_eps=1e-20,
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            n_group=int(hf.get("n_group", 1)),
            topk_group=int(hf.get("topk_group", 1)),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
        )
    if hf.get("query_pre_attn_scalar") is not None:
        kw["query_pre_attn_scalar"] = float(hf["query_pre_attn_scalar"])
    if model_type.startswith("gemma"):
        kw.update(
            hidden_act="gelu_tanh", norm_style="gemma",
            embedding_multiplier=hidden ** 0.5,
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        )
        if model_type in ("gemma2", "gemma3", "gemma3_text"):
            kw["post_norms"] = True
        if model_type == "gemma2":
            kw["logit_softcap"] = float(hf.get("final_logit_softcapping") or 30.0)
            kw["attn_softcap"] = float(hf.get("attn_logit_softcapping") or 50.0)
            kw["sliding_window_pattern"] = 2
            kw["rope_local_theta"] = float(hf.get("rope_theta", 10000.0))
        if model_type in ("gemma3", "gemma3_text"):
            kw["qk_norm"] = True
            kw["sliding_window_pattern"] = int(hf.get("sliding_window_pattern", 6))
            kw["rope_local_theta"] = float(hf.get("rope_local_base_freq", 10000.0))
    # multimodal wrapper (qwen3_vl): dynamic-resolution ViT + deepstack.
    # Serving needs static shapes, so images are resized to a fixed
    # square (the interpolated position grid handles any size).
    vc = outer.get("vision_config")
    if isinstance(vc, dict) and outer.get("model_type") in (
            "qwen3_vl", "qwen3_vl_moe"):
        from llms_on_kubernetes_tpu.models.vision import VisionConfig

        patch = int(vc.get("patch_size", 16))
        merge = int(vc.get("spatial_merge_size", 2))
        image_size = int(vc.get("image_size") or 768)
        image_size -= image_size % (patch * merge)
        kw["vision"] = VisionConfig(
            hidden_size=int(vc.get("hidden_size", 1152)),
            intermediate_size=int(vc.get("intermediate_size", 4304)),
            num_layers=int(vc.get("depth", 27)),
            num_heads=int(vc.get("num_heads", 16)),
            image_size=image_size,
            patch_size=patch,
            num_channels=int(vc.get("in_channels", 3)),
            family="qwen3vl",
            temporal_patch_size=int(vc.get("temporal_patch_size", 2)),
            spatial_merge_size=merge,
            out_hidden_size=int(vc.get("out_hidden_size", hidden)),
            num_grid_per_side=int(
                round(vc.get("num_position_embeddings", 2304) ** 0.5)),
            deepstack_indexes=tuple(vc.get("deepstack_visual_indexes", ())),
            mm_tokens_per_image=(image_size // (patch * merge)) ** 2,
        )
        kw["image_token_id"] = int(outer.get("image_token_id", 151655))
        kw["boi_token_id"] = int(outer.get("vision_start_token_id", 151652))
        kw["eoi_token_id"] = int(outer.get("vision_end_token_id", 151653))
    # multimodal wrapper (gemma3): vision tower + image token ids
    if isinstance(vc, dict) and outer.get("model_type") == "gemma3":
        from llms_on_kubernetes_tpu.models.vision import VisionConfig

        kw["vision"] = VisionConfig(
            hidden_size=int(vc.get("hidden_size", 1152)),
            intermediate_size=int(vc.get("intermediate_size", 4304)),
            num_layers=int(vc.get("num_hidden_layers", 27)),
            num_heads=int(vc.get("num_attention_heads", 16)),
            image_size=int(vc.get("image_size", 896)),
            patch_size=int(vc.get("patch_size", 14)),
            num_channels=int(vc.get("num_channels", 3)),
            layer_norm_eps=float(vc.get("layer_norm_eps", 1e-6)),
            mm_tokens_per_image=int(outer.get("mm_tokens_per_image", 256)),
        )
        kw["image_token_id"] = int(outer.get("image_token_index", 262144))
        kw["boi_token_id"] = int(outer.get("boi_token_index", 255999))
        kw["eoi_token_id"] = int(outer.get("eoi_token_index", 256000))
    return ModelConfig(**kw)
