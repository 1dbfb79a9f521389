"""Sharding rules: parameter / cache PartitionSpecs for the decoder.

Megatron-style tensor parallelism expressed declaratively: column-parallel
q/k/v and gate/up (output head / hidden axis over "model"), row-parallel
wo/w_down (input axis over "model" — XLA inserts the psum), vocab-parallel
embedding and lm_head. MoE expert weights additionally shard their expert
axis over "expert". The paged KV pool shards its kv-head axis over "model"
so each chip's pages hold only its own heads.

When a dimension doesn't divide the axis size (e.g. 4 kv heads on an
8-way model axis), the rule degrades to replication for that tensor —
same behaviour serving engines use for small-GQA models.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llms_on_kubernetes_tpu.configs import ModelConfig
from llms_on_kubernetes_tpu.parallel.mesh import AXIS_DATA, AXIS_EXPERT, AXIS_MODEL

Params = dict[str, Any]


def _axis(mesh: Mesh, dim: int, axis: str):
    """Use `axis` for this dim if it divides evenly, else replicate."""
    size = mesh.shape[axis]
    return axis if size > 1 and dim % size == 0 else None


def param_specs(cfg: ModelConfig, mesh: Mesh) -> Params:
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    D, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    m_h = _axis(mesh, H, AXIS_MODEL)
    m_kv = _axis(mesh, KV, AXIS_MODEL)
    m_f = _axis(mesh, F, AXIS_MODEL)
    m_v = _axis(mesh, V, AXIS_MODEL)

    layers: Params = {
        "attn_norm": P(),
        "wq": P(None, None, m_h, None),
        "wk": P(None, None, m_kv, None),
        "wv": P(None, None, m_kv, None),
        "wo": P(None, m_h, None, None),
        "mlp_norm": P(),
    }
    if cfg.attention_bias:
        layers["bq"] = P(None, m_h, None)
        layers["bk"] = P(None, m_kv, None)
        layers["bv"] = P(None, m_kv, None)
    if cfg.qk_norm:
        layers["q_norm"] = P()
        layers["k_norm"] = P()
    if cfg.post_norms:
        layers["attn_post_norm"] = P()
        layers["mlp_post_norm"] = P()
    if cfg.is_moe:
        e = _axis(mesh, cfg.num_experts, AXIS_EXPERT)
        layers["router"] = P()
        layers["w_gate"] = P(None, e, None, m_f)
        layers["w_up"] = P(None, e, None, m_f)
        layers["w_down"] = P(None, e, m_f, None)
    else:
        layers["w_gate"] = P(None, None, m_f)
        layers["w_up"] = P(None, None, m_f)
        layers["w_down"] = P(None, m_f, None)

    specs: Params = {
        "embed": P(m_v, None),
        "final_norm": P(),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, m_v)
    return specs


def pool_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Sharding of every KVPool leaf (data [KV, L*P, page, hd] AND the
    int8 per-token scales [KV, L*P, page]): the leading kv-head axis over
    ``model``, so each TP shard keeps its own heads' pages and scales
    local. On a seq>1 mesh the FLAT PAGE axis additionally shards over
    ``seq`` (context parallelism, ops/cp.py): total KV capacity then
    scales with the ring size instead of being bounded by one device's
    share. Trailing axes are left off the spec (= replicated) so one spec
    fits both leaves."""
    from llms_on_kubernetes_tpu.parallel.mesh import AXIS_SEQ

    m_kv = _axis(mesh, cfg.num_kv_heads, AXIS_MODEL)
    sq = AXIS_SEQ if mesh.shape.get(AXIS_SEQ, 1) > 1 else None
    return NamedSharding(mesh, P(m_kv, sq))


def shard_pool(pool, cfg: ModelConfig, mesh: Mesh):
    """Device_put an existing KVPool onto the mesh (see pool_sharding; the
    engine creates its pools sharded from the start via
    cache.init_pages)."""
    sharding = pool_sharding(cfg, mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), pool)


def shard_lora_stack(stack, mesh: Mesh):
    """Device_put a LoRAStack onto the mesh, rank-parallel when possible.

    The rank dimension is the stack's only axis guaranteed shardable
    regardless of the base model's head/hidden divisibility (the engine
    picks it), and it is a contraction of the delta — so each device holds
    a rank shard of BOTH factors and ``lora_delta`` psums the partial
    deltas (mirrors row-parallel wo/w_down). When the model axis doesn't
    divide the rank, the stack replicates (it's tiny: 2*S*r*(in+out))."""
    from llms_on_kubernetes_tpu.ops.lora import LoRAStack

    ax = _axis(mesh, stack.rank, AXIS_MODEL)
    a_spec = P(*([None] * (stack.a.ndim - 1)), ax)      # [..., S, *in, r]
    b_spec = P(None, None, ax,                          # [L, S, r, *out]
               *([None] * (stack.b.ndim - 3)))
    return LoRAStack(
        jax.device_put(stack.a, NamedSharding(mesh, a_spec)),
        jax.device_put(stack.b, NamedSharding(mesh, b_spec)),
        rank_axis=ax,
    )


def batch_spec() -> P:
    return P(AXIS_DATA)


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Device_put params onto the mesh according to param_specs.

    Int8-quantized weights (``QTensor``) shard their data with the weight's
    spec and their (keepdims) scale with the same axes on the non-reduced
    dims — so a TP-sharded weight carries its channel scales on the same
    chip as the channels.
    """
    from llms_on_kubernetes_tpu.ops.quant import GroupQTensor, QTensor, scale_spec

    specs = param_specs(cfg, mesh)
    if "vision" in params:
        # the vision tower is small relative to the decoder: replicate
        specs["vision"] = jax.tree.map(lambda _: P(), params["vision"])

    def put(x, s):
        if isinstance(x, GroupQTensor):
            # group-quantized (AWQ-native) weight: shard the FLAT OUTPUT
            # axis with the model axis the original spec put on any of
            # the logical out dims (column-parallel preserved). When the
            # original spec is CONTRACTION-sharded instead (row-parallel
            # wo/w_down), shard the GROUP axis over that mesh axis —
            # group_qeinsum partial-sums the local groups and psums
            # (group_axis below) — so TP actually divides the per-device
            # weight bytes for those tensors instead of replicating.
            k = len(x.out_shape)
            out_axes = tuple(s)[-k:] if len(tuple(s)) >= k else ()
            m = next((a for a in out_axes if a is not None), None)
            if m is not None and x.data.shape[-1] % mesh.shape[m] != 0:
                m = None
            if m is not None:
                def spec_for(arr):
                    return P(*([None] * (arr.ndim - 1)), m)
                return GroupQTensor(
                    jax.device_put(x.data,
                                   NamedSharding(mesh, spec_for(x.data))),
                    jax.device_put(x.scale,
                                   NamedSharding(mesh, spec_for(x.scale))),
                    jax.device_put(
                        x.zero_scaled,
                        NamedSharding(mesh, spec_for(x.zero_scaled))),
                    x.out_shape, packed=x.packed)
            # row-parallel: the contraction part of the original spec
            # (between the layer-stack dim and the out dims) names the
            # mesh axis; the G axis must divide it.
            con = tuple(s)[1:len(tuple(s)) - k]
            ax = next((a for a in reversed(con) if a is not None), None)
            G = x.data.shape[-3]
            if ax is not None and mesh.shape[ax] > 1 \
                    and G % mesh.shape[ax] == 0:
                def gspec(arr, tail):  # shard the G axis (ndim - tail - 1)
                    return P(*([None] * (arr.ndim - 1 - tail)), ax,
                             *([None] * tail))
                return GroupQTensor(
                    jax.device_put(
                        x.data, NamedSharding(mesh, gspec(x.data, 2))),
                    jax.device_put(
                        x.scale, NamedSharding(mesh, gspec(x.scale, 1))),
                    jax.device_put(
                        x.zero_scaled,
                        NamedSharding(mesh, gspec(x.zero_scaled, 1))),
                    x.out_shape, packed=x.packed, group_axis=ax)
            # no shardable axis: replicate (degenerate-mesh fallback)
            return GroupQTensor(
                jax.device_put(x.data, NamedSharding(mesh, P())),
                jax.device_put(x.scale, NamedSharding(mesh, P())),
                jax.device_put(x.zero_scaled, NamedSharding(mesh, P())),
                x.out_shape, packed=x.packed)
        if isinstance(x, QTensor):
            data = jax.device_put(x.data, NamedSharding(mesh, s))
            scale = jax.device_put(
                x.scale, NamedSharding(mesh, scale_spec(s, x.scale.shape))
            )
            return QTensor(data, scale)
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree.map(
        put, params, specs,
        is_leaf=lambda x: isinstance(x, (QTensor, GroupQTensor))
    )
