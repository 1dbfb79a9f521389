"""Mixture-of-Experts block: a top-k router and a grouped expert product.

One path for prefill, chunk and decode, and for every routed model
(Mixtral, Qwen3-MoE, LFM2-MoE):

  route             scores [N, E] -> the k experts of each token and their
                    weights (``route``)
  sort              the N*k (token, expert) pairs by expert, stable, the
                    pairs of padding and idle tokens last
  grouped product   ``jax.lax.ragged_dot``: row i of the sorted rows is
                    multiplied with the weights of ITS expert, so the work
                    is top_k x N rows whatever the number of experts (on
                    the TPU XLA lowers it to a grouped-matmul kernel of its
                    own; elsewhere to a masked dense product)
  combine           the pairs back in token order, weighted and summed

Dropless by construction: a token's result never depends on which other
requests share its batch. There is no dispatch tensor over
(token, choice, expert, capacity) and no capacity.

With the stacks sharded over the engine's mesh each device does this for
the experts and the slice of the hidden width it holds, and the results
are summed (``_per_shard``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llms_on_kubernetes_tpu.ops.quant import QTensor


def route(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    bias: "jnp.ndarray | None" = None,
    *,
    top_k: int,
    scores: str = "softmax",
    renorm: bool = True,
    eps: float = 0.0,
    scale: float = 1.0,
):
    """x [N, D], router_w [D, E] -> (sel [N, k] int32, weight [N, k] f32).

    ``scores``: "softmax" over all experts, or "sigmoid" of each expert's
    logit alone. ``bias`` [E] is added to the scores for the SELECTION
    only; a weight is always the unbiased score of the expert chosen.
    ``renorm`` divides the chosen scores by their sum (+ ``eps``), then
    ``scale`` multiplies them. Float32 at the highest matmul precision:
    the product is tiny, and a near-tie decides which weights a token
    reads."""
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)        # [N, E]
        if scores == "sigmoid":
            s = jax.nn.sigmoid(logits)
        elif scores == "softmax":
            s = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown router scores {scores!r}")
        pick = s if bias is None else s + bias.astype(jnp.float32)
        _, sel = jax.lax.top_k(pick, top_k)                          # [N, k]
        weight = jnp.take_along_axis(s, sel, axis=-1)
        if renorm:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + eps)
        return sel, weight * scale


def _grouped_dot(rows: jnp.ndarray, w, layer, group_sizes: jnp.ndarray,
                 expert_of_row: jnp.ndarray) -> jnp.ndarray:
    """rows [M, K] sorted by expert x layer ``layer`` of w [n, E, K, N]
    -> [M, N] in rows' type.

    The product is handed the WHOLE stack as n * E groups, every group of
    another layer empty: a slice of the stack cannot be (the grouped
    kernel is a custom call, whose operand XLA would first copy out of the
    stack, 1.2 GB a layer at 64 experts of 2048 x 1536), and an empty
    group costs the kernel nothing, it visits the groups that have rows.
    An int8 ``QTensor`` goes in as int8 (the kernel widens a block as it
    loads it: no widened copy of the experts is ever written) and its
    per-column scale is applied to the result, by each row's expert."""
    data = w.data if isinstance(w, QTensor) else w.astype(rows.dtype)
    n, E = data.shape[:2]
    if n > 1:
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n * E,), group_sizes.dtype), group_sizes, (layer * E,))
    out = jax.lax.ragged_dot(
        rows, data.reshape(n * E, *data.shape[2:]), group_sizes,
        preferred_element_type=rows.dtype)
    if isinstance(w, QTensor):
        scale = jax.lax.dynamic_index_in_dim(w.scale, layer, 0, False)
        scale = scale.reshape(E, -1)                                 # [E, N]
        out = out * scale[jnp.minimum(expert_of_row, E - 1)].astype(out.dtype)
    return out


def _experts(x, expert, weight, w_gate, w_up, w_down, layer, act):
    """The sort, the three grouped products and the combine over the
    experts of the stacks given: ``expert`` [N*k] names each (token,
    choice) pair's expert among them, or their number for a pair that
    reaches none of them (padding, an idle row, another shard's expert).
    Returns [N, D] float32."""
    N, D = x.shape
    k = expert.shape[0] // N
    E = w_gate.shape[1]
    order = jnp.argsort(expert, stable=True)                         # [N*k]
    expert_sorted = expert[order]
    rows = jnp.sum(expert[:, None] == jnp.arange(E, dtype=jnp.int32),
                   axis=0, dtype=jnp.int32)                          # [E]
    xs = x[order // k]                                               # [N*k, D]
    h = (act(_grouped_dot(xs, w_gate, layer, rows, expert_sorted))
         * _grouped_dot(xs, w_up, layer, rows, expert_sorted))
    ys = _grouped_dot(h, w_down, layer, rows, expert_sorted)         # [N*k, D]
    # rows past the last group belong to no expert here: whatever the
    # product left there is dropped, not multiplied by zero
    ys = jnp.where((expert_sorted < E)[:, None],
                   ys.astype(jnp.float32)
                   * weight.reshape(N * k)[order][:, None], 0.0)
    # back to token order (the inverse permutation: a gather, not a
    # scatter-add), then the k choices of a token are summed
    return ys[jnp.argsort(order)].reshape(N, k, D).sum(axis=1)


def _per_shard(x, expert, weight, w_gate, w_up, w_down, layer, act):
    """``_experts`` once per shard of the expert stacks, the partial
    results summed over the mesh.

    XLA cannot partition the grouped kernel's group axis: given stacks
    sharded over ``expert`` it first gathers them, every layer's, onto
    every chip. The stacks are sharded as parallel/sharding.param_specs
    says (experts over ``expert``, the hidden width over ``model``), so
    under ``shard_map`` each device sorts the pairs by ITS experts (the
    others' sort last, as padding does), multiplies its slice of the
    width, and the [N, D] results are summed: the one exchange of an
    expert layer, as the all-reduce after a row-parallel ``w_down`` is.
    Runs ``_experts`` directly where the engine's mesh shards neither
    axis (no mesh, one chip, sizes the axes do not divide)."""
    from jax.sharding import PartitionSpec as P

    from llms_on_kubernetes_tpu.ops.quant import scale_spec
    from llms_on_kubernetes_tpu.parallel.mesh import (
        AXIS_EXPERT, AXIS_MODEL, get_active_mesh)
    from llms_on_kubernetes_tpu.parallel.sharding import _axis

    mesh = get_active_mesh()
    e_ax = m_ax = None
    if mesh is not None:
        e_ax = _axis(mesh, w_gate.shape[1], AXIS_EXPERT)
        m_ax = _axis(mesh, w_gate.shape[-1], AXIS_MODEL)
    if e_ax is None and m_ax is None:
        return _experts(x, expert, weight, w_gate, w_up, w_down, layer, act)

    def spec(w, s):
        return QTensor(s, scale_spec(s, w.scale.shape)) if isinstance(
            w, QTensor) else s

    def shard(x, expert, weight, w_gate, w_up, w_down, layer):
        mine = w_gate.shape[1]
        first = 0 if e_ax is None else jax.lax.axis_index(e_ax) * mine
        here = (expert >= first) & (expert < first + mine)
        out = _experts(x, jnp.where(here, expert - first, mine), weight,
                       w_gate, w_up, w_down, layer, act)
        return jax.lax.psum(out.astype(x.dtype),
                            tuple(a for a in (e_ax, m_ax) if a is not None))

    up, down = P(None, e_ax, None, m_ax), P(None, e_ax, m_ax, None)
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(), P(), P(), spec(w_gate, up), spec(w_up, up),
                  spec(w_down, down), P()),
        out_specs=P(), check_vma=False,
    )(x, expert, weight, w_gate, w_up, w_down, jnp.asarray(layer, jnp.int32))


def grouped_experts(
    x: jnp.ndarray,
    sel: jnp.ndarray,
    weight: jnp.ndarray,
    w_gate,
    w_up,
    w_down,
    *,
    act=jax.nn.silu,
    valid: "jnp.ndarray | None" = None,
    layer=None,
):
    """sum_i weight[:, i] * expert_{sel[:, i]}(x) for x [N, D], with
    w_gate/w_up [E, D, F] and w_down [E, F, D]; or, with ``layer`` given
    (an index, traced or not), layer ``layer`` of weights stacked over a
    run's layers, [n, E, D, F] and [n, E, F, D] (``_grouped_dot`` says
    why the stack is not sliced).

    Returns (out [N, D], rows [E] int32): ``rows`` counts the (token,
    expert) pairs each expert got. ``valid`` ([N] bool) keeps padding and
    idle tokens out: they reach no expert, count nowhere, and their rows
    of ``out`` are zero."""
    N, k = sel.shape
    if layer is None:
        layer = 0
        w_gate, w_up, w_down = jax.tree_util.tree_map(
            lambda a: a[None], (w_gate, w_up, w_down))
    E = w_gate.shape[1]
    with jax.named_scope("moe.experts"):
        expert = sel.reshape(N * k).astype(jnp.int32)
        if valid is not None:
            expert = jnp.where(jnp.repeat(valid, k), expert, E)  # sorts last
        rows = jnp.sum(expert[:, None] == jnp.arange(E, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)                      # [E]
        out = _per_shard(x, expert, weight, w_gate, w_up, w_down, layer, act)
        return out.astype(x.dtype), rows


def moe_block(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w_gate,
    w_up,
    w_down,
    *,
    top_k: int,
    act=jax.nn.silu,
    valid: "jnp.ndarray | None" = None,
    bias: "jnp.ndarray | None" = None,
    scores: str = "softmax",
    renorm: bool = True,
    eps: float = 0.0,
    scale: float = 1.0,
    layer=None,
):
    """x: [N, D]; router_w: [D, E]; w_gate/w_up: [E, D, F]; w_down:
    [E, F, D] (with ``layer``: that layer of [n, E, ...] stacks). Returns
    (out [N, D], rows [E]): see ``route`` and ``grouped_experts``."""
    sel, weight = route(x, router_w, bias, top_k=top_k, scores=scores,
                        renorm=renorm, eps=eps, scale=scale)
    return grouped_experts(x, sel, weight, w_gate, w_up, w_down, act=act,
                           valid=valid, layer=layer)
