"""Mixture-of-Experts block: a top-k router and a grouped expert product.

One path for prefill, chunk and decode, and for every routed model
(Mixtral, Qwen3-MoE, LFM2-MoE, DeepSeek-V3):

  route             scores [N, E] -> the k experts of each token and their
                    weights (``route``), among all experts or among those
                    of the token's best groups
  sort              the N*k (token, expert) pairs by expert, stable, the
                    pairs of padding and idle tokens last, and with them
                    the pairs of experts that are not held here (a layer
                    may hold one chip's share of the router's experts:
                    ``grouped_experts(held=)``)
  grouped product   row i of the sorted rows is multiplied with the weights
                    of ITS expert, so the work is top_k x N rows whatever
                    the number of experts. One algorithm, two tiles
                    (``_plan``, from the static shape): on the TPU, up
                    to the 4,096 rows an expert it was measured at, the
                    Pallas kernel of ops/pallas_grouped.py: each
                    expert's rows start at a multiple of a 16- to 128-row
                    tile, and a grid step is one touched expert's weight
                    block, read once, times the rows it got; everywhere
                    else ``jax.lax.ragged_dot`` (on the TPU XLA's own
                    grouped kernel, elsewhere a masked dense product)
  combine           the pairs back in token order, weighted and summed

Which product a step was traced with is in the server's log, beside the
attention dispatchers' picks: ``[attention] op=experts impl=pallas-compiled
why=256 pairs over 64 experts, row tile 16`` or ``impl=xla why=ragged_dot,
<the reason>``; in a capture the first is ``grouped_expert_matmul``, the
second ``ragged-dot-none``.

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
    n_group: int = 1,
    topk_group: int = 1,
):
    """x [N, D], router_w [D, E] -> (sel [N, k] int32, weight [N, k] f32).

    ``scores``: "softmax" over all experts, or "sigmoid" of each expert's
    logit alone. ``bias`` [E] is added to the scores for the SELECTION
    only; a weight is always the unbiased score of the expert chosen.
    ``n_group`` > 1 limits the selection to groups (DeepSeek-V3): the
    experts lie in ``n_group`` groups of E / n_group consecutive ones, a
    group's score is the sum of its two largest selection scores, and only
    the experts of the ``topk_group`` best groups can be chosen (the rest
    are masked to -inf; a tie goes to the lower index, group or expert).
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
        if n_group > 1:
            N, E = pick.shape
            best2, _ = jax.lax.top_k(pick.reshape(N, n_group, E // n_group), 2)
            _, keep = jax.lax.top_k(best2.sum(axis=-1), topk_group)
            kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
            pick = jnp.where(jnp.repeat(kept, E // n_group, axis=1), pick,
                             -jnp.inf)
        _, sel = jax.lax.top_k(pick, top_k)                          # [N, k]
        weight = jnp.take_along_axis(s, sel, axis=-1)
        if renorm:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + eps)
        return sel, weight * scale


# The most rows an expert gets at the mean (pairs / experts) at which the
# Pallas grouped kernel (ops/pallas_grouped.py) takes the three products:
# the largest at which a v5e has timed it, and found it no slower than
# ``ragged_dot``. XLA's own kernel pays a 256-row tile of MXU work for
# every touched expert and block whatever rows it got, so the gap is widest
# at a handful of rows: a layer of 64 experts of 2048 x 1536 reads 2.34 ->
# 1.62 ms at 4 rows an expert at the mean (decode), 3.97 -> 1.80 at 8,
# 4.25 -> 2.14 at 32, 5.10 -> 3.01 at 128, 6.81 -> 4.71 at 256, 11.2 -> 8.7
# at 512, 19.4 -> 15.3 at 1,024, 38.5 -> 32.6 at 2,048; one of 8 experts of
# 4096 x 3584 1.36 -> 1.32 at 8, 3.67 -> 2.40 at 256, 31.1 -> 26.3 at 4,096
# (PERF.md, section 6, PR 39). No crossing was found; past what was
# measured ``ragged_dot`` stays.
KERNEL_MAX_MEAN_ROWS = 4096


def _plan(x, pairs: int, stacks, experts: int) -> "int | None":
    """The row tile of the Pallas grouped kernel for this expert layer, or
    None where ``jax.lax.ragged_dot`` multiplies it: chosen from what the
    trace can observe (backend, types, the pairs over the ``experts`` they
    were routed among, VMEM) and recorded as ``op=experts`` beside the
    attention dispatchers' picks."""
    from llms_on_kubernetes_tpu.ops import attention, pallas_grouped

    datas = [w.data if isinstance(w, QTensor) else w for w in stacks]
    what = f"{pairs} pairs over {experts} experts"
    if datas[0].shape[1] != experts:
        what += f" ({datas[0].shape[1]} on this shard)"
    mode = attention.pallas_mode()
    tile, why = pallas_grouped.row_tile(pairs, experts), None
    if mode is None:
        why = attention._no_pallas_why()
    elif pairs > KERNEL_MAX_MEAN_ROWS * experts:
        why = f"{what}: {pairs // experts} rows an expert, not measured"
    elif mode == "compiled" and x.dtype != jnp.bfloat16:
        why = f"{x.dtype.name} rows"
    elif mode == "compiled" and any(d % 128 for d in datas[0].shape[2:]):
        why = ("experts of {} x {}: not multiples of 128"
               .format(*datas[0].shape[2:]))
    else:
        need = max(pallas_grouped.grouped_vmem_bytes(
            tile, *w.shape[2:], x.dtype.itemsize, w.dtype.itemsize)
            for w in datas)
        if need > attention.VMEM_BUDGET_BYTES:
            why = (f"{what} need {attention._mib(need)} VMEM > "
                   f"{attention._mib(attention.VMEM_BUDGET_BYTES)} budget")
    if why is not None:
        attention.record_choice("experts", "xla", f"ragged_dot, {why}")
        return None
    attention.record_choice("experts", f"pallas-{mode}", f"{what}, row tile {tile}")
    return tile


def _grouped_dot(rows: jnp.ndarray, w, layer, group_sizes: jnp.ndarray,
                 expert_of_row: jnp.ndarray, lay=None) -> jnp.ndarray:
    """rows [M, K] sorted by expert x layer ``layer`` of w [n, E, K, N]
    -> [M, N] in rows' type; with ``lay`` the rows are group-aligned
    (``pallas_grouped.layout``) and the Pallas kernel multiplies them.

    Either product is handed the WHOLE stack: a slice of it cannot be (the
    grouped kernel is a custom call, whose operand XLA would first copy
    out of the stack, 1.2 GB a layer at 64 experts of 2048 x 1536).
    ``ragged_dot`` sees n * E groups, every group of another layer empty
    (an empty group costs it nothing, it visits the groups that have
    rows); the Pallas kernel's index map names (layer, expert) itself.
    An int8 ``QTensor`` goes in as int8 (both kernels widen a block as
    they load it: no widened copy of the experts is ever written) and its
    per-column scale is applied to the result, by each row's expert."""
    data = w.data if isinstance(w, QTensor) else w.astype(rows.dtype)
    n, E = data.shape[:2]
    if lay is not None:
        from llms_on_kubernetes_tpu.ops import attention, pallas_grouped

        out = pallas_grouped.grouped_matmul(
            rows, data, layer, lay.tile_expert, lay.n_tiles,
            tile=rows.shape[0] // lay.tile_expert.shape[0],
            interpret=attention.pallas_mode() == "interpret")
    else:
        if n > 1:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), group_sizes.dtype), group_sizes,
                (layer * E,))
        out = jax.lax.ragged_dot(
            rows, data.reshape(n * E, *data.shape[2:]), group_sizes,
            preferred_element_type=rows.dtype)
    if isinstance(w, QTensor):
        scale = jax.lax.dynamic_index_in_dim(w.scale, layer, 0, False)
        scale = scale.reshape(E, -1)                                 # [E, N]
        out = out * scale[jnp.minimum(expert_of_row, E - 1)].astype(out.dtype)
    return out


def _experts(x, expert, weight, w_gate, w_up, w_down, layer, act,
             experts=None):
    """The sort, the three grouped products and the combine over the
    experts of the stacks given: ``expert`` [N*k] names each (token,
    choice) pair's expert among them, or their number for a pair that
    reaches none of them (padding, an idle row, another shard's expert).
    ``experts``: how many the pairs were routed among, where the stacks
    hold a shard of them. Returns [N, D] float32."""
    from llms_on_kubernetes_tpu.ops import pallas_grouped

    N, D = x.shape
    M = expert.shape[0]
    k = M // N
    E = w_gate.shape[1]
    order = jnp.argsort(expert, stable=True)                         # [N*k]
    reaches = expert[:, None] == jnp.arange(E, dtype=jnp.int32)      # [N*k, E]
    rows = jnp.sum(reaches, axis=0, dtype=jnp.int32)                 # [E]
    # where the sorted pairs sit in the products' rows, and back
    back = jnp.argsort(order)
    tile = _plan(x, M, (w_gate, w_up, w_down), experts or E)
    if tile is None:
        lay, source, expert_of_row = None, order, expert[order]
    else:
        lay = pallas_grouped.layout(rows, M, tile)
        source, expert_of_row = order[lay.source], lay.expert
        # + the offset of the pair's expert (summed, not gathered)
        back = back + jnp.sum(jnp.where(reaches, lay.offset[None, :], 0),
                              axis=1)
    xs = x[source // k]                                              # [rows, D]
    h = (act(_grouped_dot(xs, w_gate, layer, rows, expert_of_row, lay))
         * _grouped_dot(xs, w_up, layer, rows, expert_of_row, lay))
    ys = _grouped_dot(h, w_down, layer, rows, expert_of_row, lay)    # [rows, D]
    # back to token order (the inverse permutation: a gather, not a
    # scatter-add). A pair that reaches no expert here has no row of its
    # own: whatever it reads is dropped, not multiplied by zero. Then the
    # k choices of a token are summed
    mine = expert < E
    ys = jnp.where(mine[:, None],
                   ys[jnp.where(mine, back, 0)].astype(jnp.float32)
                   * weight.reshape(M, 1), 0.0)
    return ys.reshape(N, k, D).sum(axis=1)


def _per_shard(x, expert, weight, w_gate, w_up, w_down, layer, act,
               routed_among=None):
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
    experts = w_gate.shape[1]
    if mesh is not None:
        e_ax = _axis(mesh, experts, AXIS_EXPERT)
        m_ax = _axis(mesh, w_gate.shape[-1], AXIS_MODEL)
    if e_ax is None and m_ax is None:
        return _experts(x, expert, weight, w_gate, w_up, w_down, layer, act,
                        routed_among)

    def spec(w, s):
        return QTensor(s, scale_spec(s, w.scale.shape)) if isinstance(
            w, QTensor) else s

    def shard(x, expert, weight, w_gate, w_up, w_down, layer):
        mine = w_gate.shape[1]
        first = 0 if e_ax is None else jax.lax.axis_index(e_ax) * mine
        here = (expert >= first) & (expert < first + mine)
        out = _experts(x, jnp.where(here, expert - first, mine), weight,
                       w_gate, w_up, w_down, layer, act, experts)
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
    held: "tuple | None" = None,
):
    """sum_i weight[:, i] * expert_{sel[:, i]}(x) for x [N, D], with
    w_gate/w_up [E, D, F] and w_down [E, F, D]; or, with ``layer`` given
    (an index, traced or not), layer ``layer`` of weights stacked over a
    run's layers, [n, E, D, F] and [n, E, F, D] (``_grouped_dot`` says
    why the stack is not sliced).

    ``held`` = (first, experts): the stacks hold experts [first, first +
    E) of the ``experts`` that ``sel`` was chosen among, one chip's share
    of an expert-parallel deployment. The sum is then over the chosen
    experts that are held: a pair routed to another chip's expert is
    dropped before the sort, as padding is, so it costs no row of a
    product, no tile and no byte, and that partial result is the layer's.

    Returns (out [N, D], rows int32): ``rows`` [E] counts the (token,
    expert) pairs each expert got; with ``held``, [E + 1], the last entry
    the pairs routed to experts held elsewhere. ``valid`` ([N] bool) keeps
    padding and idle tokens out: they reach no expert, count nowhere, and
    their rows of ``out`` are zero."""
    N, k = sel.shape
    if layer is None:
        layer = 0
        w_gate, w_up, w_down = jax.tree_util.tree_map(
            lambda a: a[None], (w_gate, w_up, w_down))
    E = w_gate.shape[1]
    with jax.named_scope("moe.experts"):
        expert = sel.reshape(N * k).astype(jnp.int32)
        live = True if valid is None else jnp.repeat(valid, k)
        experts = None
        if held is not None:
            first, experts = held
            expert = expert - first
            here = (expert >= 0) & (expert < E)
            elsewhere = jnp.sum(live & ~here, dtype=jnp.int32)
            live = live & here
        expert = jnp.where(live, expert, E)                  # sorts last
        rows = jnp.sum(expert[:, None] == jnp.arange(E, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)                      # [E]
        out = _per_shard(x, expert, weight, w_gate, w_up, w_down, layer, act,
                         experts)
        if held is not None:
            rows = jnp.concatenate([rows, elsewhere[None]])
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
    n_group: int = 1,
    topk_group: int = 1,
    first_expert: "int | None" = None,
):
    """x: [N, D]; router_w: [D, E]; w_gate/w_up: [E, D, F]; w_down:
    [E, F, D] (with ``layer``: that layer of [n, E, ...] stacks; with
    ``first_expert``: stacks of the experts held here, from that one on,
    of the router's E). Returns (out [N, D], rows): see ``route`` and
    ``grouped_experts``."""
    sel, weight = route(x, router_w, bias, top_k=top_k, scores=scores,
                        renorm=renorm, eps=eps, scale=scale, n_group=n_group,
                        topk_group=topk_group)
    held = (None if first_expert is None
            else (first_expert, router_w.shape[-1]))
    return grouped_experts(x, sel, weight, w_gate, w_up, w_down, act=act,
                           valid=valid, layer=layer, held=held)
