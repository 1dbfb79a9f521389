"""Batched multi-adapter LoRA: per-slot low-rank deltas in one matmul pass.

Punica (Chen et al., 2023) and S-LoRA (Sheng et al., 2023) serve many
fine-tunes from one base model by keeping the base weights shared and
applying each request's low-rank delta inside the batched step. The TPU
port follows ``ops/quant.py::group_qeinsum``'s structure: a ``lax.scan``
over the resident adapter slots with an f32 accumulator, each slot's
delta masked to the batch rows that selected it (the segmented-matmul
formulation — every slot's two rank-r matmuls run over the whole batch,
which at decode batch sizes and r<=64 is noise next to the base matmul).

- ``LoRAStack`` holds EVERY resident adapter's A/B factors for one target
  weight, slot-major, with the engine's layer-stack axis leading — so the
  stacks ride ``lax.scan`` over ``params["layers"]`` and slice per layer
  like any other leaf. Empty slots are zeros: their delta vanishes, so
  slot residency never changes the compiled program.
- ``lora_qeinsum`` adds the gathered delta on top of ``qeinsum`` of the
  BASE weight — additive on the output, so it composes unchanged with
  QTensor / GroupQTensor (packed4 AWQ) bases; the base path stays the
  exact kernel the non-LoRA engine runs.
- Rank-axis sharding: when the stack's ``rank_axis`` names a mesh axis
  that divides r, the slot scan runs under ``shard_map`` with each device
  holding a rank shard of A and B; the delta is a sum over rank, so a
  single f32 ``psum`` combines the partial deltas exactly (mirrors
  group_qeinsum's group-axis sharding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
class LoRAStack:
    """All resident adapters' factors for ONE target weight.

    a   [..., S, *in_dims, r]  float32 (x @ a -> rank space)
    b   [..., S, r, *out_dims] float32 (rank space -> output; the
        adapter's alpha/r scale is folded in at upload time)
    Leading axes (the engine's layer stack) ride along and slice under
    ``lax.scan``. ``rank_axis`` is pytree AUX data — the mesh axis name
    sharding the rank dimension, or None when replicated.
    """

    def __init__(self, a, b, rank_axis=None):
        self.a = a
        self.b = b
        self.rank_axis = rank_axis

    @property
    def rank(self):
        return self.a.shape[-1]

    def tree_flatten(self):
        return (self.a, self.b), (self.rank_axis,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, rank_axis=aux[0])

    def __repr__(self):
        return (f"LoRAStack(a={tuple(self.a.shape)}, "
                f"b={tuple(self.b.shape)}, rank_axis={self.rank_axis})")


def lora_zeros(num_layers: int, num_slots: int, in_shape: tuple,
               out_shape: tuple, rank: int) -> LoRAStack:
    """An empty (all-slots-vacant) stack for one layer-stacked target."""
    a = jnp.zeros((num_layers, num_slots) + tuple(in_shape) + (rank,),
                  jnp.float32)
    b = jnp.zeros((num_layers, num_slots, rank) + tuple(out_shape),
                  jnp.float32)
    return LoRAStack(a, b)


def _delta_eqs(eq: str) -> tuple[str, str]:
    """Derive the two rank-space einsums from the base equation.

    "btd,dhk->bthk" -> ("btd,dr->btr", "btr,rhk->bthk"): contract x with
    A over the base contraction dims into rank space, then expand with B
    into the base output dims. Works for every decoder equation because
    the weight's contracted dims are exactly x's dims shared with w.
    """
    lhs, out = eq.split("->")
    x_sub, w_sub = lhs.split(",")
    batch = "".join(c for c in x_sub if c not in w_sub)
    contract = "".join(c for c in x_sub if c in w_sub)
    out_dims = "".join(c for c in out if c not in x_sub)
    assert "r" not in eq, f"rank label collides in {eq!r}"
    return (f"{x_sub},{contract}r->{batch}r",
            f"{batch}r,r{out_dims}->{out}")


def lora_delta(eq: str, x: jnp.ndarray, lora: LoRAStack,
               idx: jnp.ndarray) -> jnp.ndarray:
    """Sum of per-slot adapter deltas, each masked to its batch rows.

    ``idx`` [B] int32 holds each row's adapter slot (< 0 = base model,
    matches no slot). Returns the f32 delta with the base output's shape.
    """
    a, b = lora.a, lora.b  # [S, *in, r] / [S, r, *out] (layer axis sliced)
    S = a.shape[0]
    eq_a, eq_b = _delta_eqs(eq)

    def scan_slots(xf, idx_, a_, b_):
        def body(acc, per_s):
            a_s, b_s, s = per_s
            t = jnp.einsum(eq_a, xf, a_s,
                           preferred_element_type=jnp.float32)
            d = jnp.einsum(eq_b, t, b_s,
                           preferred_element_type=jnp.float32)
            keep = (idx_ == s).reshape((-1,) + (1,) * (d.ndim - 1))
            return acc + jnp.where(keep, d, 0.0), None

        # delta shape: out labels resolve against x (batch/contract dims)
        # or against b's trailing out dims ([S, r, *out_dims])
        lhs, out = eq.split("->")
        x_sub = lhs.split(",")[0]
        out_dims = [c for c in out if c not in x_sub]
        shape = tuple(xf.shape[x_sub.index(c)] if c in x_sub
                      else b_.shape[2 + out_dims.index(c)] for c in out)
        acc0 = jnp.zeros(shape, jnp.float32)
        acc, _ = jax.lax.scan(
            body, acc0, (a_, b_, jnp.arange(S, dtype=jnp.int32)))
        return acc

    xf = x.astype(jnp.float32)
    ax = lora.rank_axis
    mesh = None
    if ax is not None:
        from llms_on_kubernetes_tpu.parallel.mesh import get_active_mesh

        mesh = get_active_mesh()
    if mesh is not None and mesh.shape.get(ax, 1) > 1 \
            and lora.rank % mesh.shape[ax] == 0:
        from jax.sharding import PartitionSpec as P

        def local(xf_, idx_, a_, b_):
            # each device scans its rank shard; delta is a sum over rank
            return jax.lax.psum(scan_slots(xf_, idx_, a_, b_), ax)

        out_ndim = len(eq.split("->")[1])
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*([None] * xf.ndim)), P(None),
                      P(*([None] * (a.ndim - 1) + [ax])),
                      P(None, ax, *([None] * (b.ndim - 2)))),
            out_specs=P(*([None] * out_ndim)),
            check_vma=False,
        )(xf, idx, a, b)
    return scan_slots(xf, idx, a, b)


def lora_qeinsum(eq: str, x: jnp.ndarray, w, lora, idx) -> jnp.ndarray:
    """``qeinsum`` plus the batch's per-row adapter deltas.

    ``lora`` None or ``idx`` None short-circuits to the exact base kernel
    (adapter-free engines trace the identical program they always did).
    """
    from llms_on_kubernetes_tpu.ops.quant import qeinsum

    base = qeinsum(eq, x, w)
    if lora is None or idx is None:
        return base
    return (base.astype(jnp.float32)
            + lora_delta(eq, x, lora, idx)).astype(base.dtype)


def merge_delta(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Dense [in.., out..] weight delta for ONE adapter's (a, b) factors
    ([in.., r], [r, out..]) — the merged-weights reference the parity
    tests check the batched path against."""
    return jnp.tensordot(jnp.asarray(a, jnp.float32),
                         jnp.asarray(b, jnp.float32), axes=[[-1], [0]])
