"""Sharded execution on the virtual 8-device CPU mesh: results must match
single-device execution, for dense TP and MoE expert-parallel layouts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llms_on_kubernetes_tpu.configs import get_config
from llms_on_kubernetes_tpu.engine.cache import CacheConfig, PageAllocator, init_pages
from llms_on_kubernetes_tpu.models.decoder import forward_decode, forward_prefill, init_params
from llms_on_kubernetes_tpu.parallel.mesh import make_mesh
from llms_on_kubernetes_tpu.parallel.sharding import shard_params, shard_pool


def _setup(name, dtype="float32"):
    cfg = dataclasses.replace(get_config(name), dtype=dtype)
    params = init_params(cfg, jax.random.key(0), dtype=dtype)
    cc = CacheConfig(
        num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, num_pages=32, page_size=4, pages_per_slot=8,
        dtype=dtype,
    )
    kp, vp = init_pages(cc)
    alloc = PageAllocator(cc.num_pages, cc.page_size, 2, cc.pages_per_slot)
    alloc.allocate(0, 8)
    alloc.allocate(1, 8)
    pt = jnp.asarray(alloc.page_tables)
    toks = jnp.asarray([[4, 8, 15, 16], [23, 42, 0, 0]], jnp.int32)
    lens = jnp.asarray([4, 2], jnp.int32)
    return cfg, params, kp, vp, pt, toks, lens


def test_eight_devices_available():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("name,mesh_dims", [
    ("debug-tiny", dict(data=1, expert=1, model=2)),
    ("debug-tiny", dict(data=2, expert=1, model=2)),
    ("debug-moe", dict(data=1, expert=4, model=2)),
])
def test_sharded_forward_matches_unsharded(name, mesh_dims):
    cfg, params, kp, vp, pt, toks, lens = _setup(name)

    ref_logits, ref_kp, ref_vp = forward_prefill(params, cfg, toks, lens, kp, vp, pt)
    ref_dec, _, _ = forward_decode(
        params, cfg, jnp.asarray([7, 11], jnp.int32),
        lens + 1, ref_kp, ref_vp, pt,
    )

    mesh = make_mesh(**mesh_dims)
    sp = shard_params(params, cfg, mesh)
    kp_s = shard_pool(kp, cfg, mesh)
    vp_s = shard_pool(vp, cfg, mesh)

    got_logits, got_kp, got_vp = jax.jit(
        forward_prefill, static_argnums=(1,)
    )(sp, cfg, toks, lens, kp_s, vp_s, pt)
    got_dec, _, _ = jax.jit(forward_decode, static_argnums=(1,))(
        sp, cfg, jnp.asarray([7, 11], jnp.int32), lens + 1, got_kp, got_vp, pt
    )

    np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(got_logits), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ref_dec), np.asarray(got_dec), rtol=2e-4, atol=2e-4)


def test_mesh_shapes():
    m = make_mesh(data=2, expert=2, model=2)
    assert m.shape == {"data": 2, "seq": 1, "expert": 2, "model": 2}
    m = make_mesh(seq=4, model=2)
    assert m.shape == {"data": 1, "seq": 4, "expert": 1, "model": 2}
    with pytest.raises(ValueError):
        make_mesh(data=3)


@pytest.mark.parametrize("quantization,mesh_dims", [
    (None, dict(expert=4, model=2)),
    ("int8", dict(expert=2, model=1)),
], ids=["float, expert 4 x model 2", "int8, expert 2"])
def test_engine_serves_routed_experts_shard_by_shard(quantization, mesh_dims):
    """The engine sets the mesh the expert layers read (ops/moe._per_shard:
    each device multiplies its experts and its slice of the width): greedy
    tokens through prefill and fused decode windows are the one-device
    engine's."""
    from llms_on_kubernetes_tpu.engine.engine import (
        Engine, EngineConfig, SamplingParams,
    )
    from llms_on_kubernetes_tpu.parallel.mesh import set_active_mesh

    def greedy(mesh):
        eng = Engine(EngineConfig(
            model="debug-moe", dtype="float32", max_decode_slots=4,
            page_size=8, num_pages=64, pages_per_slot=8,
            prefill_buckets=(16, 32), decode_steps=4,
            quantization=quantization), mesh=mesh)
        try:
            reqs = [eng.submit(p, SamplingParams(temperature=0.0,
                                                 max_tokens=10))
                    for p in ([1, 2, 3], [4, 5, 6, 7, 8], [9, 10])]
            while any(not r.finished for r in reqs):
                eng.step()
            return [r.output for r in reqs]
        finally:
            set_active_mesh(None)

    n = mesh_dims["expert"] * mesh_dims["model"]
    assert greedy(make_mesh(data=1, devices=jax.devices()[:n],
                            **mesh_dims)) == greedy(None)
