"""The decode kernels of the main path, compiled for a v5e that is
described and not attached (no chip, a few seconds each).

Interpret mode cannot see what Mosaic refuses (a slice off the tiling, too
much VMEM) nor whether XLA keeps an aliased pool in place; the chip's
compiler, which is installed here, can. Nothing runs: results and times are
tests/test_tpu_hardware.py's, on the chip. The topology is described inside
a fixture, never at import (one process at a time may load the TPU's
library, and every xdist worker imports this file).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the dispatchers import ops/cp.py when they run; that module and the
# decoder import each other, and only this order resolves
import llms_on_kubernetes_tpu.models.decoder  # noqa: F401

N_KV, GROUP, D = 8, 4, 128             # mistral-7b attention heads
ROWS, PAGE, PPS, PAGES = 32, 64, 32, 769   # the mistral-7b.chat cell


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _operands(dev, kv_dtype, page, pps, heads=None, pools=None, shards=1,
              rows=ROWS, n_kv=N_KV, group=GROUP, d=D, pages=PAGES):
    """Shapes of dispatch_paged_attention_write's operands on ``dev``, the
    pools in the layout ``CacheConfig`` gives them; a tensor-parallel case
    (``shards`` chips over ``model``) places q / k_new / v_new by ``heads``
    and the pools by ``pools``."""
    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, KVPool

    def sds(shape, dtype, sharding=None):
        if sharding is None and dev is None:      # traced, never compiled
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding or dev)

    row, lanes = CacheConfig(num_layers=1, num_kv_heads=n_kv, head_dim=d,
                             kv_dtype=kv_dtype, model_shards=shards).pool_row

    def pool():
        data = sds((row, pages, page, lanes),
                   jnp.int8 if kv_dtype == "int8" else jnp.bfloat16, pools)
        scale = (sds((row, pages, page), jnp.float32, pools)
                 if kv_dtype == "int8" else None)
        return KVPool(data, scale)

    return (sds((rows, n_kv * group, d), jnp.bfloat16, heads), pool(), pool(),
            sds((rows, pps), jnp.int32), sds((rows,), jnp.int32),
            sds((rows, n_kv, d), jnp.bfloat16, heads),
            sds((rows, n_kv, d), jnp.bfloat16, heads),
            sds((rows, 1), jnp.int32))


def _compile_dispatch(args, op=None):
    from llms_on_kubernetes_tpu.ops import attention

    op = op or attention.dispatch_paged_attention_write
    step = jax.jit(
        lambda *a: op(*a, scale=D ** -0.5, sliding_window=4096),
        donate_argnums=(1, 2))
    return step.lower(*args).compile()


def _pallas_call(jaxpr):
    """The first ``pallas_call`` equation under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _pallas_call(sub)
            if found is not None:
                return found


# pool type, page, pages a slot -> what the dispatcher must say it took.
# The 16,384-token slot is the longest that fitted the VMEM budget while a
# whole slot was staged (PR 34: 64 MiB + headroom of 96): no shape that
# took the kernel then may leave it for the XLA path.
CASES = {
    "bf16 page 64 (the cell)": (None, 64, 32, "fused write+attend kernel"),
    "bf16 page 64, a 16,384-token slot": (None, 64, 256,
                                          "fused write+attend kernel"),
    "int8 page 128": ("int8", 128, 16, "fused int8 write+attend kernel"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_append_rides_the_kernel_in_place(one_chip, no_cache,
                                                 monkeypatch, case):
    """The decode dispatcher takes the fused write+attend kernel at the
    serving geometry; Mosaic accepts it; and the compiled program writes no
    row by ``dynamic-update-slice``, copies no pool and hands both pools
    back in the buffers they came in."""
    from llms_on_kubernetes_tpu.ops import attention

    kv_dtype, page, pps, why = CASES[case]
    # the code asks the backend, which is the CPU here
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    args = _operands(one_chip, kv_dtype, page, pps)
    compiled = _compile_dispatch(args)
    assert attention._chosen["decode"] == ("pallas-compiled", why)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert " dynamic-update-slice(" not in hlo
    pools = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(args[1:3]))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 8     # no copy of a pool


LATENT_CASE = "latent 640-lane rows (deepseek-v3's cell)"


@pytest.mark.parametrize("case", sorted(CASES) + [LATENT_CASE])
def test_kernel_is_given_the_vmem_the_dispatcher_counted(monkeypatch, case):
    """``paged_vmem_bytes``, which the dispatcher holds against the budget,
    is what the kernel hands Mosaic as its limit, and is the kernel's own
    VMEM scratch (both halves of the staging and of the write blocks) plus
    the headroom: a block's worth, whatever the slot's length. The latent
    kernel's ``latent_vmem_bytes`` likewise: ONE staging, 1.3 MB."""
    from llms_on_kubernetes_tpu.ops import attention, pallas_paged

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    if case == LATENT_CASE:
        page, pps = 64, 144
        pool = jax.ShapeDtypeStruct((1, 6 * 4609, page, 640), jnp.bfloat16)
        eqn = _pallas_call(jax.make_jaxpr(
            lambda *a: attention.dispatch_latent_decode(
                *a, scale=0.1, lat=512))(
            jax.ShapeDtypeStruct((ROWS, 128, 576), jnp.bfloat16), pool,
            jax.ShapeDtypeStruct((ROWS, pps), jnp.int32),
            jax.ShapeDtypeStruct((ROWS,), jnp.int32)).jaxpr)
        counted = pallas_paged.latent_vmem_bytes(page, pps, 640, pool.dtype)
        most = 2 * 512 * 640 * 2                   # both halves of a block
    else:
        kv_dtype, page, pps, _ = CASES[case]
        args = _operands(None, kv_dtype, page, pps)
        eqn = _pallas_call(jax.make_jaxpr(
            lambda *a: attention.dispatch_paged_attention_write(
                *a, scale=D ** -0.5, sliding_window=4096))(*args).jaxpr)
        counted = pallas_paged.paged_vmem_bytes(
            N_KV, page, pps, D, args[1].data.dtype, kv_dtype == "int8")
        most = (6 << 20) - 1                       # 4 MiB + the write blocks
    scratch = sum(
        ref.size * ref.dtype.itemsize
        for ref in eqn.params["grid_mapping"].scratch_avals
        if "vmem" in str(ref.memory_space).lower())
    assert counted == scratch + pallas_paged._VMEM_HEADROOM
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == counted <= attention.VMEM_BUDGET_BYTES
    block = pallas_paged._block_tokens(page, pps)
    assert block == 512 and scratch <= most


def test_two_op_path_still_compiles_the_dus_loop(one_chip, no_cache,
                                                 monkeypatch):
    """``write_then_attend`` (the path every shape the kernel does not take
    falls back to) keeps its per-slot loop and the plain paged kernel."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    hlo = _compile_dispatch(_operands(one_chip, None, PAGE, PPS),
                            attention.write_then_attend).as_text()
    assert attention._chosen["decode"] == ("pallas-compiled", "paged kernel")
    assert hlo.count("tpu_custom_call") == 1
    assert hlo.count(" dynamic-update-slice(") >= 2 * ROWS


def test_tensor_parallel_append_stays_on_each_chips_heads(topo, no_cache,
                                                          monkeypatch):
    """``--tp 4``: each chip runs the fused kernel on its own two KV heads
    (shard_map over ``model``) and writes its own shard of the pools in
    place; nothing gathers a pool for the unpartitionable custom call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import (
        AXIS_MODEL, make_mesh, set_active_mesh,
    )
    from llms_on_kubernetes_tpu.parallel.sharding import pool_sharding

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    mesh = make_mesh(model=4, devices=list(topo.devices)[:4])
    args = _operands(
        NamedSharding(mesh, P()), None, PAGE, PPS,
        heads=NamedSharding(mesh, P(None, AXIS_MODEL)),
        pools=pool_sharding(get_config("mistral-7b"), mesh))
    set_active_mesh(mesh)
    try:
        compiled = _compile_dispatch(args)
    finally:
        set_active_mesh(None)
    assert attention._chosen["decode"] == (
        "pallas-compiled", "fused write+attend kernel")
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" not in hlo
    assert " dynamic-update-slice(" not in hlo
    shard = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(args[1:3])) // 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= shard
    assert mem.temp_size_in_bytes < shard // 8


# the lfm2-24b-a2b.long-answers cell: 32/8 heads of 64, 64 slots of 32 pages
# of 64, two attention layers of 2049 pages in one flat pool
CELL64 = dict(kv_dtype=None, page=64, pps=32, rows=64, n_kv=8, group=4, d=64,
              pages=2 * 2049)


def _pool_shaped_copies(hlo, pool):
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    return [ln for ln in hlo.splitlines()
            if (" copy(" in ln or " copy-start(" in ln)
            and shape in ln.split("=", 1)[1].split("copy")[0]]


PAIRED = ", 2 heads of 64 to a 128-lane page row"


def test_64_wide_cell_rides_the_kernel_on_paired_heads(one_chip, no_cache,
                                                       monkeypatch):
    """At the routed cell's shape the decode dispatcher takes the fused
    write+attend kernel on the pool of paired heads, Mosaic accepts it
    (its page DMA is refused at 64 lanes: the parent's reason), the record
    names the layout, both pools come back in the buffers they came in,
    nothing copies a pool or appends by ``dynamic-update-slice``, and the
    kernel's VMEM is what ``paged_vmem_bytes`` counts for 4 rows of 128."""
    from llms_on_kubernetes_tpu.ops import attention, pallas_paged

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    args = _operands(one_chip, **CELL64)
    step = jax.jit(
        lambda *a: attention.dispatch_paged_attention_write(
            *a, scale=64 ** -0.5), donate_argnums=(1, 2))
    compiled = step.lower(*args).compile()
    assert attention._chosen["decode"] == (
        "pallas-compiled", "fused write+attend kernel" + PAIRED)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert " dynamic-update-slice(" not in hlo
    assert not _pool_shaped_copies(hlo, args[1])
    pools = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(args[1:3]))
    assert pools == 537_133_056          # the configuration file's bytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 8

    eqn = _pallas_call(jax.make_jaxpr(
        lambda *a: attention.dispatch_paged_attention_write(
            *a, scale=64 ** -0.5))(*_operands(None, **CELL64)).jaxpr)
    counted = pallas_paged.paged_vmem_bytes(4, 64, 32, 128, jnp.bfloat16)
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == counted <= attention.VMEM_BUDGET_BYTES
    # the kernel sees 4 heads of 128 with twice the group
    assert eqn.invars[2].aval.shape == (64, 4, 8, 128)


def test_two_op_path_takes_the_plain_kernel_on_paired_heads(
        one_chip, no_cache, monkeypatch):
    """One gate: through ``write_then_attend`` the same pool gets the plain
    paged kernel after the write loop, with the same note on its record."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    args = _operands(one_chip, **CELL64)
    hlo = jax.jit(
        lambda *a: attention.write_then_attend(*a, scale=64 ** -0.5),
        donate_argnums=(1, 2)).lower(*args).compile().as_text()
    assert attention._chosen["decode"] == (
        "pallas-compiled", "paged kernel" + PAIRED)
    assert hlo.count("tpu_custom_call") == 1


def test_tensor_parallel_keeps_whole_pairs_on_each_chip(topo, no_cache,
                                                        monkeypatch):
    """``--tp 4`` at 8 KV heads of 64: four pairs, one a chip. Each chip
    runs the kernel on its own pair under ``shard_map`` and writes its own
    shard of the pools in place; nothing gathers a pool. (At 4 KV heads the
    axis would split a pair: ``heads_per_row`` then leaves the pool
    unpaired and the gate says so: tests/test_pallas.py.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel.mesh import (
        AXIS_MODEL, make_mesh, set_active_mesh,
    )
    from llms_on_kubernetes_tpu.parallel.sharding import pool_sharding

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    mesh = make_mesh(model=4, devices=list(topo.devices)[:4])
    args = _operands(
        NamedSharding(mesh, P()), **CELL64, shards=4,
        heads=NamedSharding(mesh, P(None, AXIS_MODEL)),
        pools=pool_sharding(get_config("lfm2-24b-a2b"), mesh))
    set_active_mesh(mesh)
    try:
        compiled = jax.jit(
            lambda *a: attention.dispatch_paged_attention_write(
                *a, scale=64 ** -0.5),
            donate_argnums=(1, 2)).lower(*args).compile()
    finally:
        set_active_mesh(None)
    assert attention._chosen["decode"] == (
        "pallas-compiled", "fused write+attend kernel" + PAIRED)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" not in hlo
    assert " dynamic-update-slice(" not in hlo
    shard = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(args[1:3])) // 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= shard
    assert mem.temp_size_in_bytes < shard // 8


@pytest.mark.parametrize("rows,bucket", [(1, 512), (4, 128)])
def test_prefill_write_into_paired_pool_copies_no_pool(one_chip, no_cache,
                                                       rows, bucket):
    """A prefill's ``write_tokens`` into the pool of paired heads stays the
    in-place page merge it is: the token rows become pool rows by a
    reshape, and nothing the size of a pool is copied or kept."""
    from llms_on_kubernetes_tpu.engine.cache import write_tokens

    _, kp, vp, pt, *_ = _operands(one_chip, **CELL64)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(write_tokens, donate_argnums=(0, 1)).lower(
        kp, vp, sds((rows, bucket, 8, 64), jnp.bfloat16),
        sds((rows, bucket, 8, 64), jnp.bfloat16),
        sds((rows, CELL64["pps"]), jnp.int32),
        sds((rows, bucket), jnp.int32)).compile()
    pools = 2 * kp.data.size * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 8
    assert not _pool_shaped_copies(compiled.as_text(), kp)


def test_128_wide_call_has_the_operands_it_had(monkeypatch):
    """At mistral-7b's shape nothing of the paired layout shows: the
    kernel's operands are q as [rows, 8, 4, 128], the pools as they are and
    the new rows as they came, and around the call there are two reshapes
    (q in, the rows out) and no transpose, product or stack."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    args = _operands(None, None, PAGE, PPS)
    jaxpr = jax.make_jaxpr(
        lambda *a: attention.dispatch_paged_attention_write(
            *a, scale=D ** -0.5, sliding_window=4096))(*args).jaxpr
    assert attention._chosen["decode"] == (
        "pallas-compiled", "fused write+attend kernel")
    eqn = _pallas_call(jaxpr)
    assert [v.aval.shape for v in eqn.invars] == [
        (ROWS, PPS), (ROWS,), (ROWS, N_KV, GROUP, D),
        (N_KV, PAGES, PAGE, D), (N_KV, PAGES, PAGE, D),
        (ROWS, N_KV, D), (ROWS, N_KV, D)]

    def outside(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                continue
            yield e.primitive.name
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from outside(sub)

    names = [n for n in outside(jaxpr) if n not in ("pjit", "jit")]
    assert sorted(names) == ["reshape", "reshape"], names


# mesh (expert x model), the stacks' type, (n, E, D, F, rows, top_k): an
# expert layer of eight experts of 4096 x 14336 (two layers of an
# eight-layer stack, 32 rows), and the lfm2-24b-a2b.long-answers cell's
MIXTRAL = (8, 8, 4096, 14336, 32, 2)
EXPERT_CASES = {
    "one chip, int8": ((1, 1), "int8", MIXTRAL),
    "--ep 4, bfloat16": ((4, 1), "bfloat16", MIXTRAL),
    "--ep 4, int8": ((4, 1), "int8", MIXTRAL),
    "--tp 4, bfloat16": ((1, 4), "bfloat16", MIXTRAL),
    "--ep 2 --tp 2, int8": ((2, 2), "int8", MIXTRAL),
    "one chip, bfloat16, the cell": ((1, 1), "bfloat16",
                                     (8, 64, 2048, 1536, 64, 4)),
}


def _expert_layers(topo, case):
    """(two expert layers over stacked weights, their operands' shapes on
    the case's mesh, the mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llms_on_kubernetes_tpu.ops import moe
    from llms_on_kubernetes_tpu.ops.quant import QTensor
    from llms_on_kubernetes_tpu.parallel.mesh import (
        AXIS_EXPERT, AXIS_MODEL, make_mesh,
    )

    (ep, tp), dtype, (n, E, D, F, N, k) = EXPERT_CASES[case]
    mesh = make_mesh(expert=ep, model=tp, devices=list(topo.devices)[:ep * tp])
    e = AXIS_EXPERT if ep > 1 else None
    m = AXIS_MODEL if tp > 1 else None

    def sds(shape, dt, spec=P()):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    def stack(shape, spec, scale_spec):
        if dtype == "bfloat16":
            return sds(shape, jnp.bfloat16, spec)
        return QTensor(sds(shape, jnp.int8, spec),
                       sds((n, E, 1, shape[3]), jnp.float32, scale_spec))

    up = (n, E, D, F), P(None, e, None, m), P(None, e, None, m)
    stacks = (stack(*up), stack(*up),
              stack((n, E, F, D), P(None, e, m, None), P(None, e)))

    def two_layers(x, router, w_gate, w_up, w_down):
        for i in range(2):
            x = x + moe.moe_block(x, router[i], w_gate, w_up, w_down,
                                  top_k=k, layer=i)[0]
        return x

    return two_layers, (sds((N, D), jnp.bfloat16),
                        sds((n, D, E), jnp.bfloat16), *stacks), mesh


def _grouped_calls(hlo):
    """The lines of ``hlo`` that call the Pallas grouped kernel."""
    return [line for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "grouped_expert_matmul" in line]


@pytest.mark.parametrize("case", sorted(EXPERT_CASES))
def test_expert_stacks_stay_where_they_are_and_as_they_are(topo, no_cache,
                                                           monkeypatch, case):
    """The grouped product takes each chip's shard of the stacks in place:
    no stack is gathered to a chip for the unpartitionable group axis, no
    layer is copied out of its stack, no int8 expert is widened in memory;
    the one exchange of a layer is the sum of its [rows, hidden] result.
    At a handful of rows an expert the dispatcher says it took the Pallas
    kernel, and the program holds it, three products a layer."""
    from llms_on_kubernetes_tpu.ops import attention, pallas_grouped
    from llms_on_kubernetes_tpu.parallel.mesh import set_active_mesh

    (ep, tp), dtype, (n, E, D, F, N, k) = EXPERT_CASES[case]
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    two_layers, args, mesh = _expert_layers(topo, case)
    set_active_mesh(mesh)
    try:
        compiled = jax.jit(two_layers).lower(*args).compile()
    finally:
        set_active_mesh(None)
    assert attention._chosen["experts"] == (
        "pallas-compiled", f"{N * k} pairs over {E} experts"
        + (f" ({E // ep} on this shard)" if ep > 1 else "") + ", row tile 16")
    hlo = compiled.as_text()
    assert len(_grouped_calls(hlo)) == 6 and "ragged-dot" not in hlo
    assert "all-gather" not in hlo
    assert hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(") == (
        2 if ep * tp > 1 else 0)
    one_expert = D * F * (1 if dtype == "int8" else 2) // tp
    # ... or, where an expert is smaller than the rows' own buffers (the
    # cell: 1,216 group-aligned rows of 2048 and of 1536, in and out),
    # under those: a layer of the stack is 1.2 GB there
    tiles = pallas_grouped.num_tiles(N * k, E // ep, 16)
    rows = 2 * tiles * 16 * (D + F // tp) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < max(
        one_expert // 4, rows)


def test_past_the_rows_an_expert_it_was_measured_at_ragged_dot_stays(
        topo, no_cache, monkeypatch):
    """A prefill of 32,768 rows over eight experts (8,192 pairs an expert
    at the mean, twice what the kernel was timed at) stays on
    ``jax.lax.ragged_dot``, and the dispatcher says why."""
    from llms_on_kubernetes_tpu.ops import attention, moe

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setitem(EXPERT_CASES, "a prefill",
                        ((1, 1), "bfloat16", (2, 8, 1024, 512, 32768, 2)))
    two_layers, args, _ = _expert_layers(topo, "a prefill")
    hlo = jax.jit(two_layers).lower(*args).compile().as_text()
    assert attention._chosen["experts"] == (
        "xla", "ragged_dot, 65536 pairs over 8 experts: 8192 rows an expert, "
        "not measured")
    assert "ragged-dot" in hlo and not _grouped_calls(hlo)
    assert 65536 > moe.KERNEL_MAX_MEAN_ROWS * 8


def test_grouped_kernel_is_given_the_vmem_the_dispatcher_counted(monkeypatch):
    """At the cell's decode shape ``grouped_vmem_bytes``, which ops/moe.py
    holds against the budget, is what each of the three products hands
    Mosaic as its limit, and is the pipeline's two buffers of every block
    plus the float32 product and the headroom."""
    from llms_on_kubernetes_tpu.ops import attention, moe, pallas_grouped

    n, E, D, F, N, k = 8, 64, 2048, 1536, 64, 4
    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    bf16 = jnp.bfloat16
    jaxpr = jax.make_jaxpr(
        lambda x, sel, a, g, u, d: moe.grouped_experts(
            x, sel, a, g, u, d, layer=3))(
        jax.ShapeDtypeStruct((N, D), bf16),
        jax.ShapeDtypeStruct((N, k), jnp.int32),
        jax.ShapeDtypeStruct((N, k), jnp.float32),
        jax.ShapeDtypeStruct((n, E, D, F), bf16),
        jax.ShapeDtypeStruct((n, E, D, F), bf16),
        jax.ShapeDtypeStruct((n, E, F, D), bf16)).jaxpr
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    assert len(calls) == 3
    for eqn, (K, width) in zip(calls, ((D, F), (D, F), (F, D))):
        mapping = eqn.params["grid_mapping"]
        blocks = sum(
            2 * np.prod([getattr(d, "block_size", 1) for d in bm.block_shape])
            * bm.array_aval.dtype.itemsize
            for bm in mapping.block_mappings)
        tk, tn = pallas_grouped.weight_block(K, width, 2)
        assert tk == K and tk * tn * 2 >= 2 << 20     # an expert is read once
        counted = pallas_grouped.grouped_vmem_bytes(16, K, width, 2, 2)
        assert counted == (blocks + 16 * tn * 4
                           + pallas_grouped._VMEM_HEADROOM)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert limit == counted <= attention.VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# deepseek-v3's cell: the engine's real-size steps for a described v5e
# ---------------------------------------------------------------------------

# the cell's geometry (benchmark/configs/deepseek-v3.json): 32 slots of 144
# pages of 64 tokens, 4609 pages; step -> (rows, tokens a row)
DEEPSEEK = "deepseek-v3@0,3-7+experts0-15+vocab0-16159"
DEEPSEEK_STEPS = {"decode, K = 4": (32, 1), "prefill 1 x 2048": (1, 2048),
                  "prefill 4 x 512": (4, 512), "chunk 1 x 2048": (1, 2048)}
V5E_BYTES = 16.9e9


@pytest.mark.parametrize("step", sorted(DEEPSEEK_STEPS))
def test_deepseek_v3_steps_fit_a_v5e_and_leave_the_latent_pool_in_place(
        one_chip, no_cache, monkeypatch, step):
    """The fused decode window, both buckets and a 2,048-token chunk over
    a 9,216-token window, at the published widths with the cell's weights
    (11.0 GB) and latent pool (2.27 GB): each compiles for a v5e with at
    least a gigabyte to spare, the pool goes in and comes out in one
    buffer, and no step copies it (a 576-wide row, left unpadded, was
    re-laid out around every write: 386 pool-sized copies a window). The
    prompts' steps take the latent flash kernel (PR 47), six custom calls
    beside the experts', and no tile of scores is left in the program
    around them (the XLA loop's were f32[128,256,256], 33.5 MB each)."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine import engine as E
    from llms_on_kubernetes_tpu.engine.cache import KVPool
    from llms_on_kubernetes_tpu.models import decoder
    from llms_on_kubernetes_tpu.ops import attention, pallas_flash

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setenv("LLMK_UNROLL_LAYERS", "1")
    cfg = get_config(DEEPSEEK)
    slots, page, pps, pages = 32, 64, 144, 4609

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: decoder.init_params(cfg, jax.random.key(0),
                                        dtype="bfloat16")))
    pool = KVPool(sds((1, cfg.num_attn_layers * pages, page, 640),
                      jnp.bfloat16))
    no_v = KVPool(sds((1, 1, 1, 1), jnp.bfloat16))
    counts = sds((slots, cfg.vocab_size), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    rows, tokens = DEEPSEEK_STEPS[step]
    if step.startswith("decode"):
        compiled = jax.jit(
            E._decode_multi_packed_step, static_argnums=(1, 2),
            donate_argnums=(6, 7, 8, 11)).lower(
                params, cfg, 4, sds((rows, E._DEC_COLS + pps), jnp.int32),
                sds((rows,), jnp.int32), sds((1,), jnp.int32), pool, no_v,
                counts, key, None, None).compile()
    else:
        fn, cols = ((E._prefill_packed_step, E._PRE_COLS)
                    if step.startswith("prefill")
                    else (E._chunk_packed_step, E._CHK_COLS))
        compiled = jax.jit(fn, static_argnums=(1,),
                           donate_argnums=(4, 5, 6, 9)).lower(
            params, cfg, sds((rows, tokens), jnp.int32),
            sds((rows, cols + pps), jnp.int32), pool, no_v, counts, key,
            None, None).compile()
    mem = compiled.memory_analysis()
    pool_bytes = cfg.num_attn_layers * pages * page * 640 * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"[deepseek_compile] {step}: temp {mem.temp_size_in_bytes} B, "
          f"peak {peak} B, {compiled.as_text().count(chr(10))} HLO lines")
    assert peak < V5E_BYTES - 1e9, (step, peak)
    assert not _pool_shaped_copies(compiled.as_text(), pool)
    kind = step.split()[0].rstrip(",")
    # the token step rides the latent decode kernel, the prompts' steps
    # the latent flash kernel
    assert attention._chosen[kind][0] == "pallas-compiled"
    assert attention._chosen["experts"][0] == "pallas-compiled"
    if kind != "decode":
        import re

        assert attention._chosen[kind][1].startswith("latent flash kernel")
        hlo = compiled.as_text()
        assert hlo.count("flash_latent_attention") >= cfg.num_attn_layers
        qb, kb, _ = pallas_flash.latent_flash_blocks(
            tokens, tokens if kind == "prefill" else pps * page,
            cfg.num_heads)
        # all heads' scores of a tile pair, at the XLA loop's blocks or
        # the kernel's
        tiles = re.findall(
            rf"f32\[{cfg.num_heads},(?:256,256|{qb},{kb})\]", hlo)
        assert not tiles, sorted(set(tiles))


# bucket, rows attended: both buckets over their own rows, a chunk over the
# slot's 144 pages of 64
DEEPSEEK_FLASH = {"bucket 512": (512, 512), "bucket 2048": (2048, 2048),
                  "chunk 2048 over a 144-page table": (2048, 9216)}


@pytest.mark.parametrize("case", sorted(DEEPSEEK_FLASH))
def test_latent_flash_kernel_is_given_the_vmem_the_dispatcher_counted(
        one_chip, no_cache, monkeypatch, case):
    """The prompts' attention alone at the cell's widths (128 heads, a
    latent of 512, keys of 128 + 64): the dispatcher takes the kernel,
    Mosaic takes it, it is given exactly the VMEM the dispatcher counted
    against its budget, and a chunk's only temporary beside the padded
    rotated queries is the slot's gathered rows (11.8 MB): no pool."""
    from llms_on_kubernetes_tpu.ops import attention, pallas_flash

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    T, S = DEEPSEEK_FLASH[case]
    H, lat, rope, nope, vd, page, pages = 128, 512, 64, 128, 128, 64, 4609

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = (sds((1, T, H, nope)), sds((1, T, H, rope)))
    w = (sds((H, lat, nope)), sds((H, lat, vd)))
    n = sds((1,), jnp.int32)
    if case.startswith("bucket"):
        fn = lambda *a: attention.dispatch_latent_prefill(*a, scale=0.1)
        args = (*q, sds((1, T, lat + rope)), *w, n)
    else:
        fn = lambda *a: attention.dispatch_latent_chunk(*a, scale=0.1)
        args = (*q, sds((1, 6 * pages, page, 640)),
                sds((1, S // page), jnp.int32), *w, n, n)
    eqn = _pallas_call(jax.make_jaxpr(fn)(*args).jaxpr)
    counted = pallas_flash.latent_flash_vmem_bytes(T, S, H, lat, 128, nope,
                                                   vd, 2)
    limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert limit == counted <= attention.VMEM_BUDGET_BYTES
    compiled = jax.jit(fn).lower(*args).compile()
    kind = "prefill" if case.startswith("bucket") else "chunk"
    assert attention._chosen[kind][0] == "pallas-compiled"
    assert compiled.as_text().count("tpu_custom_call") == 1
    padded_qr = T * H * 128 * 2
    gathered = 0 if kind == "prefill" else S * 640 * 2
    # (a bucket's rows padded to 640 lanes are under a megabyte)
    assert (compiled.memory_analysis().temp_size_in_bytes
            <= padded_qr + gathered + (2 << 20))


def _decode_window(one_chip, model, slots, pps, pool_shape):
    """(cfg, the per-slot state's shapes, the compiled fused K = 4 decode
    window) of a model that keeps slot state, at a cell's sizes, for a
    described v5e."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine import engine as E
    from llms_on_kubernetes_tpu.engine.cache import KVPool
    from llms_on_kubernetes_tpu.models import decoder

    cfg = get_config(model)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def shaped(shape, dtype):
        return sds(jax.ShapeDtypeStruct(shape, dtype))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: decoder.init_params(cfg, jax.random.key(0),
                                    dtype="bfloat16")))
    state = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: decoder.init_conv_state(cfg, slots, "bfloat16")))
    pool = KVPool(shaped(pool_shape, jnp.bfloat16))
    key = sds(jax.eval_shape(lambda: jax.random.key(0)))
    return cfg, state, jax.jit(
        E._decode_multi_packed_step, static_argnums=(1, 2),
        donate_argnums=(6, 7, 8, 11)).lower(
            params, cfg, 4, shaped((slots, E._DEC_COLS + pps), jnp.int32),
            shaped((slots,), jnp.int32), shaped((1,), jnp.int32), pool, pool,
            shaped((slots, cfg.vocab_size), jnp.int32), key, None,
            state).compile()


def _scheduled_ops(hlo):
    """The lines of an optimized HLO text that are ops of their own on the
    device: every computation's but a fusion's body's, less the ones that
    move nothing."""
    out, fused = [], False
    for ln in hlo.splitlines():
        if ln.endswith("{") and ("(" in ln) and not ln.startswith(" "):
            fused = "fused_computation" in ln
        elif not fused and " = " in ln and not any(
                f" {op}(" in ln for op in ("parameter", "bitcast", "tuple",
                                           "get-tuple-element", "while")):
            out.append(ln)
    return out


def test_jamba_decode_window_keeps_the_mamba_state_in_place(
        one_chip, no_cache, monkeypatch):
    """The fused K = 4 decode window of jamba2-3b at its cell's sizes (6.06
    GB of weights, a 0.27 GB pool, 1.2 GB of per-slot Mamba state): it
    compiles for a v5e with half the chip to spare, the state goes in and
    comes out in the buffers it came in, no step copies the float32
    state-space state (one copy a step would be the whole cell), the
    state-space step is the kernel over the live slots on that array in
    place (26 of them a step), the convolution window's step the kernel
    over the live tiles of rows on ITS array in place (26 more) with no
    other op over an array of the window's shape, and the paged decode
    kernel takes one KV head under 20 query heads."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setenv("LLMK_UNROLL_LAYERS", "1")
    # the sampler's candidates as the chip takes them (approx_max_k)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, state, compiled = _decode_window(
        one_chip, "jamba2-3b", slots=128, pps=32,
        pool_shape=(1, 2 * 4097, 64, 128))
    mem = compiled.memory_analysis()
    state_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(state))
    assert state_bytes == 1_202_073_600
    assert mem.alias_size_in_bytes >= state_bytes + 2 * 134_250_496
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    print(f"[jamba_compile] decode K = 4: temp {mem.temp_size_in_bytes} B, "
          f"peak {peak} B")
    assert peak < 8.5e9, peak
    hlo = compiled.as_text()
    assert not [ln for ln in hlo.splitlines()
                if (" copy(" in ln or " copy-start(" in ln)
                and "f32[26,129,16,5120]" in ln.split("=", 1)[1][:60]]
    assert attention._chosen["decode"] == (
        "pallas-compiled", "fused write+attend kernel")
    assert attention._chosen["ssm_step"][0] == "pallas-compiled"
    calls = [ln for ln in hlo.splitlines() if "ssm_step_live_slots" in ln
             and "custom-call(" in ln]
    assert len(calls) == cfg.num_mamba_layers
    assert all("f32[26,129,16,5120]" in ln.split("custom-call(")[0]
               for ln in calls)
    # the convolution window's token step: one kernel a Mamba layer on the
    # window array in place, and nothing else of the program touches an
    # array of the window's shape (the general T-position code at T = 1 ran
    # ten scheduled ops a layer over them, a re-layout copy among them; the
    # XLA one-pass form runs four: PERF.md section 6, PR 52)
    assert attention._chosen["conv_step"][0] == "pallas-compiled"
    window = ("bf16[128,4,5120]", "bf16[128,3,5120]", "bf16[128,15360]",
              "bf16[1,128,15360]", "bf16[26,129,15360]")
    scheduled = _scheduled_ops(hlo)
    assert not [ln for ln in scheduled
                if (" copy(" in ln or " copy-start(" in ln)
                and any(sh in ln.split(" copy")[0] for sh in window)]
    calls = [ln for ln in scheduled if "conv_step_live_tiles" in ln
             and "custom-call(" in ln]
    assert len(calls) == cfg.num_mamba_layers
    assert all("bf16[26,129,15360]" in ln.split("custom-call(")[0]
               and "output_to_operand_aliasing={{2}: (8, {})}" in ln
               for ln in calls)

    def result(ln):     # the type of what the op writes, a tuple's whole
        rhs = ln.split(" = ", 1)[1]
        return rhs.split(") ", 1)[0] if rhs.startswith("(") \
            else rhs.split("(", 1)[0]

    others = [ln for ln in scheduled if ln not in calls
              and any(sh in result(ln) for sh in window[:4])]
    assert not others, others[:3]
    # PR 53: the penalty counts' update and the sampler's [slots, vocab]
    # work each sit in a conditional of this one executable. The first
    # hands the counts through in place (nothing copies them); out of the
    # second come the candidates and the log-sum-exp alone (without the
    # barrier in sampling._candidates XLA hoists the branches' common tail
    # out and each branch hands it two float32 [128, 65536] arrays)
    conds = [ln for ln in scheduled if " conditional(" in ln]
    assert len(conds) == 2, len(conds)
    assert sorted("[128,65536]" in result(ln) for ln in conds) == [
        False, True], [result(ln) for ln in conds]
    assert "s32[128,65536]" in result(
        next(ln for ln in conds if "[128,65536]" in result(ln)))
    assert not [ln for ln in hlo.splitlines()
                if (" copy(" in ln or " copy-start(" in ln)
                and "s32[128,65536]" in ln.split("=", 1)[1][:60]]


def test_lfm2_conv_layers_take_the_one_pass_token_step(one_chip, no_cache,
                                                       monkeypatch):
    """The fused K = 4 decode window of two conv layers and an attention
    layer of lfm2-24b-a2b at its cell's sizes (64 slots, state
    ``bf16[n, 65, 2, 2048]``): a conv layer's token step reads its rows
    out of the state array, selects once and writes them back: two
    scheduled ops a layer whose result has the state's shape where the
    general T-position code at T = 1 ran four (the taps joined with the
    input to ``bf16[64, 3, 2048]``, a gather by ``n_valid``, a reshape, a
    second select), and no array of taps + 1 positions exists."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setenv("LLMK_UNROLL_LAYERS", "1")
    cfg, state, compiled = _decode_window(
        one_chip, "lfm2-24b-a2b@4-6", slots=64, pps=32,
        pool_shape=(4, 2049, 64, 128))
    assert cfg.layer_types == ("conv", "conv", "full_attention")
    assert state.shape == (2, 65, 2, 2048)
    hlo = compiled.as_text()
    scheduled = _scheduled_ops(hlo)
    assert not [ln for ln in scheduled if "bf16[64,3,2048]" in ln]
    shapes = ("bf16[64,2,2048]", "bf16[1,64,2,2048]", "bf16[2,65,2,2048]")
    # (the whole array is copied at the window's two ends, as it was)
    over = [ln for ln in scheduled if " copy(" not in ln
            and any(sh in ln.split(" = ", 1)[1].split("(", 1)[0]
                    for sh in shapes)]
    assert len(over) <= 2 * cfg.num_conv_layers, over
    assert not [ln for ln in over if " reshape(" in ln or " gather(" in ln]


# ---------------------------------------------------------------------------
# mellum2-12b's cell: three window layers to one full layer, 64 experts
# ---------------------------------------------------------------------------

MELLUM = "mellum2-12b@0-11"
MELLUM_CELL = dict(slots=48, page=64, pps=144, pages=2561)
MELLUM_STEPS = {"decode, K = 4": (48, 1), "prefill 1 x 2048": (1, 2048),
                "prefill 4 x 512": (4, 512), "chunk 1 x 2048": (1, 2048)}


def _mellum_step(one_chip, step, cell=MELLUM_CELL):
    """The compiled step of the cell at its real size, for a described
    v5e."""
    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine import engine as E
    from llms_on_kubernetes_tpu.engine.cache import KVPool
    from llms_on_kubernetes_tpu.models import decoder

    cfg = get_config(MELLUM)
    slots, page, pps, pages = (cell[k] for k in ("slots", "page", "pps",
                                                 "pages"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: decoder.init_params(cfg, jax.random.key(0),
                                        dtype="bfloat16")))
    shape = (cfg.num_kv_heads, cfg.num_attn_layers * pages, page,
             cfg.head_dim)
    k_pool, v_pool = (KVPool(sds(shape, jnp.bfloat16)) for _ in "kv")
    counts = sds((slots, cfg.vocab_size), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    rows, tokens = MELLUM_STEPS[step]
    if step.startswith("decode"):
        return cfg, k_pool, jax.jit(
            E._decode_multi_packed_step, static_argnums=(1, 2),
            donate_argnums=(6, 7, 8, 11)).lower(
                params, cfg, 4, sds((rows, E._DEC_COLS + pps), jnp.int32),
                sds((rows,), jnp.int32), sds((1,), jnp.int32), k_pool,
                v_pool, counts, key, None, None).compile()
    fn, cols = ((E._prefill_packed_step, E._PRE_COLS)
                if step.startswith("prefill")
                else (E._chunk_packed_step, E._CHK_COLS))
    return cfg, k_pool, jax.jit(
        fn, static_argnums=(1,), donate_argnums=(4, 5, 6, 9)).lower(
            params, cfg, sds((rows, tokens), jnp.int32),
            sds((rows, cols + pps), jnp.int32), k_pool, v_pool, counts,
            key, None, None).compile()


def _peak(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("step", sorted(MELLUM_STEPS))
def test_mellum_steps_fit_a_v5e_on_kernels_with_static_windows(
        one_chip, no_cache, monkeypatch, step):
    """The fused decode window, both buckets and a 2,048-token chunk over a
    9,216-token slot, at the published widths with the cell's weights
    (10.93 GB) and pool (4.03 GB): each compiles for a v5e with room to
    spare, both pools go in and come out in one buffer each, no step
    copies a pool, and the window and the full layers BOTH take a Pallas
    kernel (the window a Python int in each): the paged write-and-attend
    kernel, the flash prefill kernel, the flash chunk kernel."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setenv("LLMK_UNROLL_LAYERS", "1")
    attention._chosen.clear()
    cfg, pool, compiled = _mellum_step(one_chip, step)
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    print(f"[mellum_compile] {step}: temp {mem.temp_size_in_bytes} B, "
          f"peak {_peak(mem)} B, {hlo.count(chr(10))} HLO lines")
    pool_bytes = 4_028_104_704          # K and V together
    assert mem.alias_size_in_bytes >= pool_bytes
    assert _peak(mem) < V5E_BYTES - 0.5e9, (step, _peak(mem))
    assert not _pool_shaped_copies(hlo, pool)
    kind = step.split()[0].rstrip(",")
    for layers in ("sliding", "full"):
        impl, why = attention._chosen[f"{kind}_{layers}"]
        assert impl == "pallas-compiled", (layers, why)
        if kind == "chunk":
            assert ("inside a window of 1024" in why) == (layers == "sliding")
            # a window layer gathers 56 of the slot's 144 pages
            assert (("3584 of a slot's 9216" if layers == "sliding"
                     else "a slot's 9216") + " gathered keys") in why
    assert attention._chosen["experts"][0] == "pallas-compiled"
    if kind == "chunk":
        assert hlo.count("flash_chunk_attention") >= cfg.num_attn_layers


def test_mellum_xla_chunk_path_leaves_no_room_beside_the_cells_pool(
        one_chip, no_cache, monkeypatch):
    """Why the chunk path has a kernel: the XLA gather path holds a layer's
    scores [4, 8, 2048, 9216] in float32 (2.4 GB; written and read back
    in each of the 12 layers), so a 2,048-token chunk over the cell's slot
    peaks at 16.71 GB beside 10.93 GB of weights and a 3.22 GB pool
    (2,049 pages): under a fifth of a gigabyte from the 16.9 GB a v5e
    gives a process, where every step on the kernel leaves 2.2 GB or
    more, which the cell's pool takes 0.8 GB of (2,561 pages)."""
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
    monkeypatch.setattr(attention, "_chunk_kernel_mode",
                        lambda *a: (None, "shut", None))
    monkeypatch.setenv("LLMK_UNROLL_LAYERS", "1")
    jax.clear_caches()      # the step's trace is kept by its function
    try:
        _cfg, _pool, compiled = _mellum_step(
            one_chip, "chunk 1 x 2048", dict(MELLUM_CELL, pages=2049))
    except Exception as e:       # the compiler refuses what cannot fit
        assert "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower()
        return
    finally:
        jax.clear_caches()
    mem = compiled.memory_analysis()
    print(f"[mellum_compile] xla chunk: temp {mem.temp_size_in_bytes} B, "
          f"peak {_peak(mem)} B")
    assert mem.temp_size_in_bytes > 2.4e9
    assert _peak(mem) > V5E_BYTES - 0.5e9
