"""Ring attention (context parallelism) + multi-host plumbing.

Ring attention runs on a virtual 8-device CPU ring (conftest forces
xla_force_host_platform_device_count=8) and is pinned against the
single-device XLA reference — the long-context capability the reference
stack lacked entirely (SURVEY §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.ops.attention import prefill_attention
from llms_on_kubernetes_tpu.ops.ring_attention import ring_prefill_attention
from llms_on_kubernetes_tpu.parallel.mesh import make_mesh


@pytest.mark.parametrize("ring", [2, 4, 8])
def test_ring_matches_reference(rng, ring):
    B, T, n_q, n_kv, d = 2, 64, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, n_q, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    lengths = jnp.asarray([T, T - 17], jnp.int32)

    ref = prefill_attention(q, k, v, lengths, scale=d ** -0.5)
    mesh = make_mesh(seq=ring, model=1)
    out = ring_prefill_attention(q, k, v, lengths, mesh, scale=d ** -0.5)
    # padding rows are don't-care; compare valid rows only
    for b, n in enumerate([T, T - 17]):
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n],
                                   rtol=2e-5, atol=2e-5)


def test_ring_softcap_and_window(rng):
    B, T, n_q, n_kv, d = 1, 32, 2, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, n_q, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    lengths = jnp.asarray([T], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, scale=d ** -0.5,
                            sliding_window=9, attn_softcap=30.0)
    mesh = make_mesh(seq=4, model=1)
    out = ring_prefill_attention(q, k, v, lengths, mesh, scale=d ** -0.5,
                                 sliding_window=9, attn_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_composes_with_tensor_parallel(rng):
    """seq x model mesh: ring over 4 devices, TP over 2."""
    B, T, n_q, n_kv, d = 1, 32, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, n_q, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, n_kv, d)), jnp.float32)
    lengths = jnp.asarray([T], jnp.int32)
    ref = prefill_attention(q, k, v, lengths, scale=d ** -0.5)
    mesh = make_mesh(seq=4, model=2)
    out = ring_prefill_attention(q, k, v, lengths, mesh, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# multi-host plumbing (single-process units)
# ---------------------------------------------------------------------------

def test_pod_ordinal_parsing():
    from llms_on_kubernetes_tpu.parallel.distributed import pod_ordinal

    assert pod_ordinal("model-llama-3-70b-0") == 0
    assert pod_ordinal("model-llama-3-70b-13") == 13
    with pytest.raises(ValueError):
        pod_ordinal("api-gateway")


def test_distributed_env_contract(monkeypatch):
    from llms_on_kubernetes_tpu.parallel.distributed import (
        distributed_env, is_coordinator,
    )

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    assert distributed_env() is None
    assert is_coordinator()

    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "model-x-0.svc:8476")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    monkeypatch.setenv("POD_NAME", "model-x-2")
    env = distributed_env()
    assert env == {"coordinator_address": "model-x-0.svc:8476",
                   "num_processes": 4, "process_id": 2}
    assert not is_coordinator()
    monkeypatch.setenv("POD_NAME", "model-x-0")
    assert is_coordinator()
    monkeypatch.setenv("JAX_PROCESS_ID", "9")
    with pytest.raises(ValueError, match="out of range"):
        distributed_env()


def test_multihost_message_struct_fixed_shape():
    """Every broadcast message must have ONE fixed pytree shape derived
    from the EngineConfig — that is the v2 protocol contract (coordinator
    and follower build it independently; a mismatch deadlocks the psum)."""
    from llms_on_kubernetes_tpu.engine import multihost as mh
    from llms_on_kubernetes_tpu.engine.engine import (
        _CHK_COLS, _DEC_COLS, EngineConfig,
    )

    cfg = EngineConfig(max_decode_slots=16, pages_per_slot=32,
                       prefill_buckets=(64, 256), admit_batch=4)
    shapes = mh.ProtoShapes.from_engine_config(cfg)
    z = shapes.zeros()
    assert z["ctrl"].shape == (mh.CTRL_LEN,)
    assert z["pre_tokens"].shape == (4, 256)
    assert z["pre_packed"].shape == (4, _CHK_COLS + 32)
    assert z["dec_packed"].shape == (16, _DEC_COLS + 32)
    assert all(v.dtype == np.int32 for v in z.values())


def test_multihost_score_message_roundtrip(monkeypatch):
    """MSG_SCORE framing (PR 3): ctrl[4:6] carries (padded width, true
    length); the follow-up payload broadcast ships the [1, width] token
    row. Coordinator-side sends are replayed through the follower-side
    receive helpers — same bytes out, same bytes in."""
    from llms_on_kubernetes_tpu.engine import multihost as mh
    from llms_on_kubernetes_tpu.engine.engine import EngineConfig

    sent = []
    monkeypatch.setattr(mh, "_broadcast", lambda v: (sent.append(v), v)[1])
    cfg = EngineConfig(max_decode_slots=2, pages_per_slot=8,
                       prefill_buckets=(16,), admit_batch=2)
    shapes = mh.ProtoShapes.from_engine_config(cfg)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :5] = (1, 5, 9, 42, 17)
    mh.send_message(shapes, mh.MSG_SCORE, score=(32, 5))
    mh.send_score_payload(toks)
    assert len(sent) == 2

    replay = iter(list(sent))
    monkeypatch.setattr(mh, "_broadcast", lambda v: next(replay))
    m = mh.receive_message(shapes)
    assert int(m["ctrl"][0]) == mh.MSG_SCORE
    width, n = int(m["ctrl"][4]), int(m["ctrl"][5])
    assert (width, n) == (32, 5)
    got = mh.receive_score_payload(width)
    np.testing.assert_array_equal(got, toks)


def test_multihost_score_prompt_broadcasts_and_matches_single_host(
        monkeypatch):
    """score_prompt under multihost=True (the former hard 400): announces
    MSG_SCORE + ships the padded token row, then returns the same scores
    as a plain single-host engine."""
    from llms_on_kubernetes_tpu.engine import multihost as mh
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig

    kw = dict(model="debug-tiny", dtype="float32", max_decode_slots=2,
              page_size=16, num_pages=64, pages_per_slot=8,
              prefill_buckets=(16,))
    prompt = [1, 5, 9, 42, 17, 3]
    want = Engine(EngineConfig(**kw)).score_prompt(prompt)

    sent = []
    monkeypatch.setattr(mh, "_broadcast", lambda v: (sent.append(v), v)[1])
    got = Engine(EngineConfig(multihost=True, **kw)).score_prompt(prompt)
    assert len(sent) == 2  # one control word + one token payload
    ctrl = sent[0]["ctrl"]
    assert int(ctrl[0]) == mh.MSG_SCORE
    assert (int(ctrl[4]), int(ctrl[5])) == (16, len(prompt))
    assert sent[1].shape == (1, 16)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert got[1] == want[1]
    np.testing.assert_allclose(got[2], want[2], rtol=1e-6)


def test_engine_single_host_unaffected_by_multihost_flag_default():
    """multihost=False (default) must not touch broadcast machinery."""
    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams

    eng = Engine(EngineConfig(
        model="debug-tiny", dtype="float32", max_decode_slots=2,
        page_size=16, num_pages=64, pages_per_slot=8, prefill_buckets=(16,),
    ))
    out = eng.generate([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=4))
    assert len(out) == 4


def test_ring_attention_integrated_in_prefill_forward():
    """forward_prefill under a seq>1 mesh must route attention through the
    ring (CP) path and match the single-device forward bit-for-tolerance —
    including composition with TP (seq=2 x model=2)."""
    import jax
    from jax.sharding import NamedSharding

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.engine.cache import CacheConfig, init_pages
    from llms_on_kubernetes_tpu.models.decoder import (
        forward_prefill, init_params,
    )
    from llms_on_kubernetes_tpu.parallel.mesh import (
        make_mesh, set_active_mesh,
    )
    from llms_on_kubernetes_tpu.parallel.sharding import shard_params, shard_pool

    cfg = get_config("debug-tiny")
    params = init_params(cfg, jax.random.key(0), dtype="float32")
    B, T, page, pps = 2, 32, 8, 8
    cache = CacheConfig(num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
                        head_dim=cfg.head_dim, num_pages=B * pps + 1,
                        page_size=page, pages_per_slot=pps, dtype="float32")
    kp, vp = init_pages(cache)
    pt = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    toks = jnp.asarray(rngs_tokens(B, T, cfg.vocab_size), jnp.int32)
    lens = jnp.asarray([T, T - 9], jnp.int32)

    set_active_mesh(None)  # reference: single-device path
    ref_logits, ref_kp, _ = forward_prefill(params, cfg, toks, lens, kp, vp, pt)

    mesh = make_mesh(data=1, seq=2, expert=1, model=2)
    try:
        set_active_mesh(mesh)
        sp = shard_params(params, cfg, mesh)
        kp_s = shard_pool(kp, cfg, mesh)
        vp_s = shard_pool(vp, cfg, mesh)
        got_logits, got_kp, _ = jax.jit(forward_prefill, static_argnums=(1,))(
            sp, cfg, toks, lens, kp_s, vp_s, pt)
    finally:
        set_active_mesh(None)

    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)
    # KV cache written identically — logically, over the VALID region.
    # Under CP (seq>1, round 4) the flat pool folds layers PAGE-MAJOR
    # (flat = pid*L + layer); rearrange to the reference's layer-major
    # layout first. Only positions < lengths are compared: beyond them
    # the two write paths leave different (never-read) filler — the
    # non-CP path blind-writes clamped duplicates into append territory,
    # the CP path preserves old bytes via read-merge.
    KV, flat, pg, d = ref_kp.data.shape
    L = cfg.num_layers
    P = flat // L
    got = np.asarray(got_kp.data).reshape(KV, P, L, pg, d)
    got = got.transpose(0, 2, 1, 3, 4).reshape(KV, flat, pg, d)
    ref = np.asarray(ref_kp.data)
    pt_np = np.asarray(pt)
    for b in range(B):
        for pos in range(int(lens[b])):
            fl = np.arange(cfg.num_layers) * P + pt_np[b, pos // page]
            np.testing.assert_allclose(
                got[:, fl, pos % page], ref[:, fl, pos % page],
                rtol=2e-4, atol=2e-4, err_msg=f"row {b} pos {pos}")


def rngs_tokens(B, T, V):
    return np.random.default_rng(3).integers(1, V - 1, (B, T))
