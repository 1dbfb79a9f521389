"""DeepSeek-V3's block on the normal path against its plain reference.

The program (models/decoder.py, ops/attention.py, ops/moe.py, ops/rope.py,
engine/cache.py, engine/engine.py) is held to
``benchmark/reference/deepseek_v3.py`` (float32 ``jax.numpy``, the expanded
form of latent attention only, no cache, no batching, importing nothing of
the program) on the seeded random weights of ``debug-deepseek`` cut by name
to a share: latent attention with both ranks and YaRN rope in every layer, a
dense layer and two expert layers with a shared expert, group-limited
routing over 8 experts of which 4 are held, 260 of 300 vocabulary rows.
"""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from reference import deepseek_v3 as ref  # noqa: E402

from llms_on_kubernetes_tpu.configs import (  # noqa: E402
    DEEPSEEK_YARN, from_hf_config, get_config,
)
from llms_on_kubernetes_tpu.engine.cache import (  # noqa: E402
    CacheConfig, init_pages, write_latent,
)
from llms_on_kubernetes_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.models import decoder as dec  # noqa: E402
from llms_on_kubernetes_tpu.ops import attention, moe, rope  # noqa: E402


def config_file(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


REF_CFG = config_file("debug-deepseek")
NAME = REF_CFG["registry_name"]
CFG = get_config(NAME)
PAGE, PPS, SLOTS = 8, 8, 4
F32_TOL = 1e-4


@pytest.fixture(scope="module")
def params32():
    return dec.init_params(CFG, jax.random.key(0), dtype="float32")


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def ref_logits(params, tokens, positions=None, cfg=REF_CFG):
    positions = range(len(tokens)) if positions is None else positions
    return np.asarray(ref.logits_at(cfg, params, list(tokens),
                                    list(positions)))


class Cache:
    """The latent pool and page tables for SLOTS slots, and the jitted
    forward passes: what the engine's steps hand to models/decoder.py."""

    def __init__(self, params, cfg=CFG):
        self.params, self.cfg = params, cfg
        heads, width = cfg.cache_row
        cc = CacheConfig(num_layers=cfg.num_attn_layers, num_kv_heads=heads,
                         head_dim=width, num_pages=SLOTS * PPS + 1,
                         page_size=PAGE, pages_per_slot=PPS, dtype="float32",
                         latent=True)
        self.kp, self.vp = init_pages(cc)
        self.tables = 1 + np.arange(SLOTS * PPS, dtype=np.int32).reshape(
            SLOTS, PPS)
        self._prefill = jax.jit(dec.forward_prefill, static_argnums=(1,))
        self._chunk = jax.jit(dec.forward_chunk, static_argnums=(1,))
        self._decode = jax.jit(dec.forward_decode, static_argnums=(1,))

    def _keep(self, out):
        logits, self.kp, self.vp, aux = out
        return np.asarray(logits), aux

    def prefill(self, rows, bucket, slots):
        toks = np.zeros((len(rows), bucket), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        return self._keep(self._prefill(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([len(r) for r in rows], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[slots]), aux=dec.LayerAux()))

    def chunk(self, row, bucket, history, slot):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(row)] = row
        return self._keep(self._chunk(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([history], jnp.int32),
            jnp.asarray([len(row)], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[[slot]]), aux=dec.LayerAux()))

    def decode(self, toks, lengths):
        return self._keep(self._decode(
            self.params, self.cfg, jnp.asarray(toks, jnp.int32),
            jnp.asarray(lengths, jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables), aux=dec.LayerAux()))


# ---------------------------------------------------------------------------
# the three paths against the reference's full forward pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(1, 16), (3, 16), (16, 16), (17, 32),
                                      (32, 32)])
def test_prefill_at_every_bucket_with_padding(params32, n, bucket):
    toks = prompt(n, n)
    got, aux = Cache(params32).prefill([toks], bucket, [2])
    np.testing.assert_allclose(got[0], ref_logits(params32, toks, [n - 1])[0],
                               atol=F32_TOL, rtol=0)
    # every real token is routed to 2 experts in each of 2 layers, to held
    # ones or elsewhere; the padding to none
    assert int(aux.moe_rows.sum()) == n * 2 * CFG.num_moe_layers
    assert aux.moe_rows.shape == (CFG.num_moe_layers, 4 + 1)


def test_batched_prefill_of_rows_of_unequal_length(params32):
    rows = [prompt(5, 1), [], prompt(16, 2), prompt(11, 3)]
    got, _ = Cache(params32).prefill(rows, 16, [0, 1, 2, 3])
    for i, r in enumerate(rows):
        if r:
            np.testing.assert_allclose(
                got[i], ref_logits(params32, r, [len(r) - 1])[0],
                atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("n", [33, 40, 63])
def test_a_chunked_prompt_attends_the_cached_latents_of_its_history(
        params32, n):
    """A prompt longer than the largest bucket (32): a first chunk at
    history 0, then chunks whose queries attend cached latent rows."""
    toks, c = prompt(n, n), Cache(params32)
    for at in range(0, n, 32):
        part = toks[at:at + 32]
        got, _ = c.chunk(part, 32 if len(part) > 16 else 16, at, 1)
        np.testing.assert_allclose(
            got[0], ref_logits(params32, toks, [at + len(part) - 1])[0],
            atol=F32_TOL, rtol=0)


def test_teacher_forced_decode_through_the_latent_cache(params32):
    """Prefill, a chunked prompt, then 17 absorbed decode steps through the
    cache, each held to the reference's FULL (expanded) forward pass of the
    whole sequence at its position; slots 0 and 2 are idle rows."""
    c = Cache(params32)
    seqs = {1: prompt(6, 11) + prompt(17, 12),
            3: prompt(40, 13) + prompt(17, 14)}
    start = {1: 6, 3: 40}
    c.prefill([seqs[1][:6]], 16, [1])
    c.chunk(seqs[3][:32], 32, 0, 3)
    c.chunk(seqs[3][32:40], 16, 32, 3)
    want = {s: ref_logits(params32, seqs[s]) for s in seqs}
    idle = np.asarray(c.kp.data)[0, c.tables[[0, 2]].reshape(-1)].copy()
    for step in range(17):
        toks, lens = [0] * SLOTS, [0] * SLOTS
        for s in seqs:
            toks[s] = seqs[s][start[s] + step]
            lens[s] = start[s] + step + 1
        got, aux = c.decode(toks, lens)
        for s in seqs:
            np.testing.assert_allclose(
                got[s], want[s][start[s] + step], atol=F32_TOL, rtol=0)
        assert int(aux.moe_rows.sum()) == 2 * 2 * CFG.num_moe_layers
    np.testing.assert_array_equal(
        np.asarray(c.kp.data)[0, c.tables[[0, 2]].reshape(-1)], idle)


def test_absorbed_equals_expanded():
    """One layer's decode token, attended both ways over the same rows."""
    rng = np.random.default_rng(5)
    H, lat, nope, rp, vd, S = 4, 16, 16, 8, 16, 24
    rows = jnp.asarray(rng.normal(size=(2, S, lat + rp)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(H, lat, nope)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(H, lat, vd)), jnp.float32)
    qn = jnp.asarray(rng.normal(size=(2, 1, H, nope)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(2, 1, H, rp)), jnp.float32)
    lengths = jnp.asarray([S, 7], jnp.int32)
    expanded = attention.latent_expanded_attention(
        qn, qr, rows, w_uk, w_uv, (lengths - 1)[:, None], lengths,
        scale=0.2, block=8)[:, 0]
    # a pool of 3-token pages holding the same rows, padded to 32 lanes
    page = 3
    pool = jnp.zeros((1, 2 * S // page + 1, page, 32), jnp.float32)
    table = 1 + jnp.arange(2 * S // page, dtype=jnp.int32).reshape(2, -1)
    pool = pool.at[0, table].set(jnp.pad(
        rows, ((0, 0), (0, 0), (0, 8))).reshape(2, -1, page, 32))
    q_lat = jnp.einsum("bhk,hrk->bhr", qn[:, 0], w_uk)
    o_lat = attention.latent_paged_attention(
        jnp.concatenate([q_lat, qr[:, 0]], -1), pool, table, lengths,
        scale=0.2, lat=lat, block_pages=2)
    absorbed = jnp.einsum("bhr,hrk->bhk", o_lat, w_uv)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=0)


@pytest.mark.parametrize("history", [0, 13, 40])
def test_expanded_attention_in_blocks_against_plain_softmax(history):
    """Three blocks of queries over up to eight blocks of keys, a chunk at
    some history (0: a bucket over itself), padded queries at its end and
    an idle row: the same as one plain causal softmax over the written
    rows."""
    rng = np.random.default_rng(9)
    H, lat, nope, rp, vd, T, S = 3, 16, 16, 8, 16, 24, 64
    n = 19                                       # real queries of the 24
    rows = jnp.asarray(rng.normal(size=(2, S, lat + rp + 8)), jnp.float32)
    w_uk = jnp.asarray(rng.normal(size=(H, lat, nope)), jnp.float32)
    w_uv = jnp.asarray(rng.normal(size=(H, lat, vd)), jnp.float32)
    qn = jnp.asarray(rng.normal(size=(2, T, H, nope)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(2, T, H, rp)), jnp.float32)
    q_pos = jnp.asarray(history + np.arange(T)[None].repeat(2, 0), jnp.int32)
    kv_len = jnp.asarray([history + n, 0], jnp.int32)
    got = attention.latent_expanded_attention(
        qn, qr, rows, w_uk, w_uv, q_pos, kv_len, scale=0.3, block=8)
    c, kr = rows[0, :, :lat], rows[0, :, lat:lat + rp]
    kn = jnp.einsum("sr,hrk->shk", c, w_uk)
    v = jnp.einsum("sr,hrk->shk", c, w_uv)
    sc = (jnp.einsum("thk,shk->hts", qn[0], kn)
          + jnp.einsum("thk,sk->hts", qr[0], kr)) * 0.3
    k_pos = np.arange(S)
    mask = (k_pos[None] <= np.asarray(q_pos[0])[:, None]) \
        & (k_pos[None] < history + n)
    sc = jnp.where(mask[None], sc, -jnp.inf)
    want = jnp.einsum("hts,shk->thk", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(got[0, :n], want[:n], atol=2e-5, rtol=0)
    assert not np.asarray(got[1]).any() and np.isfinite(got).all()
    one = attention.latent_expanded_attention(
        qn, qr, rows, w_uk, w_uv, q_pos, kv_len, scale=0.3, block=256)
    np.testing.assert_allclose(got, one, atol=2e-5, rtol=0)


def test_write_latent_appends_in_place_and_pads_the_row():
    pool = init_pages(CacheConfig(num_layers=1, num_kv_heads=1, head_dim=128,
                                  num_pages=6, page_size=4, pages_per_slot=3,
                                  dtype="float32", latent=True))[0]
    table = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    rows = jnp.arange(2 * 6 * 24, dtype=jnp.float32).reshape(2, 6, 24) + 1
    pos = jnp.asarray([[2, 3, 4, 5, 6, 7], [0, 1, 2, -1, -1, -1]], jnp.int32)
    pool = write_latent(pool, rows, table, pos)
    got = np.asarray(pool.data)[0]
    np.testing.assert_array_equal(got[1, 2:, :24], np.asarray(rows[0, :2]))
    np.testing.assert_array_equal(got[2, :, :24], np.asarray(rows[0, 2:]))
    np.testing.assert_array_equal(got[4, :3, :24], np.asarray(rows[1, :3]))
    assert not got[1, :2].any() and not got[..., 24:].any()
    # (a row's later pages, here 3, 5 and the trash, are append territory:
    # written blind with filler that no length-masked read sees)
    assert not got[4, 3].any()
    one = write_latent(pool, rows[:, :1] * 0 - 1.0, table,
                       jnp.asarray([[8], [-1]], jnp.int32))
    assert (np.asarray(one.data)[0, 3, 0, :24] == -1).all()
    np.testing.assert_array_equal(np.asarray(one.data)[0, 4],
                                  got[4])     # the idle row wrote the trash


# ---------------------------------------------------------------------------
# the share of the experts, and the routing
# ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(params32):
    """The routed parts that the two chips of this deployment compute
    (experts 0-3 and 4-7), with the shared expert counted once, add up to
    what the uncut reference gives for the whole layer."""
    rng = np.random.default_rng(3)
    whole_cfg = get_config("debug-deepseek@0,2-3")          # all 8 experts
    whole = dec.init_params(whole_cfg, jax.random.key(1), dtype="float32")
    lp = jax.tree_util.tree_map(lambda a: a[0], whole["layers"][1])
    x = jnp.asarray(rng.normal(size=(37, 64)), jnp.float32)
    kw = dict(eps=1e-6, top_k=2, n_group=2, topk_group=1, renorm=True,
              scale=2.5)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x, lp, first=0, **kw) - x
        shared = ref._swiglu(ref._rms_norm(x, lp["mlp_norm"], 1e-6),
                             lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        g = ref._rms_norm(x, lp["mlp_norm"], 1e-6)
        parts, rows = [], []
        for first in (0, 4):
            stacks = [lp[k][first:first + 4] for k in
                      ("w_gate", "w_up", "w_down")]
            # the reference, given this share
            cut = dict(lp, w_gate=stacks[0], w_up=stacks[1], w_down=stacks[2])
            part_ref = ref._experts(x, cut, first=first, **kw) - x - shared
            # the program's expert layer, told which experts it holds
            part, n = moe.moe_block(
                g, lp["router"], *stacks, top_k=2, bias=lp["router_bias"],
                scores="sigmoid", renorm=True, eps=1e-20, scale=2.5,
                n_group=2, topk_group=1, first_expert=first)
            np.testing.assert_allclose(part, part_ref, atol=2e-5, rtol=0)
            parts.append(part)
            rows.append(np.asarray(n))
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want, atol=5e-5,
                               rtol=0)
    # a pair is held by exactly one chip: each chip's "elsewhere" is the
    # other's held rows, and together they are tokens x top_k
    assert rows[0][:4].sum() == rows[1][4] and rows[1][:4].sum() == rows[0][4]
    assert rows[0].sum() == rows[1].sum() == 37 * 2


def loop_route(scores, bias, top_k, n_group, topk_group, eps, scale):
    """Group-limited routing as a plain loop over tokens; a tie goes to the
    lower index, group or expert."""
    sel, weight = [], []
    for s in np.asarray(scores, np.float64):
        p = s + bias
        size = len(p) // n_group
        group_score = [sum(sorted(p[g * size:(g + 1) * size])[-2:])
                       for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-group_score[g], g))
        kept = kept[:topk_group]
        able = [e for e in range(len(p)) if e // size in kept]
        chosen = sorted(able, key=lambda e: (-p[e], e))[:top_k]
        w = s[chosen]
        sel.append(chosen)
        weight.append(w / (w.sum() + eps) * scale)
    return np.asarray(sel), np.asarray(weight)


@pytest.mark.parametrize("case", ["random", "ties", "a negative bias"])
def test_group_limited_route_against_a_plain_loop(case):
    rng = np.random.default_rng(7)
    N, D, E, groups, keep, k = 64, 16, 16, 4, 2, 3
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, E)) * 0.5, jnp.float32)
    bias = rng.normal(size=E) * 0.05
    if case == "ties":
        # experts 1 and 2, and 5 and 9, share a column: equal scores, and
        # with an equal bias equal selection scores within and across groups
        w = w.at[:, 2].set(w[:, 1]).at[:, 9].set(w[:, 5])
        bias[2], bias[9] = bias[1], bias[5]
    if case == "a negative bias":
        bias = bias - 0.7      # kept selection scores below zero: the mask
    bias = np.asarray(bias, np.float32)  # must be -inf, 0 would win
    sel, weight = moe.route(x, w, jnp.asarray(bias), top_k=k,
                            scores="sigmoid", renorm=True, eps=1e-20,
                            scale=2.5, n_group=groups, topk_group=keep)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)))
    want_sel, want_w = loop_route(s, bias, k, groups, keep, 1e-20, 2.5)
    np.testing.assert_array_equal(np.asarray(sel), want_sel)
    np.testing.assert_allclose(np.asarray(weight), want_w, atol=1e-6, rtol=0)
    assert (np.asarray(sel) // (E // groups)).max() < groups
    # never more groups than kept
    assert max(len(set(r // (E // groups))) for r in want_sel) <= keep
    # and the reference's own router agrees
    dense = np.asarray(ref.route(
        jnp.asarray(x) @ jnp.eye(D), w, jnp.asarray(bias), top_k=k,
        n_group=groups, topk_group=keep, renorm=True, scale=2.5))
    for t in range(N):
        assert set(np.flatnonzero(dense[t])) == set(want_sel[t])


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_scale_as_published():
    assert rope.yarn_correction_range(64, 10000.0, DEEPSEEK_YARN) == (10, 23)
    m = rope.yarn_attention_factor(DEEPSEEK_YARN)
    assert abs(m - 1.36889) < 1e-5
    assert rope.yarn_attention_factor(None) == 1.0
    inv = rope.rope_frequencies(64, 10000.0, DEEPSEEK_YARN)
    theta = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:11], theta[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], theta[23:] / 40, rtol=1e-6)
    r = (np.arange(32) - 10) / 13
    np.testing.assert_allclose(
        inv[11:23], (theta * (1 - r) + theta / 40 * r)[11:23], rtol=1e-6)
    # the reference computes both on its own
    full = config_file("deepseek-v3")
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(full)), inv,
                               rtol=1e-6)
    assert abs(ref.softmax_scale(full) - 192 ** -0.5 * m * m) < 1e-9
    assert abs(ref.softmax_scale(full) - 0.135234) < 1e-5
    assert math.isclose(m, 0.1 * math.log(40) + 1)


# ---------------------------------------------------------------------------
# the engine: fused windows, a chunked prompt, the prefix cache, refusals
# ---------------------------------------------------------------------------

def engine(params, **kw):
    base = dict(model=NAME, dtype="float32", max_decode_slots=SLOTS,
                page_size=PAGE, num_pages=SLOTS * PPS + 1, pages_per_slot=PPS,
                prefill_buckets=(16, 32), async_scheduling=True,
                decode_steps=4)
    base.update(kw)
    return Engine(EngineConfig(**base), params=params)


def run(eng, reqs, limit=2000):
    for _ in range(limit):
        eng.step()
        if all(r.finished for r in reqs):
            return
    raise AssertionError("the engine did not finish")


def held_to_reference(params, req, tol=F32_TOL):
    seq = req.prompt + req.output
    lp = np.asarray(jax.nn.log_softmax(
        jnp.asarray(ref_logits(params, seq)), axis=-1))
    for j, (tok, entry) in enumerate(zip(req.output, req.output_logprobs)):
        at = len(req.prompt) - 1 + j
        assert abs(entry[0] - lp[at, tok]) < tol, (j, entry[0], lp[at, tok])
        assert tok == int(np.argmax(lp[at]))


def submit(eng, toks, n_out):
    return eng.submit(list(toks), SamplingParams(
        max_tokens=n_out, temperature=0.0, logprobs=True))


def test_the_engine_serves_all_three_paths_and_counts_them(params32):
    eng = engine(params32)
    assert eng.v_pages.shape == (1, 1, 1, 1)            # no V pool
    assert eng.k_pages.shape == (1, 3 * (SLOTS * PPS + 1), PAGE, 128)
    assert eng.cache_config.bytes_per_token == 3 * 128 * 4
    reqs = [submit(eng, prompt(7, 21), 14),       # >= 3 windows of K = 4
            submit(eng, prompt(40, 22), 13)]      # longer than bucket 32
    run(eng, reqs)
    for r in reqs:
        held_to_reference(params32, r)
    assert eng.path_tokens == {"prefill": 7, "chunk": 40}
    st = eng.moe_stats
    for kind in ("decode", "chunk", "prefill"):
        assert 0 < st[kind]["held_rows"] < st[kind]["routed_rows"]
    # held experts, not routed ones, are what there is to touch
    steps = st["decode"]["expert_slots"] / (4 * CFG.num_moe_layers)
    assert steps == int(steps) and steps >= 13
    assert st["decode"]["routed_rows"] <= steps * 2 * 2 * CFG.num_moe_layers
    said = attention._chosen
    assert "expanded" in said["prefill"][1] and "expanded" in said["chunk"][1]
    assert "absorbed" in said["decode"][1]


def test_the_prefix_cache_adopts_latent_pages(params32):
    """Latent pages are pages: a second request of one prompt adopts the
    first one's full pages and sends the rest through the chunk path."""
    eng = engine(params32)
    toks = prompt(27, 51)                           # three full pages
    a = submit(eng, toks, 6)
    run(eng, [a])
    b = submit(eng, toks, 6)
    run(eng, [b])
    assert a.output == b.output
    assert eng.allocator.hit_tokens_total == 24
    assert eng.prefix_reuse_skipped == {"recurrent_state": 0}
    held_to_reference(params32, b)


@pytest.mark.parametrize("impl,said,prompts", [
    ("auto", "xla (absorbed: 4 query heads over one",
     "xla (the bucket's own latent rows, expanded to 4 heads"),
    ("pallas", "pallas-interpret (latent: 4 query heads over one 128-lane "
               "row a token, live pages only)",
     "pallas-interpret (latent flash kernel: the bucket's own latent rows"),
])
def test_debug_engine_lists_the_attention_records(params32, monkeypatch, impl,
                                                  said, prompts):
    """``GET /debug/engine`` says under ``"attention"`` what each dispatcher
    last chose, the absorbed decode step's and the prompt bucket's among
    them: the XLA loops with their reason on the CPU backend, the latent
    kernels where the kernels run (interpreted here), whose streams are
    the reference's too."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer
    from llms_on_kubernetes_tpu.server.openai_api import OpenAIServer

    monkeypatch.setenv("LLMK_ATTENTION_IMPL", impl)
    jax.clear_caches()        # the engine's step traces outlive an Engine
    attention._chosen.clear()
    eng = engine(params32)
    req = submit(eng, prompt(7, 61), 9)
    run(eng, [req])
    held_to_reference(params32, req)

    async def body():
        client = TestClient(TestServer(
            OpenAIServer(eng, ByteTokenizer(), NAME).make_app()))
        await client.start_server()
        try:
            return await (await client.get("/debug/engine")).json()
        finally:
            await client.close()

    try:
        snap = asyncio.run(body())
    finally:
        jax.clear_caches()
    assert set(snap["attention"]) >= {"prefill", "decode", "experts"}
    assert snap["attention"]["decode"].startswith(said)
    assert snap["attention"]["prefill"].startswith(prompts)


def test_preemption_and_resume(params32):
    eng = engine(params32, max_decode_slots=2, num_pages=7)
    reqs = [submit(eng, prompt(12, 41), 18), submit(eng, prompt(12, 42), 18)]
    run(eng, reqs)
    assert eng.preemptions >= 1
    for r in reqs:
        assert len(r.output) == 18
        held_to_reference(params32, r)


@pytest.mark.parametrize("kw,word", [
    (dict(quantization="int8"), "--quantization"),
    (dict(speculation="ngram"), "speculation"),
    (dict(kv_host_cache_gb=0.1), "host KV tier"),
    (dict(adapters=(("a", "/nowhere"),)), "LoRA"),
    (dict(multihost=True), "multihost"),
    (dict(role="decode", kv_host_cache_gb=0.1), "role"),
    (dict(kv_cache_dtype="int8"), "int8 KV cache"),
])
def test_what_cannot_take_a_latent_pool_refuses_at_start_up(kw, word):
    with pytest.raises(ValueError, match="latent attention.*does not support"):
        try:
            Engine(EngineConfig(model=NAME, **kw))
        except ValueError as e:
            assert word in str(e)
            raise


def test_a_mesh_and_a_checkpoint_are_refused(tmp_path):
    from llms_on_kubernetes_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="more than one device"):
        Engine(EngineConfig(model=NAME),
               mesh=make_mesh(expert=2, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="no tensor names"):
        Engine(EngineConfig(model=NAME), model_dir=str(tmp_path))
    # a model of ONE run with latent attention refuses alike
    one_run = dataclasses.replace(CFG, name="x", num_dense_layers=0)
    assert len(one_run.layer_runs) == 1
    with pytest.raises(ValueError, match="latent attention"):
        Engine(EngineConfig(model="x", speculation="ngram"),
               model_config=one_run)


# ---------------------------------------------------------------------------
# the registry, the name's grammar, the published file, the bytes
# ---------------------------------------------------------------------------

def test_the_registry_entry_is_the_published_model_and_the_name_the_cut():
    full = get_config("deepseek-v3")
    assert get_config("deepseek-ai/DeepSeek-V3") is full
    assert full.layer_runs == (("mla", "dense", 0, 3), ("mla", "moe", 3, 58))
    assert 670e9 < full.num_params < 672e9
    assert full.cache_row == (1, 640) and full.latent_width == 576
    cut = get_config("deepseek-v3@0,3-7+experts0-15+vocab0-16159")
    assert cut.layer_runs == (("mla", "dense", 0, 1), ("mla", "moe", 1, 5))
    assert (cut.num_experts, cut.num_held_experts, cut.first_expert,
            cut.vocab_size) == (256, 16, 0, 16160)
    assert dataclasses.replace(
        cut, name="deepseek-v3", num_layers=61, num_dense_layers=3,
        experts_held=None, vocab_size=129280) == full
    assert get_config("deepseek-v3@0,3-7+experts16-31").first_expert == 16
    for bad in ("deepseek-v3@", "deepseek-v3@+experts0-15",
                "deepseek-v3@0,3-7+experts0-256", "deepseek-v3@0,3-7+heads0-3",
                "deepseek-v3@0,3-7+vocab8-15", "deepseek-v3@0,3-7+vocab0-x",
                "deepseek-v3@0,3-7+experts0-3+experts4-7",
                "mistral-7b@0-3+vocab0-99"):
        with pytest.raises(KeyError):
            get_config(bad)


def test_from_hf_config_reads_the_published_keys():
    doc = config_file("deepseek-v3")
    whole = dict(doc, **{k: doc["published"][k] for k in doc["reduced"]})
    cfg = from_hf_config(whole, name="deepseek-v3")
    assert cfg == get_config("deepseek-v3")
    cut = from_hf_config(doc, name="x")     # the file's own numbers
    assert (cut.num_layers, cut.num_dense_layers, cut.vocab_size) == (
        6, 1, 16160)
    with pytest.raises(NotImplementedError, match="yarn for deepseek_v3"):
        from_hf_config(dict(whole, model_type="llama"))
    with pytest.raises(NotImplementedError, match="mscale"):
        from_hf_config(dict(whole, rope_scaling=dict(
            whole["rope_scaling"], mscale_all_dim=0.5)))


@pytest.mark.parametrize("name", ["debug-deepseek", "deepseek-v3"])
def test_expected_bytes_are_the_seeded_trees_and_the_shape_counts(name):
    from harness import shapes_deepseek_v3 as shapes

    doc = config_file(name)
    cfg = get_config(doc["registry_name"])
    tree = jax.eval_shape(
        lambda k: dec.init_params(cfg, k, dtype="bfloat16"),
        jax.random.key(0))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))
    assert held == shapes.weight_bytes(doc) == doc["expected_bytes"]["weights"]
    flags = doc["serve_flags"]
    heads, width = cfg.cache_row
    cc = CacheConfig(num_layers=cfg.num_attn_layers, num_kv_heads=heads,
                     head_dim=width, num_pages=flags["--num-pages"],
                     page_size=flags["--page-size"], latent=True)
    k_pool, v_pool = jax.eval_shape(lambda: init_pages(cc))
    assert int(np.prod(v_pool.shape)) == 1
    assert int(np.prod(k_pool.shape)) * 2 == shapes.pool_bytes(doc) \
        == doc["expected_bytes"]["pool"] \
        == cc.bytes_per_token * flags["--num-pages"] * flags["--page-size"]
    # what the algorithm needs of a token: the unpadded latent row a layer
    assert shapes.kv_bytes_per_token(doc) == (
        cfg.latent_width * 2 * cfg.num_layers)
    if name == "deepseek-v3":
        assert shapes.kv_bytes_per_token(doc) == 1152 * 6
        assert held == 11006725120 and cc.bytes_per_token == 1280 * 6
        # a step of 25 rows is expected to touch a little over half of the
        # 16 held experts
        assert 8.5 < shapes.experts_touched(doc, 25) < 9.0
        step = shapes.decode_step_bytes(doc, 25, 25 * 4096)
        assert 7.5e9 < step < 8.5e9
