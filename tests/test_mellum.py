"""Mellum 2 on the normal path against its plain reference.

The program (models/decoder.py, ops/attention.py, engine/engine.py) is held
to ``benchmark/reference/mellum.py`` — float32 ``jax.numpy``, no cache, no
batching, importing nothing of the program — on the seeded random weights
of the ``debug-mellum`` preset: two periods of three window layers (8
positions, plain rotary) and one full layer (YaRN, factor 4 over 32), 8
softmax-routed experts top-2 in every layer. Contexts run to several
windows, so a window layer's mask in a bucket, the chunk path's history and
the paged kernel's block skipping all lie past the window's edge; every
path is run on the XLA ops and on the Pallas kernels (interpreted), and the
window reaches every dispatcher as a Python int.
"""

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

from harness import shapes_mellum as shapes  # noqa: E402
from reference import mellum as ref  # noqa: E402

from llms_on_kubernetes_tpu.configs import (  # noqa: E402
    cut_to_layers, from_hf_config, get_config,
)
from llms_on_kubernetes_tpu.engine.cache import (  # noqa: E402
    CacheConfig, init_pages,
)
from llms_on_kubernetes_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.models import decoder as dec  # noqa: E402
from llms_on_kubernetes_tpu.ops import attention, rope  # noqa: E402

CFG = get_config("debug-mellum")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config_file(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


REF_CFG = config_file("debug-mellum")
PAGE, PPS, SLOTS = 8, 16, 4
WINDOW = CFG.sliding_window
# float32 program against the float32 reference: the two sum in different
# orders (a paged or blockwise softmax against a dense one, the experts'
# grouped product against a loop); the largest difference seen is 4e-5
F32_TOL = 2e-4
KINDS = ("sliding", "full")


def params_of(dtype):
    return dec.init_params(CFG, jax.random.key(0), dtype=dtype)


@pytest.fixture(scope="module")
def params32():
    return params_of("float32")


@pytest.fixture(params=["xla", "pallas"])
def impl(request, monkeypatch):
    """Every attention path on the XLA ops and on the Pallas kernels
    (interpreted here). The choice is made at trace time and traces are
    shared: cleared on the way in and out."""
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", request.param)
    jax.clear_caches()
    attention._chosen.clear()
    yield request.param
    jax.clear_caches()


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def ref_logits(params, tokens, positions=None):
    positions = range(len(tokens)) if positions is None else positions
    return np.asarray(ref.logits_at(REF_CFG, params, list(tokens),
                                    list(positions)))


class Cache:
    """Pools and page tables for SLOTS slots, and the jitted forward
    passes: what the engine's steps hand to models/decoder.py."""

    def __init__(self, params, cfg=CFG, dtype="float32"):
        self.params, self.cfg = params, cfg
        cc = CacheConfig(num_layers=cfg.num_attn_layers,
                         num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                         num_pages=SLOTS * PPS + 1, page_size=PAGE,
                         pages_per_slot=PPS, dtype=dtype)
        self.kp, self.vp = init_pages(cc)
        self.tables = 1 + np.arange(SLOTS * PPS, dtype=np.int32).reshape(
            SLOTS, PPS)
        self._prefill = jax.jit(dec.forward_prefill, static_argnums=(1,))
        self._chunk = jax.jit(dec.forward_chunk, static_argnums=(1,))
        self._decode = jax.jit(dec.forward_decode, static_argnums=(1,))

    def _keep(self, out):
        logits, self.kp, self.vp, _aux = out
        return np.asarray(logits)

    def prefill(self, rows, bucket, slots):
        toks = np.zeros((len(rows), bucket), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        return self._keep(self._prefill(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([len(r) for r in rows], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[slots]), aux=dec.LayerAux()))

    def chunk(self, tokens, history, bucket, slot):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(tokens)] = tokens
        return self._keep(self._chunk(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([history], jnp.int32),
            jnp.asarray([len(tokens)], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[[slot]]), aux=dec.LayerAux()))

    def decode(self, tokens, lengths):
        """One token for every slot; lengths 0 = an idle row."""
        return self._keep(self._decode(
            self.params, self.cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables), aux=dec.LayerAux()))


def said(op):
    """{kind: (impl, why)} of the dispatcher ``op``'s last choices."""
    return {k: attention._chosen[f"{op}_{k}"] for k in KINDS}


def took_the_kernels(op, impl):
    want = "pallas-interpret" if impl == "pallas" else "xla"
    return all(choice[0] == want for choice in said(op).values())


# ---------------------------------------------------------------------------
# the three forward passes against the reference's full forward pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(1, 16), (7, 16), (9, 16), (16, 16),
                                      (17, 32), (31, 32), (64, 64),
                                      (100, 128)])
def test_prefill_at_every_bucket_with_padding(params32, impl, n, bucket):
    """From inside the window (1, 7) to twelve windows (100)."""
    toks = prompt(n, seed=n)
    got = Cache(params32).prefill([toks], bucket, [1])
    np.testing.assert_allclose(got, ref_logits(params32, toks, [n - 1]),
                               atol=F32_TOL, rtol=0)
    assert took_the_kernels("prefill", impl)


def test_rows_of_unequal_length_in_one_bucket_equal_each_row_alone(
        params32, impl):
    rows = [prompt(5, 1), prompt(30, 2), [], prompt(19, 3)]
    got = Cache(params32).prefill(rows, 32, [3, 0, 0, 2])
    for i, r in enumerate(rows):
        if r:
            np.testing.assert_allclose(
                got[i], ref_logits(params32, r, [len(r) - 1])[0],
                atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("n", [33, 40, 63, 64, 70, 121])
def test_a_prompt_longer_than_the_largest_bucket_takes_chunks(params32, impl,
                                                              n):
    """Chunks of 32 over up to 96 cached positions: a window layer's
    queries read at most the last 8 of them, a full layer's all."""
    c = Cache(params32)
    toks = prompt(n, seed=n)
    got = None
    for at in range(0, n, 32):
        part = toks[at:at + 32]
        got = c.chunk(part, at, 16 if len(part) <= 16 else 32, 2)
    np.testing.assert_allclose(got, ref_logits(params32, toks, [n - 1]),
                               atol=F32_TOL, rtol=0)
    assert took_the_kernels("chunk", impl)


def test_every_decoded_position_matches_the_full_forward_pass(params32, impl):
    """Prefill, then 40 decode steps through the cache, each held to the
    reference's FULL forward pass of the whole sequence at its position;
    slot 1 starts inside the window (6 tokens) and leaves it, slot 3
    starts five windows in (43) and crosses pages; slots 0 and 2 idle."""
    c = Cache(params32)
    steps = 40
    seqs = {1: prompt(6 + steps, 11), 3: prompt(43 + steps, 13)}
    start = {1: 6, 3: 43}
    c.prefill([seqs[1][:6]], 16, [1])
    c.prefill([seqs[3][:43]], 64, [3])
    want = {s: ref_logits(params32, seqs[s]) for s in seqs}
    for step in range(steps):
        toks, lens = [0] * SLOTS, [0] * SLOTS
        for s in seqs:
            toks[s] = seqs[s][start[s] + step]
            lens[s] = start[s] + step + 1
        got = c.decode(toks, lens)
        for s in seqs:
            np.testing.assert_allclose(
                got[s], want[s][start[s] + step], atol=F32_TOL, rtol=0)
    assert took_the_kernels("decode", impl)


# ---------------------------------------------------------------------------
# the windows are static, and each kind says which kernel it took
# ---------------------------------------------------------------------------

def test_no_traced_window_reaches_a_dispatcher(params32, monkeypatch):
    """``_static_window`` is where a traced window would send a stack to
    the XLA ops: every window it is shown is a Python int or None, for
    prefill, chunk and decode of both kinds of layer."""
    seen = []
    real = attention._static_window

    def watched(w):
        assert w is None or type(w) is int, f"a traced window: {w!r}"
        seen.append(w)
        return real(w)

    monkeypatch.setattr(attention, "_static_window", watched)
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    jax.clear_caches()
    c = Cache(params32)
    toks = prompt(41, 5)
    c.prefill([toks[:20]], 32, [0])
    c.chunk(toks[20:40], 20, 32, 0)
    c.decode([toks[40], 0, 0, 0], [41, 0, 0, 0])
    jax.clear_caches()
    assert set(seen) == {WINDOW, None}
    # three dispatchers x eight layers (a run is traced once a layer here:
    # the rolled scan traces its body once, so once a run)
    assert seen.count(WINDOW) >= 3 and seen.count(None) >= 3


def test_the_records_name_a_kernel_for_both_kinds(params32, monkeypatch):
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    jax.clear_caches()
    attention._chosen.clear()
    c = Cache(params32)
    toks = prompt(41, 6)
    c.prefill([toks[:20]], 32, [0])
    c.chunk(toks[20:40], 20, 32, 0)
    c.decode([toks[40], 0, 0, 0], [41, 0, 0, 0])
    jax.clear_caches()
    for kind in KINDS:
        assert attention._chosen[f"prefill_{kind}"] == (
            "pallas-interpret", "flash kernel, bucket 32")
        impl, why = attention._chosen[f"chunk_{kind}"]
        assert impl == "pallas-interpret" and why.startswith(
            "flash chunk kernel: a slot's 128 gathered keys")   # one block
        assert ("inside a window of 8" in why) == (kind == "sliding")
        assert attention._chosen[f"decode_{kind}"] == (
            "pallas-interpret", "fused write+attend kernel")
    # a stack of one kind keeps the plain keys
    assert not {"prefill", "chunk", "decode"} & set(attention._chosen)


def test_each_kind_gets_its_window_scale_and_frequencies(params32,
                                                          monkeypatch):
    """Window layers: the window, the plain scale, the unscaled
    frequencies. Full layers: no window, YaRN's frequencies and its factor
    squared on the scale."""
    calls = []
    real = dec._attend

    def watched(cfg, inv_freq, *args):
        *_, scale, window = args
        calls.append((window, scale, np.asarray(inv_freq)))
        return real(cfg, inv_freq, *args)

    monkeypatch.setattr(dec, "_attend", watched)
    with jax.disable_jit():     # the frequencies as numbers, not tracers
        Cache(params32).prefill([prompt(9, 1)], 16, [0])
    plain = rope.rope_frequencies(16, 10000.0)
    yarn = rope.rope_frequencies(16, 10000.0, CFG.rope_scaling)
    assert not np.allclose(plain, yarn)
    by_window = {w: (s, f) for w, s, f in calls}
    assert set(by_window) == {WINDOW, None}
    s, f = by_window[WINDOW]
    assert s == 16 ** -0.5 and np.array_equal(f, plain)
    s, f = by_window[None]
    assert s == pytest.approx(16 ** -0.5 * 1.1386294361119891 ** 2)
    assert np.array_equal(f, yarn)


@pytest.mark.parametrize("window,keys", [
    (None, "a slot's 96"), (8, "32 of a slot's 96"),
    (24, "64 of a slot's 96")])
def test_the_chunk_kernel_against_the_xla_gather_path(monkeypatch, window,
                                                      keys):
    """``flash_chunk_attention`` (interpreted) through its dispatcher, as
    any model with a plain pool takes it: a row that starts its prompt, a
    row 40 positions in whose chunk is not full, an idle row, a row whose
    chunk ends with its slot; key blocks outside the causal range and the
    window are skipped, not masked, and inside a window only the pages
    from the first query's window edge on are gathered (those of row 1
    start in mid-slot, those of row 3 are the slot's last)."""
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    attention._chosen.clear()
    rng = np.random.default_rng(0)
    n_kv, n_q, d, page, pps, B, T = 2, 4, 16, 8, 12, 4, 16
    kp, vp = (jnp.asarray(rng.normal(size=(n_kv, 1 + B * pps, page, d)),
                          jnp.float32) for _ in "kv")
    table = jnp.asarray(1 + np.arange(B * pps).reshape(B, pps), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, T, n_q, d)), jnp.float32)
    history = jnp.asarray([0, 43, 70, 80], jnp.int32)
    lens = jnp.asarray([16, 11, 0, 16], jnp.int32)
    want = attention.chunk_attention(q, kp, vp, table, history, lens,
                                     scale=0.25, sliding_window=window)
    got = attention.dispatch_chunk_attention(
        q, kp, vp, table, history, lens, scale=0.25, sliding_window=window)
    impl, why = attention._chosen["chunk"]
    assert impl == "pallas-interpret" and f"{keys} gathered keys" in why
    for b, n in enumerate([16, 11, 0, 16]):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-6, rtol=0)
    assert np.all(np.asarray(got[2]) == 0)      # an idle row reads nothing
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("T,page,slot,window,pages", [
    (2048, 64, 144, None, 144),
    (2048, 64, 144, 1024, 56),      # 3,071 positions and a page: 7 x 512
    (512, 64, 144, 1024, 32),
    (2048, 64, 144, 8192, 144),     # no fewer than the slot has
    (32, 8, 16, 8, 16),             # a slot of one key block
])
def test_a_window_layer_gathers_its_windows_pages(T, page, slot, window,
                                                  pages):
    from llms_on_kubernetes_tpu.ops.pallas_flash import chunk_gather_pages

    got = chunk_gather_pages(T, page, slot, window)
    assert got == pages
    if window is not None and got < slot:
        # the worst start: the window's edge on a page's last row
        assert got * page >= window + T - 1 + page - 1


# ---------------------------------------------------------------------------
# YaRN as the published config states it
# ---------------------------------------------------------------------------

def catalogue_keys():
    with open(CATALOG) as f:
        row, = [json.loads(line) for line in f if '"Mellum2-12B' in line]
    return row["config"]


def test_yarn_frequencies_and_factor_at_the_published_numbers():
    pub = catalogue_keys()["rope_parameters"]["full_attention"]
    cfg = get_config("mellum2-12b")
    got = rope.rope_frequencies(128, cfg.rope_theta, cfg.rope_scaling)
    # the formula, written out: pairs that turn 32 times or more in 8,192
    # positions keep their frequency, those that turn once or less are
    # divided by 16, a linear blend between
    i = np.arange(64, dtype=np.float64)
    extrap = 500000.0 ** (-2 * i / 128)

    def pair(turns):
        return 128 * math.log(8192 / (turns * 2 * math.pi)) / (
            2 * math.log(500000.0))

    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == rope.yarn_correction_range(
        128, 500000.0, cfg.rope_scaling) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extrap / 16 * ramp + extrap * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:low + 1], extrap[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], extrap[high:] / 16, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_inv_freq(128, pub)), want, rtol=1e-6)
    assert rope.yarn_cos_sin_factor(cfg.rope_scaling) \
        == pub["attention_factor"] == 1.2772588722239782
    assert pub["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1)
    # without a published factor: the scheme's default, and DeepSeek's
    # two mscales, which cancel
    assert rope.yarn_cos_sin_factor(
        {"rope_type": "yarn", "factor": 16}) == pytest.approx(
            1.2772588722239782)
    assert rope.yarn_cos_sin_factor(
        get_config("deepseek-v3").rope_scaling) == 1.0
    assert rope.yarn_cos_sin_factor(None) == 1.0
    assert rope.yarn_cos_sin_factor({"rope_type": "linear", "factor": 8}) == 1


def test_a_factor_on_cosine_and_sine_is_its_square_on_the_scale(params32):
    """What the program serves against what the reference computes, on one
    full layer's scores: (f q) . (f k) = f^2 (q . k)."""
    q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 4, 16)),
                    jnp.float32)
    k = jnp.asarray(np.random.default_rng(1).normal(size=(1, 5, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    inv = jnp.asarray(rope.rope_frequencies(16, 10000.0, CFG.rope_scaling))
    f = rope.yarn_cos_sin_factor(CFG.rope_scaling)
    rq, rk = rope.apply_rope(q, k, pos, inv)
    np.testing.assert_allclose(
        np.einsum("bthd,bskd->bhkts", f * rq, f * rk),
        f * f * np.einsum("bthd,bskd->bhkts", rq, rk), rtol=1e-5,
        atol=1e-5)


# ---------------------------------------------------------------------------
# the registry, the published keys, the cut
# ---------------------------------------------------------------------------

def test_from_hf_config_on_the_catalogs_keys_gives_the_registry_entry():
    got = from_hf_config(catalogue_keys(), name="mellum2-12b")
    want = get_config("mellum2-12b")
    assert got == want and got.rope_scaling == want.rope_scaling
    assert get_config("JetBrains/Mellum2-12B-A2.5B-Instruct") is want
    assert want.layer_types == tuple(catalogue_keys()["layer_types"])
    assert want.num_window_layers == 21 and want.num_attn_layers == 28
    assert want.num_moe_layers == 28 and want.moe_router == "softmax"
    assert not want.use_expert_bias and not want.qk_norm
    assert (want.attn_window("swa"), want.attn_window("attn")) == (1024, None)
    # every other entry keeps the window it had, on every layer or none
    assert get_config("mistral-7b").attn_window("attn") == 4096
    assert get_config("mistral-7b").num_window_layers == 32
    assert get_config("lfm2-24b-a2b").attn_window("attn") is None
    assert get_config("jamba2-3b").num_window_layers == 0
    assert get_config("debug-gemma").num_window_layers == 2


@pytest.mark.parametrize("change,word", [
    ({"layer_types": ["conv"] * 28}, "only sliding_attention"),
    ({"mlp_layer_types": ["dense"] * 28}, "every network sparse"),
    ({"layer_types": ["full_attention"] * 27}, "num_hidden_layers 28"),
    ({"sliding_window": None}, "need sliding_window"),
    ({"rope_parameters": {"full_attention": {"rope_type": "longrope",
                                             "rope_theta": 5e5},
                          "sliding_attention": {"rope_type": "default",
                                                "rope_theta": 5e5}}},
     "plain or yarn"),
])
def test_from_hf_config_refuses_what_it_would_serve_wrongly(change, word):
    with pytest.raises(NotImplementedError, match=word):
        from_hf_config(dict(catalogue_keys(), **change))


@pytest.mark.parametrize("name,runs", [
    ("mellum2-12b@0-11", [("swa", 3), ("attn", 1)] * 3),
    ("mellum2-12b@0-3", [("swa", 3), ("attn", 1)]),
    ("mellum2-12b@4-11", [("swa", 3), ("attn", 1)] * 2),
    ("mellum2-12b@3-7", [("attn", 1), ("swa", 3), ("attn", 1)]),
    ("mellum2-12b@2,3", [("swa", 1), ("attn", 1)]),
    ("debug-mellum@0-3", [("swa", 3), ("attn", 1)]),
])
def test_a_cut_keeps_the_pattern(name, runs):
    cfg = get_config(name)
    assert [(op, n) for op, _ff, _first, n in cfg.layer_runs] == runs
    assert all(ff == "moe" for _op, ff, _first, _n in cfg.layer_runs)
    assert cfg.num_layers == sum(n for _op, n in runs)
    assert cfg.num_window_layers == sum(n for op, n in runs if op == "swa")
    base = get_config(name.partition("@")[0])
    for key in ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "vocab_size", "sliding_window", "rope_scaling"):
        assert getattr(cfg, key) == getattr(base, key), key


def test_the_one_chip_cut_is_three_whole_periods():
    doc = config_file("mellum2-12b")
    cfg = get_config(doc["registry_name"])
    assert cfg.layer_types == tuple(doc["layer_types"]) \
        == ("sliding_attention",) * 3 + ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",) \
        + ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.num_layers == doc["num_hidden_layers"] == 12
    assert doc["mlp_layer_types"] == ["sparse"] * 12
    assert cut_to_layers(get_config("mellum2-12b"), tuple(range(12)),
                         "x") .layer_runs == cfg.layer_runs
    pub = catalogue_keys()
    for key, value in pub.items():
        if key not in doc["reduced"]:
            assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]


@pytest.mark.parametrize("name", ["debug-mellum", "mellum2-12b"])
def test_expected_bytes_are_the_seeded_trees_and_the_shape_counts(name):
    doc = config_file(name)
    cfg = get_config(doc["registry_name"])
    tree = jax.eval_shape(lambda: dec.init_params(
        cfg, jax.random.key(0), dtype="bfloat16"))
    leaves = jax.tree.leaves(tree)
    want = doc["expected_bytes"]
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == shapes.weight_bytes(doc) == want["weights"]
    assert shapes.pool_bytes(doc) == want["pool"]
    assert len(tree["layers"]) == len(cfg.layer_runs)
    assert all("router_bias" not in run and "q_norm" not in run
               for run in tree["layers"])
    if name == "mellum2-12b":
        assert shapes.attention_params(doc) == 21_233_664
        assert shapes.expert_params(doc) == 6_193_152
        assert sum(a.size for a in leaves) == 12 * 417_747_456 \
            + 452_984_832 + 2304
        assert want["weights"] == 10_931_913_216
        assert shapes.kv_bytes_per_token(doc) == 24_576
        assert shapes.layer_counts(doc) == (9, 3)


def test_a_token_steps_bytes_count_a_window_layers_keys_inside_the_window():
    doc = config_file("mellum2-12b")
    row = shapes.kv_bytes_per_layer_token(doc)
    assert row == 2048
    # one row at 500, inside the window: every layer reads 500 positions
    assert shapes.keys_read(doc, 500) == 12 * 500
    # at 3,000: nine layers read 1,024, three read 3,000
    assert shapes.keys_read(doc, 3000) == 9 * 1024 + 3 * 3000
    more = shapes.decode_step_bytes(doc, 30, 30 * 3000) \
        - shapes.decode_step_bytes(doc, 30, 30 * 2000)
    assert more == 30 * 3 * 1000 * row
    flops = shapes.decode_step_flops(doc, 30, 30 * 3000) \
        - shapes.decode_step_flops(doc, 30, 30 * 2000)
    assert flops == 2.0 * 32 * 128 * 2 * 30 * 3 * 1000
    # thirty rows that chose independently would touch 63 of the 64
    # experts: the even-routing expectation, which no file of the
    # benchmark may bend towards what a run read (no fitted constant)
    assert 62.5 < shapes.experts_touched(doc, 30) < 64
    for name in ("mellum2-12b", "debug-mellum"):
        assert "seeded_routing" not in config_file(name)
    # a reader that has the program's own count of the experts a step
    # read hands it over: each expert fewer is its three matrices fewer
    # in each of the twelve layers, and nothing else moves
    fewer = shapes.decode_step_bytes(
        doc, 30, 90000, experts_read_share=40 / 64) \
        - shapes.decode_step_bytes(doc, 30, 90000, experts_read_share=41 / 64)
    assert fewer == -12 * 3 * 2304 * 896 * 2
    assert shapes.decode_step_bytes(doc, 30, 90000) == pytest.approx(
        shapes.decode_step_bytes(
            doc, 30, 90000,
            experts_read_share=shapes.experts_touched(doc, 30) / 64))
    # a prompt of 4,096: the window layers' pairs stop growing with n^2
    n, w = 4096.0, 1024.0
    assert shapes.attended_pairs(n, w) == w * (w + 1) / 2 + (n - w) * w
    assert shapes.prefill_flops(doc, 4096) < shapes.prefill_flops(
        dict(doc, sliding_window=1 << 30), 4096)
    # a chunk of 2,048 behind 4,096: a window layer's queries see 3,071
    # cached positions, a full layer's 6,144
    assert shapes.chunk_attention_bytes(doc, 4096, 2048, True) \
        == 2 * 2048 * 4096 * 2 + 3071 * row
    assert shapes.chunk_attention_bytes(doc, 4096, 2048, False) \
        == 2 * 2048 * 4096 * 2 + 6144 * row
    assert shapes.chunk_attention_flops(doc, 4096, 2048, True) \
        == 2.0 * 4096 * 2 * 2048 * 1024
    assert shapes.chunk_attention_flops(doc, 0, 2048, False) \
        == 2.0 * 4096 * 2 * (2048 * 2049 / 2)


# ---------------------------------------------------------------------------
# the engine: fused K = 4 windows, the chunk path, the prefix cache, the
# window layers' counters. A request's every token is held to the
# reference's full forward pass of prompt + output
# ---------------------------------------------------------------------------

def engine(params, **kw):
    base = dict(model="debug-mellum", dtype="float32", max_decode_slots=SLOTS,
                page_size=PAGE, num_pages=SLOTS * PPS + 1, pages_per_slot=PPS,
                prefill_buckets=(16, 32), async_scheduling=True,
                decode_steps=4)
    base.update(kw)
    return Engine(EngineConfig(**base), params=params)


def submit(eng, toks, n_out, **kw):
    return eng.submit(list(toks), SamplingParams(
        max_tokens=n_out, temperature=0.0, logprobs=True, **kw))


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


@pytest.mark.parametrize("scheduler", ["pipelined", "synchronous"])
def test_fused_windows_a_chunked_prompt_and_the_window_rows(params32, impl,
                                                            scheduler):
    eng = engine(params32, async_scheduling=scheduler == "pipelined")
    reqs = [submit(eng, prompt(5, 21), 14),       # leaves the window
            submit(eng, prompt(70, 22), 13)]      # chunks, nine windows in
    run(eng, reqs)
    for r in reqs:
        assert len(r.output) in (13, 14)
        held_to_reference(params32, r)
    assert eng.path_tokens == {"prefill": 5, "chunk": 70}
    for op in ("prefill", "chunk", "decode"):
        assert took_the_kernels(op, impl), (op, said(op))
    # six window layers: every planned token step of a live row counts the
    # rows it holds and the rows a window layer can still read
    cached, reached = (eng.window_rows[k] for k in ("cached", "reached"))
    assert cached > reached > 0 and cached % 6 == 0 and reached % 6 == 0
    # the short request alone: lengths 6..19 over its 14 steps, planned a
    # window of 4 at a time (a row that stops inside a window was planned
    # to its end)
    assert reached <= 6 * WINDOW * (eng.decode_tokens + 2 * 4)
    assert cached >= 6 * sum(range(6, 19)) + 6 * sum(range(71, 83))


def test_a_long_slots_window_layers_gather_from_mid_slot(params32,
                                                        monkeypatch):
    """A slot of two key blocks (1,024 positions): the six window layers
    of a chunk gather 512 positions from the page that holds the first
    query's window edge, the two full layers the whole slot; a prompt of
    300 tokens, ten chunks, its last ones with that page in mid-slot, and
    every decoded position held to the reference."""
    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    jax.clear_caches()
    attention._chosen.clear()
    eng = engine(params32, pages_per_slot=128, num_pages=2 * 128 + 1,
                 max_decode_slots=2)
    r = submit(eng, prompt(300, 41), 5)
    run(eng, [r])
    jax.clear_caches()
    held_to_reference(params32, r)
    assert eng.path_tokens == {"prefill": 0, "chunk": 300}
    why = {k: v[1] for k, v in said("chunk").items()}
    assert "512 of a slot's 1024 gathered keys" in why["sliding"]
    assert "a slot's 1024 gathered keys" in why["full"]


@pytest.mark.parametrize("first,steps", [
    (1, 4),            # all under the window
    (6, 4),            # crosses it: 6, 7 under, 8, 9 at it
    (8, 4), (300, 4),  # at and past it
    (7, 1), (5, 0),
])
def test_a_planned_windows_rows_are_the_sum_over_its_steps(params32, first,
                                                           steps):
    eng = engine(params32)
    eng._count_window_rows({0: first, 3: 2}, {0: steps, 3: 3})
    lengths = list(range(first, first + steps)) + [2, 3, 4]
    assert eng.window_rows == {
        "cached": 6 * sum(lengths),
        "reached": 6 * sum(min(n, WINDOW) for n in lengths)}


def test_the_prefix_cache_adopts_pages_of_both_kinds(params32):
    """The pool keeps every token of every layer, so a cached page is
    valid for a window layer and a full layer alike: five full pages of a
    49-token prompt are adopted and the rest takes the chunk path."""
    eng = engine(params32, prefix_caching=True)
    shared = prompt(40, 31)
    first = submit(eng, shared + prompt(9, 32), 6)
    run(eng, [first])
    again = submit(eng, shared + prompt(11, 33), 6)
    run(eng, [again])
    assert eng.allocator.hit_tokens_total == 40
    assert eng.path_tokens == {"prefill": 0, "chunk": 49 + 11}
    for r in (first, again):
        held_to_reference(params32, r)


def test_the_window_rows_on_the_metrics_page(params32):
    from llms_on_kubernetes_tpu.server import metrics

    m = metrics.engine_metrics(metrics.Registry())
    assert m["attn_window_rows"].name == "llm_attn_window_rows_total"
    # a model without window layers counts nothing
    plain = Engine(EngineConfig(model="debug-tiny", dtype="float32",
                                prefill_buckets=(32,)))
    r = submit(plain, prompt(5, 1), 6)
    run(plain, [r])
    assert plain.window_rows == {"cached": 0, "reached": 0}
    spec = json.load(open(os.path.join(
        REPO, "benchmark", "layer_metrics", "attn_window_reach_share.json")))
    assert spec["reader"] == "counter_ratio"
    assert spec["args"]["num"] == {"metric": "llm_attn_window_rows_total",
                                   "labels": {"rows": "reached"}}
    assert spec["args"]["den"] == {"metric": "llm_attn_window_rows_total",
                                   "labels": {"rows": "cached"}}


@pytest.mark.parametrize("kw,word", [
    (dict(quantization="int8"), "--quantization"),
    (dict(speculation="ngram"), "speculation"),
    (dict(kv_host_cache_gb=0.1), "host KV tier"),
])
def test_what_a_stack_of_runs_cannot_do_refuses_at_start_up(kw, word):
    with pytest.raises(ValueError, match=word):
        Engine(EngineConfig(model="debug-mellum", dtype="float32", **kw))


# ---------------------------------------------------------------------------
# the types: bfloat16 as served stays under the golden file's tolerance, the
# reference over matrices cut to bfloat16's nearest type below does not
# ---------------------------------------------------------------------------

GOLDEN = json.load(open(os.path.join(REPO, "benchmark", "golden",
                                     "debug-mellum.json")))
TOL = GOLDEN["tolerance"]["nats"]
DECODED = 8
N_PROMPTS = 12


def _fp8(w):
    return w.astype(jnp.float8_e4m3fn).astype(w.dtype)


def matrices_cut(params):
    """Every layer matrix cut to float8_e4m3fn, the nearest type below the
    served one, and widened again (``reference/lower_precision.py``'s
    control)."""
    return dict(params, layers=tuple(
        {k: _fp8(w) if w.ndim >= 3 else w for k, w in run.items()}
        for run in params["layers"]))


def top8_diffs(params, reference_params):
    """|program - reference| at its largest over the reference's 8 best
    ids, [prompts, positions]: the program in bfloat16 (weights,
    activations, KV) after a prefill of 40-47 tokens (five windows) and
    after each of 8 teacher-forced decode steps; the float32 reference on
    ``reference_params``."""
    out = []
    for seed in range(N_PROMPTS):
        toks = prompt(40 + seed % 8 + DECODED, 100 + seed)
        n = len(toks) - DECODED
        c = Cache(params, dtype="bfloat16")
        got = [c.prefill([toks[:n]], 64, [0])[0]]
        for j in range(DECODED):
            got.append(c.decode([toks[n + j], 0, 0, 0],
                                [n + j + 1, 0, 0, 0])[0])
        want = ref_logits(reference_params, toks, range(n - 1, len(toks)))
        row = []
        for g, w in zip(got, want):
            lw = np.asarray(jax.nn.log_softmax(jnp.asarray(w)))
            lg = np.asarray(jax.nn.log_softmax(jnp.asarray(
                np.asarray(g, np.float32))))
            ids = np.argsort(-lw)[:8]
            row.append(float(np.abs(lg[ids] - lw[ids]).max()))
        out.append(row)
    return np.asarray(out)


@pytest.fixture(scope="module")
def params16():
    return params_of("bfloat16")


def test_bfloat16_as_served_reads_under_the_tolerance_and_fp8_over_it(
        params16):
    """108 readings (12 prompts x 9 positions) either way. Top-2 of 8
    routing decides what "the same" can mean: where the second and the
    third best score of a token lie within bfloat16's rounding of the
    residual stream (some of the 400 routings behind every reading do),
    the token goes to the other expert and a reading jumps to 0.2-1.3
    nats: a flip, not a fault, and the reason the rule is over quantiles.
    As served: half the readings under 0.03 and three quarters under 0.05.
    Matrices cut to float8: the smallest reads 0.115, nine in ten over
    0.15. The golden file's tolerance lies between the two."""
    served = top8_diffs(params16, params16)
    assert np.quantile(served, 0.5) < TOL / 3, np.quantile(served, 0.5)
    assert np.quantile(served, 0.75) < TOL / 2, np.quantile(served, 0.75)
    lower = top8_diffs(matrices_cut(params16), params16)
    assert np.quantile(lower, 0.1) > TOL, np.quantile(lower, 0.1)
    assert np.median(lower) > 2 * TOL


def test_the_reference_in_a_lower_precision_comes_out_as_not_correct(
        params16):
    """``lower_precision.py``'s comparison, here on the CPU: the reference
    itself over the cut matrices, at the golden prompts' first generated
    position, over the ids the check asks for. The control fails the
    tolerance (on the two longer prompts: one limit is enough), where the
    served types read under a third of it on every prompt."""
    from reference.make_golden import chat_token_ids

    cut, worst = matrices_cut(params16), {}
    for p in GOLDEN["prompts"]:
        ids = chat_token_ids(p["content"])
        lg = ref_logits(cut, ids, [len(ids) - 1])[0]
        lp = np.asarray(jax.nn.log_softmax(jnp.asarray(lg)), np.float64)
        worst[p["name"]] = max(abs(float(lp[i]) - want) for i, want in zip(
            p["top_ids"][0][:8], p["top_logprobs"][0][:8]))
    assert max(worst.values()) > 3 * TOL, worst
    assert sum(v > TOL for v in worst.values()) >= 2, worst
