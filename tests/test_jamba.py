"""Jamba on the normal path against its plain reference.

The program (models/decoder.py, engine/engine.py) is held to
``benchmark/reference/jamba.py`` — float32 ``jax.numpy``, no cache, no state
carried between calls, no batching, importing nothing of the program — on
the seeded random weights of the ``debug-jamba`` preset: Mamba, Mamba,
position-free multi-query attention, Mamba. In float32 the two agree to 1e-4
on logits on every path a request can take: a padded bucket from an empty
state, a chunk that continues its slot's state, one token a slot inside the
fused K-step decode window. The seeded weights make the state-space state
decay over tens to thousands of tokens (A = -(1..16), steps log-uniform in
[1e-3, 1e-1]), so a state carried wrongly, started from its slot's last
tenant or moved by a padded position is off by far more than the tolerance;
a state KEPT in bfloat16 reads over the bfloat16 tolerance that the served
types stay under (``test_a_bfloat16_state_fails_the_tolerance``).
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import shapes_jamba as shapes  # noqa: E402
from reference import jamba as ref  # noqa: E402

from llms_on_kubernetes_tpu.configs import (  # noqa: E402
    from_hf_config, get_config,
)
from llms_on_kubernetes_tpu.engine.cache import (  # noqa: E402
    CacheConfig, init_pages,
)
from llms_on_kubernetes_tpu.engine.engine import (  # noqa: E402
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.models import decoder as dec  # noqa: E402

CFG = get_config("debug-jamba")


def config_file(name):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


REF_CFG = config_file("debug-jamba")
PAGE, PPS, SLOTS = 8, 8, 4
# float32 program against the float32 reference: the two sum in different
# orders (a block of unrolled steps against single steps, a paged softmax
# against a dense one); the largest difference seen on these cases is 3e-5
F32_TOL = 1e-4


def params_of(dtype):
    return dec.init_params(CFG, jax.random.key(0), dtype=dtype)


@pytest.fixture(scope="module")
def params32():
    return params_of("float32")


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def ref_logits(params, tokens, positions=None, **kw):
    positions = range(len(tokens)) if positions is None else positions
    return np.asarray(ref.logits_at(REF_CFG, params, list(tokens),
                                    list(positions), **kw))


class Cache:
    """Pools, per-slot state and page tables for SLOTS slots, and the
    jitted forward passes: what the engine's steps hand to
    models/decoder.py."""

    def __init__(self, params, cfg=CFG, dtype="float32", state_dtype=None):
        self.params, self.cfg = params, cfg
        cc = CacheConfig(num_layers=cfg.num_attn_layers,
                         num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                         num_pages=SLOTS * PPS + 1, page_size=PAGE,
                         pages_per_slot=PPS, dtype=dtype)
        self.kp, self.vp = init_pages(cc)
        self.state = dec.init_conv_state(cfg, SLOTS, dtype)
        if state_dtype is not None:     # the lower-precision control
            self.state = dataclasses.replace(
                self.state, ssm=self.state.ssm.astype(state_dtype))
        self.tables = 1 + np.arange(SLOTS * PPS, dtype=np.int32).reshape(
            SLOTS, PPS)
        self._prefill = jax.jit(dec.forward_prefill, static_argnums=(1,))
        self._chunk = jax.jit(dec.forward_chunk, static_argnums=(1,))
        self._decode = jax.jit(dec.forward_decode, static_argnums=(1,))

    def poison(self):
        """A stale state in every slot, as a last tenant would leave it."""
        self.state = jax.tree.map(lambda a: a + 7.0, self.state)

    def _keep(self, out):
        logits, self.kp, self.vp, aux = out
        self.state = aux.conv
        return np.asarray(logits)

    def prefill(self, rows, bucket, slots):
        """rows: token lists (an empty one is a padding row)."""
        toks = np.zeros((len(rows), bucket), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        return self._keep(self._prefill(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([len(r) for r in rows], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[slots]),
            aux=dec.LayerAux(conv=self.state,
                             slots=jnp.asarray(slots, jnp.int32))))

    def chunk(self, tokens, history, bucket, slot):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(tokens)] = tokens
        return self._keep(self._chunk(
            self.params, self.cfg, jnp.asarray(toks),
            jnp.asarray([history], jnp.int32),
            jnp.asarray([len(tokens)], jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables[[slot]]),
            aux=dec.LayerAux(conv=self.state,
                             slots=jnp.asarray([slot], jnp.int32))))

    def decode(self, tokens, lengths):
        """One token for every slot; lengths 0 = an idle row."""
        return self._keep(self._decode(
            self.params, self.cfg, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32), self.kp, self.vp,
            jnp.asarray(self.tables), aux=dec.LayerAux(conv=self.state)))


def state_of(cache, slot):
    return [np.asarray(a)[:, slot] for a in jax.tree.leaves(cache.state)]


# ---------------------------------------------------------------------------
# the three forward passes against the reference's full forward pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,bucket", [(1, 16), (2, 16), (3, 16), (16, 16),
                                      (17, 32), (31, 32), (32, 32)])
def test_prefill_at_every_bucket_with_padding(params32, n, bucket):
    c = Cache(params32)
    c.poison()          # a prefill starts from zeros, never from its slot
    toks = prompt(n, seed=n)
    got = c.prefill([toks], bucket, [1])
    np.testing.assert_allclose(got, ref_logits(params32, toks, [n - 1]),
                               atol=F32_TOL, rtol=0)


def test_rows_of_unequal_length_in_one_bucket_equal_each_row_alone(params32):
    c = Cache(params32)
    rows = [prompt(5, 1), prompt(30, 2), [], prompt(1, 3)]
    got = c.prefill(rows, 32, [3, 0, 0, 2])
    for i, (r, slot) in enumerate(zip(rows, [3, 0, 0, 2])):
        if not r:
            continue
        np.testing.assert_allclose(
            got[i], ref_logits(params32, r, [len(r) - 1])[0],
            atol=F32_TOL, rtol=0)
        # the state the row left: what the row alone leaves, whatever the
        # padding to its right was given to compute
        one = Cache(params32)
        one.prefill([r], 32, [slot])
        for a, b in zip(state_of(c, slot), state_of(one, slot)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    # the padding row (slot column 0, like the engine's zeros) wrote the
    # trash row and not slot 0; slot 1 was never anybody's
    assert all(np.all(a == 0) for a in state_of(c, 1))


@pytest.mark.parametrize("n", [33, 40, 63, 64])
def test_a_prompt_longer_than_the_largest_bucket_carries_state_across_chunks(
        params32, n):
    c = Cache(params32)
    toks = prompt(n, seed=n)
    c.poison()
    got = None
    for at in range(0, n, 32):
        part = toks[at:at + 32]
        got = c.chunk(part, at, 16 if len(part) <= 16 else 32, 2)
    np.testing.assert_allclose(got, ref_logits(params32, toks, [n - 1]),
                               atol=F32_TOL, rtol=0)


def test_teacher_forced_decode_matches_the_full_forward_pass_everywhere(
        params32):
    """Prefill, then 13 decode steps through the cache and the state, each
    held to the reference's FULL forward pass of the whole sequence at its
    position; slots 1 and 3 decode, slots 0 and 2 are idle rows whose
    (poisoned) state stays as it was, bit for bit."""
    c = Cache(params32)
    c.poison()
    seqs = {1: prompt(6, 11) + prompt(13, 12),
            3: prompt(19, 13) + prompt(13, 14)}
    start = {1: 6, 3: 19}
    c.prefill([seqs[1][:6]], 16, [1])
    c.prefill([seqs[3][:19]], 32, [3])
    want = {s: ref_logits(params32, seqs[s]) for s in seqs}
    idle = [a.copy() for s in (0, 2) for a in state_of(c, s)]
    for step in range(13):
        toks, lens = [0] * SLOTS, [0] * SLOTS
        for s in seqs:
            toks[s] = seqs[s][start[s] + step]
            lens[s] = start[s] + step + 1
        got = c.decode(toks, lens)
        for s in seqs:
            np.testing.assert_allclose(
                got[s], want[s][start[s] + step], atol=F32_TOL, rtol=0)
    for a, b in zip(idle, [a for s in (0, 2) for a in state_of(c, s)]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n_valid", [0, 1, 3, 5, 8, 11])
def test_the_mixer_against_an_explicit_loop(n_valid):
    """``_mamba`` on a bucket of 11 with ``n_valid`` real positions, from a
    state that is not empty: outputs at the real positions and the state
    after the last real one against a loop over single tokens in numpy
    float64 (the published equations, step by step)."""
    cfg = CFG
    Di, N, R, taps = (cfg.mamba_d_inner, cfg.mamba_d_state,
                      cfg.mamba_dt_rank, cfg.mamba_d_conv)
    lp = jax.tree.map(lambda a: a[1], params_of("float32")["layers"][0])
    rng = np.random.default_rng(n_valid)
    u = rng.standard_normal((1, 11, cfg.hidden_size)).astype(np.float32)
    window = rng.standard_normal((1, (taps - 1) * Di)).astype(np.float32)
    h0 = rng.standard_normal((1, N, Di)).astype(np.float32)
    out, after = dec._mamba(
        lp, cfg, jnp.asarray(u),
        dec.MambaState(conv=jnp.asarray(window), ssm=jnp.asarray(h0)),
        jnp.asarray([n_valid], jnp.int32))
    w1, h1 = after.conv, after.ssm

    p = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    norm = lambda v, w: v / np.sqrt(np.mean(v * v) + cfg.rms_norm_eps) * w
    silu = lambda v: v / (1 + np.exp(-v))
    past = list(window[0].astype(np.float64).reshape(taps - 1, Di))
    h = h0[0].astype(np.float64)
    for t in range(n_valid):
        x, z = np.split(u[0, t].astype(np.float64) @ p["in_proj"], 2)
        past.append(x)
        x = silu(p["conv_b"] + sum(p["conv_w"][j] * past[-taps + j]
                                   for j in range(taps)))
        dt, b, c = np.split(x @ p["x_proj"], [R, R + N])
        dt, b, c = (norm(dt, p["dt_norm"]), norm(b, p["b_norm"]),
                    norm(c, p["c_norm"]))
        delta = np.log1p(np.exp(dt @ p["dt_proj"] + p["dt_bias"]))
        h = (np.exp(delta[None] * -np.exp(p["A_log"])) * h
             + (delta * x)[None] * b[:, None])
        y = (c @ h + p["D"] * x) * silu(z)
        np.testing.assert_allclose(out[0, t], y @ p["out_proj"], atol=2e-5,
                                   rtol=0)
    np.testing.assert_allclose(h1[0], h, atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        np.asarray(w1[0]).reshape(taps - 1, Di), np.stack(past[-(taps - 1):]),
        atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------
# the token step's state-space kernel (ops/pallas_ssm.py, interpreted) against
# ``_ssm_scan``'s one-step branch, and what ``dispatch_ssm_step`` chooses
# ---------------------------------------------------------------------------

def step_case(layers=3, slots=6, N=16, Di=256, dtype=jnp.float32, seed=0):
    """A whole state array and one step's operands, as ``_mamba`` hands
    them to ``dispatch_ssm_step``."""
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        ssm=jax.random.normal(ks[0], (layers, slots + 1, N, Di)).astype(dtype),
        delta=jax.nn.softplus(jax.random.normal(ks[1], (slots, 1, Di)) - 2.0),
        x=jax.random.normal(ks[2], (slots, 1, Di)),
        Bm=jax.random.normal(ks[3], (slots, 1, N)),
        Cm=jax.random.normal(ks[4], (slots, 1, N)),
        A=-jnp.exp(jax.random.normal(ks[5], (N, Di))))


def dispatched(c, layer, live):
    from llms_on_kubernetes_tpu.ops import attention

    live = jnp.asarray(live, bool)
    # a function of its own a call: the choice is made when it is traced
    return jax.jit(lambda *a: attention.dispatch_ssm_step(*a))(
        c["delta"], c["A"], c["x"], c["Bm"], c["Cm"], c["ssm"],
        jnp.int32(layer), live, attention.live_first(live))


LIVE = {"none": "000000", "one, first": "100000", "one, last": "000001",
        "some, first": "111000", "some, last": "000111",
        "scattered": "010110", "all": "111111"}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("rows", list(LIVE))
def test_the_step_kernel_updates_the_live_slots_and_no_other(
        rows, layer, monkeypatch):
    from llms_on_kubernetes_tpu.ops import attention

    monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    live = np.array([ch == "1" for ch in LIVE[rows]])
    c = step_case()
    before = np.asarray(c["ssm"])
    y, after = dispatched(c, layer, live)
    assert attention._chosen["ssm_step"][0] == "pallas-interpret"
    y_want, h_want = dec._ssm_scan(c["delta"], c["A"], c["x"], c["Bm"],
                                   c["Cm"], c["ssm"][layer, :len(live)])
    y, after = np.asarray(y), np.asarray(after)
    assert after.dtype == np.float32 and y.dtype == np.float32
    # float32 rounding: XLA may fuse a product and a sum the kernel keeps
    # apart (1 ulp of h seen)
    np.testing.assert_allclose(after[layer, :len(live)][live],
                               np.asarray(h_want)[live], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(y[live], np.asarray(y_want)[live],
                               rtol=1e-5, atol=1e-5)
    assert not y[~live].any()
    # every idle row, every other layer and the trash row: bit for bit
    same = np.ones(before.shape[:2], bool)
    same[layer, :len(live)] = ~live
    assert same[:, -1].all() and same.sum() == same.size - live.sum()
    np.testing.assert_array_equal(after[same], before[same])


@pytest.mark.parametrize("why,word", [
    ("a mesh", "a mesh of 2 devices"),
    ("channels off the lanes", "192 channels are not a multiple of 512"),
    ("channels off the chunks", "384 channels are not a multiple of 512"),
    ("a bfloat16 state", "kept in bfloat16"),
    ("the cpu", "cpu backend"),
])
def test_where_the_kernel_cannot_run_the_xla_step_does_and_says_why(
        why, word, monkeypatch):
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel import mesh as pmesh

    kw = {}
    if why == "a mesh":
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
        monkeypatch.setattr(pmesh, "_ACTIVE_MESH", jax.sharding.Mesh(
            np.array(jax.devices()[:2]), (pmesh.AXIS_MODEL,)))
    elif why.startswith("channels off"):
        monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
        kw = {"Di": int(word.split()[0])}
    elif why == "a bfloat16 state":
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
        kw = {"dtype": jnp.bfloat16}
    c = step_case(**kw)
    live = np.array([True, False, True, True, False, False])
    y, after = dispatched(c, 1, live)
    impl, said = attention._chosen["ssm_step"]
    assert impl == "xla" and word in said, said
    y_want, h_want = dec._ssm_scan(
        c["delta"], c["A"], c["x"], c["Bm"], c["Cm"],
        c["ssm"][1, :6].astype(jnp.float32))
    assert after.dtype == c["ssm"].dtype
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_want)[live],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[~live].any()       # as the kernel leaves them
    before = np.array(c["ssm"].astype(jnp.float32))
    after = np.asarray(after.astype(jnp.float32))
    tol = 1e-2 if why == "a bfloat16 state" else 2e-6
    np.testing.assert_allclose(after[1, :6][live], np.asarray(h_want)[live],
                               rtol=tol, atol=tol)
    before[1, :6][live] = after[1, :6][live]
    np.testing.assert_array_equal(after, before)       # the idle rows


# ---------------------------------------------------------------------------
# the token step's convolution in one pass (``dispatch_conv_step``: the XLA
# form, and ops/pallas_conv.py interpreted) against the general T-position
# code at T = 1 (``_mamba_conv``), bit for bit
# ---------------------------------------------------------------------------

CONV_ROWS = 32      # two of the kernel's tiles of 16 rows
# the rows that decode at each step of a fused window of K = 4 ("1" live)
CONV_STEPS = {
    "all rows live": ["1" * 32] * 4,
    "some idle, a tile with no live row": ["1011001110100001" + "0" * 16] * 4,
    "no row live": ["0" * 32] * 2,
    "a row that stops inside the window": [
        "1" * 8 + "0" * 24, "1" * 8 + "0" * 24,
        "1" * 5 + "0" * 27, "1" * 5 + "0" * 27],
    "a slot freed and taken again": [
        "1" * 20 + "0" * 12, "1" * 3 + "0" + "1" * 16 + "0" * 12,
        "refill 3", "1" * 20 + "0" * 12],
}


def wide(a):
    return np.asarray(a.astype(jnp.float32))


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CONV_STEPS))
def test_the_one_pass_token_step_equals_the_general_code_at_one_position(
        case, dtype, form, monkeypatch):
    """``xc`` of every live row and the whole window array (the idle rows,
    the other layers and the trash row too) after every step of a window,
    in the type served and in float32. A refill writes a slot's window as
    a prompt's last chunk would, between two steps."""
    from llms_on_kubernetes_tpu.ops import attention

    if form == "kernel":
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    layers, layer, taps, Di, B = 3, 1, 4, 256, CONV_ROWS
    ks = jax.random.split(jax.random.key(len(case)), 4)
    conv = jax.random.normal(ks[0], (layers, B + 1, (taps - 1) * Di)
                             ).astype(dtype)
    w = jax.random.normal(ks[1], (taps, Di)).astype(dtype).astype(jnp.float32)
    b = jax.random.normal(ks[2], (Di,)).astype(dtype).astype(jnp.float32)

    @jax.jit
    def one_pass(xz, conv, live):
        xc, xs, conv = attention.dispatch_conv_step(
            xz, w, b, conv, jnp.int32(layer), live,
            attention.live_tiles_first(live))
        return xc, xs, conv

    @jax.jit
    def general(xz, conv, live):
        xc, rows = dec._mamba_conv(conv[layer, :B], xz[:, None, :Di], w, b,
                                   live.astype(jnp.int32))
        return xc[:, 0], conv.at[layer, :B].set(rows)

    want = conv
    for i, rows in enumerate(CONV_STEPS[case]):
        if rows.startswith("refill"):
            slot = int(rows.split()[1])
            fresh = jax.random.normal(jax.random.fold_in(ks[3], 99),
                                      conv.shape[2:]).astype(dtype)
            conv, want = (a.at[layer, slot].set(fresh) for a in (conv, want))
            continue
        live = np.array([ch == "1" for ch in rows])
        xz = jax.random.normal(jax.random.fold_in(ks[3], i),
                               (B, 2 * Di)).astype(dtype)
        xc, xs, conv = one_pass(xz, conv, jnp.asarray(live))
        xc_want, want = general(xz, want, jnp.asarray(live))
        assert attention._chosen["conv_step"][0] == (
            "pallas-interpret" if form == "kernel" else "xla")
        assert xc.dtype == conv.dtype == jnp.dtype(dtype)
        assert xs.dtype == jnp.float32
        np.testing.assert_array_equal(wide(xc)[live], wide(xc_want)[live])
        np.testing.assert_array_equal(np.asarray(xs)[live], wide(xc)[live])
        np.testing.assert_array_equal(wide(conv), wide(want))
        if form == "kernel":    # rows the kernel never wrote are masked
            assert not wide(xc)[~live].any()


@pytest.mark.parametrize("why,word", [
    ("a mesh", "a mesh of 2 devices"),
    ("rows off the tiles", "24 rows are not a multiple of 16"),
    ("channels off the lanes", "192 channels are not a multiple of 128"),
    ("a window kept in another type", "kept in float32, x comes in bfloat16"),
    ("the cpu", "cpu backend"),
])
def test_where_the_conv_kernel_cannot_run_the_xla_form_does_and_says_why(
        why, word, monkeypatch):
    from llms_on_kubernetes_tpu.ops import attention
    from llms_on_kubernetes_tpu.parallel import mesh as pmesh

    B, Di, xtype = 32, 256, jnp.float32
    if why == "a mesh":
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
        monkeypatch.setattr(pmesh, "_ACTIVE_MESH", jax.sharding.Mesh(
            np.array(jax.devices()[:2]), (pmesh.AXIS_MODEL,)))
    elif why.endswith("the tiles"):
        monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
        B = 24
    elif why.endswith("the lanes"):
        monkeypatch.setattr(attention, "pallas_mode", lambda: "compiled")
        Di = 192
    elif why.endswith("another type"):
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
        xtype = jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 3)
    conv = jax.random.normal(ks[0], (2, B + 1, 3 * Di))
    xz = jax.random.normal(ks[1], (B, 2 * Di)).astype(xtype)
    w, b = jax.random.normal(ks[2], (4, Di)), jnp.zeros((Di,))
    live = jnp.arange(B) % 3 != 0
    xc, _, after = jax.jit(lambda *a: attention.dispatch_conv_step(*a))(
        xz, w, b, conv, jnp.int32(1), live, attention.live_tiles_first(live))
    impl, said = attention._chosen["conv_step"]
    assert impl == "xla" and said.startswith("one pass, T = 1") \
        and word in said, said
    xc_want, rows = jax.jit(dec._mamba_conv)(
        conv[1, :B], xz[:, None, :Di], w, b, live.astype(jnp.int32))
    np.testing.assert_array_equal(wide(xc), wide(xc_want[:, 0]))
    np.testing.assert_array_equal(
        wide(after), wide(conv.at[1, :B].set(rows.astype(conv.dtype))))


# ---------------------------------------------------------------------------
# the engine: fused K = 4 windows, slot reuse, idle rows, admission while
# others decode, preemption, the prefix cache. A request's every token is
# held to the reference's full forward pass of prompt + output: the
# log-probability the engine reported for it, and its best id
# ---------------------------------------------------------------------------

def engine(params, **kw):
    base = dict(model="debug-jamba", dtype="float32", max_decode_slots=SLOTS,
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
    lp = jax.nn.log_softmax(jnp.asarray(ref_logits(params, seq)), axis=-1)
    lp = np.asarray(lp)
    for j, (tok, entry) in enumerate(zip(req.output, req.output_logprobs)):
        at = len(req.prompt) - 1 + j
        assert abs(entry[0] - lp[at, tok]) < tol, (j, entry[0], lp[at, tok])
        assert tok == int(np.argmax(lp[at]))


def submit(eng, toks, n_out, **kw):
    return eng.submit(list(toks), SamplingParams(
        max_tokens=n_out, temperature=0.0, logprobs=True, **kw))


def poison(eng):
    eng.conv_state = jax.tree.map(lambda a: a + 7.0, eng.conv_state)


@pytest.fixture(params=["xla", "kernel"])
def ssm_step(request, monkeypatch):
    """The token step's state-space update as the XLA step over every slot,
    and as the kernel over the live ones (interpreted here, with every other
    Pallas kernel). The steps' traces are shared between engines and the
    choice is made at trace time: cleared on the way in and out."""
    if request.param == "kernel":
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", "pallas")
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def chose(ssm_step):
    from llms_on_kubernetes_tpu.ops import attention

    return attention._chosen["ssm_step"][0] == (
        "pallas-interpret" if ssm_step == "kernel" else "xla")


@pytest.mark.parametrize("scheduler", ["pipelined", "synchronous"])
def test_fused_windows_idle_rows_and_a_chunked_prompt(params32, scheduler,
                                                      ssm_step):
    eng = engine(params32, async_scheduling=scheduler == "pipelined")
    poison(eng)
    reqs = [submit(eng, prompt(7, 21), 14),       # >= 3 windows of K = 4
            submit(eng, prompt(40, 22), 13)]      # longer than bucket 32
    run(eng, reqs)                                # two of four slots idle
    for r in reqs:
        assert len(r.output) in (13, 14)
        held_to_reference(params32, r)
    # what the scan and the step ran over, and the real tokens among it
    assert eng.path_tokens == {"prefill": 7, "chunk": 40}
    assert eng.ssm_positions["prefill"] == 16
    assert eng.ssm_positions["chunk"] == 32 + 16
    assert chose(ssm_step)
    # the XLA step runs every slot, the kernel the rows live at the launch
    # (the whole window for a row that stops inside it)
    if ssm_step == "xla":
        assert eng.ssm_positions["decode"] % SLOTS == 0
        assert eng.ssm_positions["decode"] >= 2 * eng.decode_tokens
    else:
        assert eng.ssm_positions["decode"] < eng.decode_tokens + 4 * 4
    assert eng.ssm_positions["decode"] >= eng.decode_tokens > 0
    booked = [d for d in eng.ledger.dispatches_view(64) if "ssm_positions" in d]
    assert {d["kind"] for d in booked} == {"prefill", "chunk", "decode"}
    assert all(d["ssm_tokens"] <= d["ssm_positions"] for d in booked)


def test_a_slot_freed_and_taken_again_starts_from_zeros(params32, ssm_step):
    eng = engine(params32, max_decode_slots=1, num_pages=PPS + 1)
    first = submit(eng, prompt(30, 31), 9)
    run(eng, [first])
    poison(eng)         # worse than what the first request left
    second = submit(eng, prompt(3, 32), 9)
    run(eng, [second])
    third = submit(eng, prompt(41, 33), 9)     # through the chunk path
    run(eng, [third])
    for r in (first, second, third):
        held_to_reference(params32, r)
    assert chose(ssm_step)


@pytest.mark.parametrize("ends", ["budget", "stop id"])
def test_rows_that_stop_inside_a_window_under_the_kernel_and_the_xla_step(
        params32, ends, monkeypatch):
    """Three streams through K = 4 windows, two of which end INSIDE a window
    (a budget that runs out at its second step; a stop id sampled there):
    the device masks the row for the rest of the window and its slot's
    state stays where its last live step put it. Greedy streams, their
    log-probabilities and every slot's state are the same with the kernel
    as with the XLA step."""
    def streams(impl, stop_at=None):
        monkeypatch.setenv("LLMK_ATTENTION_IMPL", impl)
        jax.clear_caches()
        try:
            eng = engine(params32)
            poison(eng)
            stop = {} if stop_at is None else {"stop_token_ids": (stop_at,)}
            reqs = [submit(eng, prompt(7, 41), 1 + 4 + 2),
                    submit(eng, prompt(9, 42), 1 + 4 + 4 + 3, **stop),
                    submit(eng, prompt(5, 43), 1 + 12)]
            run(eng, reqs)
        finally:
            jax.clear_caches()
        return reqs, np.asarray(eng.conv_state.ssm)

    stop_at = None
    if ends == "stop id":
        # the second stream's seventh token, new to that stream at that
        # step: the second step of its second window
        plain, _ = streams("xla")
        out = plain[1].output
        stop_at = next(t for j, t in enumerate(out)
                       if j % 4 in (2, 3) and j > 4 and t not in out[:j])
    want, want_state = streams("xla", stop_at)
    got, got_state = streams("pallas", stop_at)
    for w, g in zip(want, got):
        assert g.output == w.output
        np.testing.assert_allclose(
            [e[0] for e in g.output_logprobs],
            [e[0] for e in w.output_logprobs], atol=1e-5)
        held_to_reference(params32, g)
    if stop_at is not None:
        assert got[1].output[-1] == stop_at and len(got[1].output) < 12
    # the slot nobody took keeps its poison, bit for bit; the trash row is
    # the prompts' (padding rows write it), the same either way
    np.testing.assert_array_equal(got_state[:, 3], want_state[:, 3])
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=1e-6)


def test_decode_positions_over_decode_tokens_reads_one_with_the_kernel(
        params32, ssm_step):
    """``llm_ssm_positions_total{path="decode"}`` over
    ``llm_path_tokens_total{path="decode"}`` (the engine's two counts that
    the metrics page shows): one stream of whole windows in four slots
    reads 1.0 where the kernel visits the live rows, slots / live = 4.0
    where the XLA step runs every slot; each decode record carries it."""
    eng = engine(params32)
    r = submit(eng, prompt(7, 51), 1 + 8)
    run(eng, [r])
    assert chose(ssm_step) and eng._ssm_live_only == (ssm_step == "kernel")
    assert eng.decode_tokens == 8
    ratio = eng.ssm_positions["decode"] / eng.decode_tokens
    assert ratio == (1.0 if ssm_step == "kernel" else SLOTS / 1)
    booked = [d for d in eng.ledger.dispatches_view(64)
              if d["kind"] == "decode"]
    assert booked and sum(d["ssm_positions"] for d in booked) == \
        eng.ssm_positions["decode"]


def test_requests_admitted_while_others_decode_read_as_they_do_alone(
        params32):
    eng = engine(params32)
    early = [submit(eng, prompt(9, 61), 24), submit(eng, prompt(20, 62), 24)]
    for _ in range(6):
        eng.step()
    assert any(r.output for r in early) and not all(
        r.finished for r in early)
    late = [submit(eng, prompt(5, 63), 10), submit(eng, prompt(37, 64), 10)]
    run(eng, early + late)
    for r in early + late:
        held_to_reference(params32, r)
        alone = engine(params32)
        same = submit(alone, r.prompt, len(r.output))
        run(alone, [same])
        assert same.output == r.output


def test_preemption_and_resume_reproduce_the_continuation(params32):
    # 6 pages of 8 tokens for two requests that each grow to 4 pages: the
    # younger is preempted and re-prefills prompt + output when pages free
    eng = engine(params32, max_decode_slots=2, num_pages=7)
    reqs = [submit(eng, prompt(12, 41), 18), submit(eng, prompt(12, 42), 18)]
    run(eng, reqs)
    assert eng.preemptions >= 1
    for r in reqs:
        assert len(r.output) == 18
        held_to_reference(params32, r)


def test_the_prefix_cache_adopts_nothing_and_counts_it(params32):
    """A cached page holds keys and values, not the Mamba layers' state at
    its end: nothing is adopted, the second request of one prompt is
    prefilled whole and answers as the first did."""
    eng = engine(params32)
    toks = prompt(24, 51)           # three full pages: adoptable elsewhere
    a = submit(eng, toks, 6)
    run(eng, [a])
    b = submit(eng, toks, 6)
    run(eng, [b])
    assert a.output == b.output
    assert eng.allocator.hit_tokens_total == 0
    assert eng.prefix_reuse_skipped == {"recurrent_state": 2}
    held_to_reference(params32, b)


@pytest.mark.parametrize("kw,word", [
    (dict(quantization="int8"), "--quantization"),
    (dict(speculation="ngram"), "speculation"),
    (dict(kv_host_cache_gb=0.1), "host KV tier"),
    (dict(adapters=(("a", "/nowhere"),)), "LoRA"),
    (dict(multihost=True), "multihost"),
    (dict(role="decode", kv_host_cache_gb=0.1), "role"),
])
def test_what_cannot_carry_the_state_refuses_at_start_up(kw, word):
    with pytest.raises(ValueError, match="debug-jamba.*does not support"):
        try:
            Engine(EngineConfig(model="debug-jamba", **kw))
        except ValueError as e:
            assert word in str(e)
            raise


def test_a_checkpoint_of_this_family_is_not_mapped(tmp_path):
    with pytest.raises(ValueError, match="no tensor names"):
        Engine(EngineConfig(model="debug-jamba"), model_dir=str(tmp_path))


def test_the_state_and_its_counters_on_the_metrics_page(params32):
    from llms_on_kubernetes_tpu.server import metrics

    eng = engine(params32)
    state = eng.conv_state
    assert state.ssm.dtype == jnp.float32 and state.ssm.shape == (
        3, SLOTS + 1, CFG.mamba_d_state, CFG.mamba_d_inner)
    assert state.conv.shape == (3, SLOTS + 1, 3 * CFG.mamba_d_inner)
    assert eng.slot_state_bytes == 3 * (SLOTS + 1) * CFG.mamba_d_inner * (
        16 * 4 + 3 * 4)
    names = {m.name for m in metrics.engine_metrics(
        metrics.Registry()).values()}
    assert {"llm_path_tokens_total", "llm_ssm_positions_total",
            "llm_conv_state_bytes"} <= names
    # a model without Mamba layers books nothing
    plain = Engine(EngineConfig(model="debug-tiny", dtype="float32",
                                prefill_buckets=(32,)))
    r = submit(plain, prompt(5, 1), 3)
    run(plain, [r])
    assert plain.ssm_positions == {"prefill": 0, "chunk": 0, "decode": 0}
    assert not any("ssm_positions" in d for d in plain.ledger.dispatches_view(16))


# ---------------------------------------------------------------------------
# the types: bfloat16 as served (weights, activations, KV; the state in
# float32), and the state KEPT in bfloat16, which has to fail
# ---------------------------------------------------------------------------

DECODED = 24


def worst_top8_diffs(params, dtype, state_dtype=None, prompts=12):
    """Program minus reference, log-probabilities of the reference's 8 best
    ids after a prefill of 20-31 tokens and after each of 24 teacher-forced
    decode steps (float32 reference on the same weights): the largest over
    each prompt."""
    out = []
    for seed in range(prompts):
        toks = prompt(20 + seed + DECODED, 100 + seed)
        n = len(toks) - DECODED
        c = Cache(params, dtype=dtype, state_dtype=state_dtype)
        got = [c.prefill([toks[:n]], 32, [0])[0]]
        for j in range(DECODED):
            got.append(c.decode([toks[n + j], 0, 0, 0],
                                [n + j + 1, 0, 0, 0])[0])
        want = ref_logits(params, toks, range(n - 1, len(toks)))
        worst = 0.0
        for g, w in zip(got, want):
            lw = np.asarray(jax.nn.log_softmax(jnp.asarray(w)))
            lg = np.asarray(jax.nn.log_softmax(jnp.asarray(
                g.astype(np.float32))))
            ids = np.argsort(-lw)[:8]
            worst = max(worst, float(np.abs(lg[ids] - lw[ids]).max()))
        out.append(worst)
    return np.asarray(out)


# bfloat16 weights, activations and KV with a float32 state, over 12
# prompts x 25 positions: 0.097 nats at the largest (0.05-0.1 a prompt),
# the rounding of every product's operands; three times that
BF16_TOL = 0.3


def test_bfloat16_as_served_agrees_within_its_tolerance():
    diffs = worst_top8_diffs(params_of("bfloat16"), "bfloat16")
    assert diffs.max() < BF16_TOL, diffs


def test_a_bfloat16_state_fails_the_tolerance(params32):
    """Everything in float32 but the state-space state, KEPT in bfloat16
    between steps: a running sum that loses 16 of its 24 bits at every
    token. Every prompt reads far over the tolerance that the float32
    state reads under (1.5e-5 at the largest)."""
    kept32 = worst_top8_diffs(params32, "float32", prompts=4)
    assert kept32.max() < F32_TOL, kept32
    lower = worst_top8_diffs(params32, "float32", "bfloat16", prompts=4)
    assert lower.min() > 10 * F32_TOL, lower


def test_a_state_lost_at_a_hand_over_fails_the_served_tolerance(params32):
    """The reference's other control: h set to zero before the last
    position, which is what a chunk or a decode window computes that starts
    from zeros and not from its slot's state. 0.76-2.7 nats over six
    prompts on the reference's 8 best ids, where the served types read
    under 0.1. (Lost 32 positions back it reads 0.05-0.31: at this size the
    state's memory fades over tens of tokens, so a comparison at one
    position sees a state lost shortly before it, not one lost long ago.)"""
    for seed in range(6):
        toks = prompt(48, 200 + seed)
        at = [len(toks) - 1]
        want = jax.nn.log_softmax(ref_logits(params32, toks, at)[0])
        lost = jax.nn.log_softmax(
            ref_logits(params32, toks, at, forget_at=at[0])[0])
        ids = np.argsort(-np.asarray(want))[:8]
        assert np.abs(np.asarray(want - lost))[ids].max() > 2 * BF16_TOL


# ---------------------------------------------------------------------------
# the registry entry, the published keys, the bytes
# ---------------------------------------------------------------------------

def test_changing_rope_theta_changes_nothing(params32):
    toks = prompt(21, 7)
    base = Cache(params32).prefill([toks], 32, [0])
    for theta in (500.0, 1e6):
        other = Cache(params32, dataclasses.replace(CFG, rope_theta=theta))
        np.testing.assert_array_equal(other.prefill([toks], 32, [0]), base)
    # and the positions reach nothing else: the same tokens further along a
    # chunked prompt's slot are attended without a rotation
    assert not CFG.use_rope and not get_config("jamba2-3b").use_rope


def catalogue_keys():
    keys = {k: v for k, v in config_file("jamba2-3b").items()
            if not isinstance(v, (dict, list)) or k == "sliding_window"}
    for k in ("source", "registry_name", "platform", "chips", "reference",
              "shapes", "decode_steps_per_dispatch", "deployment"):
        keys.pop(k)
    return keys


def test_from_hf_config_on_the_published_keys_gives_the_registry_entry():
    keys = catalogue_keys()
    assert keys["model_type"] == "jamba" and keys["mamba_dt_rank"] == 160
    assert from_hf_config(keys, name="jamba2-3b") == get_config("jamba2-3b")
    assert get_config("ai21labs/AI21-Jamba2-3B") is get_config("jamba2-3b")
    full = get_config("jamba2-3b")
    assert full.layer_runs == (
        ("mamba", "dense", 0, 7), ("attn", "dense", 7, 1),
        ("mamba", "dense", 8, 13), ("attn", "dense", 21, 1),
        ("mamba", "dense", 22, 6))
    assert (full.num_attn_layers, full.num_mamba_layers,
            full.num_conv_layers) == (2, 26, 0)
    assert full.keeps_slot_state and not get_config("mistral-7b").keeps_slot_state
    assert get_config("lfm2-24b-a2b").num_conv_layers == 30


@pytest.mark.parametrize("change,word", [
    (dict(num_experts=16), "num_experts"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
])
def test_from_hf_config_refuses_what_nothing_here_measures(change, word):
    with pytest.raises(NotImplementedError, match=word):
        from_hf_config(dict(catalogue_keys(), **change))


@pytest.mark.parametrize("name", ["debug-jamba", "jamba2-3b"])
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
    slots = doc["serve_flags"]["--max-decode-slots"]
    state = jax.eval_shape(lambda: dec.init_conv_state(cfg, slots, "bfloat16"))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state)) \
        == shapes.ssm_state_bytes(doc, slots) == want["ssm_state"]
    assert doc["reduced"] == []
    if name == "jamba2-3b":
        assert sum(a.size for a in leaves) == 3_029_337_472
        assert want["weights"] == 6_058_674_944
        # a step's bytes: the weights once, the state of the rows it
        # updates both ways, 1 KB a cached token
        assert shapes.kv_bytes_per_token(doc) == 1024
        assert shapes.decode_step_bytes(doc, 1, 0) - shapes.decode_step_bytes(
            doc, 0, 0) == 2 * 26 * 5120 * (16 * 4 + 3 * 2) + 2560 * 2
