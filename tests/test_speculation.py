"""ISSUE 12: speculative decoding on the fused-decode substrate.

Speculation is a pure-performance transform: drafted tokens ride the
packed K-step window, one ``forward_verify`` dispatch scores every window
position, and exact-match acceptance keeps greedy streams bit-identical
to speculation off. These tests pin that contract end to end:

- model level: ``forward_verify`` logits equal sequential
  ``forward_decode``'s at every window position to float32 rounding, with
  the same argmax (same chunk attention the one-shot path produces
  position-by-position);
- engine level: greedy AND seeded-sampled streams match speculation off
  exactly (same fold_in(base, seed)+position PRNG chain, same penalty
  counts);
- rejection mid-window restores reclaimable page counts and a recycled
  slot replays exactly like a fresh engine (the PR-8 abort harness);
- stop tokens inside a drafted suffix finish at the same position;
- grammar-FSM rows accept-check through ``_fsm_apply`` (a draft the
  grammar forbids is rejected, the stream stays a valid grammar path);
- multihost clamps speculation off cleanly.
"""

import time

import numpy as np
import pytest

from llms_on_kubernetes_tpu.configs import ModelConfig, get_config
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.engine.speculation import (
    DraftModelDrafter, PromptLookupDrafter, SpecPolicy,
)

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]
# lookup-friendly: the tail n-gram [5, 6, 7, 5, 6] repeats inside the prompt
REPETITIVE = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6]
# A prompt alone does not keep the drafter fed: the first verify can only
# follow the admission's plain window, and by then the context's tail is
# random weights' output, which repeats nothing of the prompt. Held to six
# tokens (an equal bias: the model still chooses among them) a stream
# always has an n-gram to offer, and the model agrees with part of it.
SMALL_VOCAB = tuple((t, 50.0) for t in range(4, 10))


def _mk(speculation=None, **kw):
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
        decode_steps=4, speculation=speculation,
    )
    base.update(kw)
    return Engine(EngineConfig(**base))


def _run(eng, reqs):
    steps = 0
    while any(not r.finished for r in reqs):
        eng.step()
        steps += 1
        assert steps < 10_000
    return reqs


# ---------------------------------------------------------------------------
# drafter / policy units
# ---------------------------------------------------------------------------

def test_prompt_lookup_proposes_continuation():
    d = PromptLookupDrafter(max_ngram=3, min_ngram=1)
    ctx = np.array([1, 2, 3, 9, 8, 1, 2, 3], np.int32)
    assert d.propose(ctx, 3).tolist() == [9, 8, 1]


def test_prompt_lookup_full_window_on_repeated_run():
    # a run of one token must propose max_draft tokens, not the single
    # token the flush-with-tail occurrence would leave
    d = PromptLookupDrafter()
    ctx = np.array([7] * 10, np.int32)
    assert d.propose(ctx, 3).tolist() == [7, 7, 7]


def test_prompt_lookup_no_match_is_empty():
    d = PromptLookupDrafter()
    assert d.propose(np.arange(16, dtype=np.int32), 3).size == 0
    assert d.propose(np.array([1], np.int32), 3).size == 0
    assert d.propose(np.array([1, 2, 1, 2], np.int32), 0).size == 0


def test_prompt_lookup_prefers_longest_ngram():
    # tail [2, 3] occurs twice; the 2-gram match (continuation 4) must
    # beat the 1-gram match of [3] alone (continuation 9)
    d = PromptLookupDrafter(max_ngram=3, min_ngram=1)
    ctx = np.array([2, 3, 4, 3, 9, 2, 3], np.int32)
    assert d.propose(ctx, 1).tolist() == [4]


def test_spec_policy_demotes_and_reprobes():
    p = SpecPolicy(min_accept=0.3, min_dispatches=4, probe_interval=8)
    assert p.should_draft()
    for _ in range(12):
        p.note(3, 0)  # nothing accepted
    assert not p.should_draft()
    for _ in range(8):
        p.tick()
    assert p.should_draft()          # probe window open
    p.note(3, 3)                     # probe succeeded...
    for _ in range(20):
        p.note(3, 3)
    assert p.should_draft()          # ...EMA recovered, promoted again
    assert 0.0 < p.accept_ratio < 1.0


def test_spec_policy_note_empty_counts_against():
    p = SpecPolicy(min_accept=0.3, min_dispatches=4, probe_interval=8)
    for _ in range(12):
        p.note_empty()
    assert not p.should_draft()
    assert p.drafted == 0            # metric counters untouched


def test_draft_model_drafter_greedy_rollout():
    # a drafter wrapping the SAME model+weights as the target must
    # propose exactly the target's greedy continuation
    import jax

    from llms_on_kubernetes_tpu.models.decoder import init_params

    cfg = get_config("debug-tiny")
    params = init_params(cfg, jax.random.key(0), dtype="float32")
    eng = _mk()  # seed 0: identical weights
    ref = eng.generate([1, 2, 3, 4],
                       SamplingParams(temperature=0.0, max_tokens=3))
    d = DraftModelDrafter(params, cfg, window=32, max_draft=3)
    got = d.propose(np.array([1, 2, 3, 4], np.int32), 3)
    assert got.tolist() == ref


# ---------------------------------------------------------------------------
# model level: verify == sequential decode, to float32 rounding
# ---------------------------------------------------------------------------

def test_forward_verify_matches_sequential_decode():
    """One [1, K] window against K [1, 1] steps. They are different XLA
    programs (a batched product against K single-row ones), so equal to
    float32 rounding and not to the bit: the run reads 1.2e-6 absolute,
    4.9e-5 relative on the logits and 1e-6 on the window's K/V rows, layer
    0's among them. What acceptance rests on, the argmax at every window
    position, is the same; the prompt's rows are the same bytes."""
    import jax
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine.cache import (
        CacheConfig, PageAllocator, init_pages,
    )
    from llms_on_kubernetes_tpu.models.decoder import (
        forward_decode, forward_prefill, forward_verify, init_params,
    )

    cfg = get_config("debug-tiny")
    params = init_params(cfg, jax.random.key(0), dtype="float32")
    cc = CacheConfig(num_layers=cfg.num_layers,
                     num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                     num_pages=32, page_size=4, pages_per_slot=8,
                     dtype="float32")
    rng = np.random.default_rng(0)
    n, K = 6, 4
    prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)

    def setup():
        al = PageAllocator(cc.num_pages, cc.page_size, 1, cc.pages_per_slot)
        al.allocate(0, n + K + 2)
        pt = jnp.asarray(al.page_tables)
        kp, vp = init_pages(cc)
        toks = np.zeros((1, 8), np.int32)
        toks[0, :n] = prompt
        logits, kp, vp = forward_prefill(
            params, cfg, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
            kp, vp, pt)
        return logits, kp, vp, pt

    logits, kp, vp, pt = setup()
    cur = int(np.argmax(np.asarray(logits)[0]))
    fed, seq_logits = [cur], []
    for j in range(K):
        lg, kp, vp = forward_decode(
            params, cfg, jnp.asarray([cur], jnp.int32),
            jnp.asarray([n + 1 + j], jnp.int32), kp, vp, pt)
        seq_logits.append(np.asarray(lg)[0])
        cur = int(np.argmax(np.asarray(lg)[0]))
        fed.append(cur)

    _, kp2, vp2, pt = setup()
    win = np.asarray(fed[:K], np.int32)[None, :]
    vlg, kp2, vp2 = forward_verify(
        params, cfg, jnp.asarray(win), jnp.asarray([n], jnp.int32),
        jnp.asarray([K], jnp.int32), kp2, vp2, pt)
    vlg, seq_logits = np.asarray(vlg)[0], np.stack(seq_logits)
    np.testing.assert_allclose(vlg, seq_logits, rtol=2e-4, atol=5e-6)
    assert vlg.argmax(-1).tolist() == fed[1:]
    table = np.asarray(pt)[0]
    for seq, win in ((kp, kp2), (vp, vp2)):
        seq, win = np.asarray(seq.data), np.asarray(win.data)
        for layer in range(cfg.num_layers):
            for pos in range(n + K):
                at = (slice(None), layer * cc.num_pages
                      + table[pos // cc.page_size], pos % cc.page_size)
                if pos < n:
                    np.testing.assert_array_equal(win[at], seq[at])
                else:
                    np.testing.assert_allclose(win[at], seq[at], rtol=0,
                                               atol=5e-6)


# ---------------------------------------------------------------------------
# engine level: stream parity + accounting
# ---------------------------------------------------------------------------

def test_greedy_bit_identical_spec_on_off():
    base, spec = _mk(), _mk("ngram")
    p = SamplingParams(temperature=0.0, max_tokens=24, logit_bias=SMALL_VOCAB)
    r0 = _run(base, [base.submit(REPETITIVE, p)])
    r1 = _run(spec, [spec.submit(REPETITIVE, p)])
    assert r1[0].output == r0[0].output
    assert r1[0].finish_reason == r0[0].finish_reason
    assert spec.spec_dispatches > 0          # speculation actually ran
    assert spec.spec_drafted_tokens > 0


def test_greedy_parity_mixed_batch():
    def submit_all(eng):
        return [eng.submit(pr, SamplingParams(temperature=0.0,
                                              max_tokens=16))
                for pr in [REPETITIVE] + PROMPTS[:3]]

    base, spec = _mk(), _mk("ngram")
    r0 = _run(base, submit_all(base))
    r1 = _run(spec, submit_all(spec))
    for ref, got in zip(r0, r1):
        assert got.output == ref.output, (got.output, ref.output)
        assert got.finish_reason == ref.finish_reason


def test_seeded_sampling_parity_spec_on_off():
    def submit_all(eng):
        return [eng.submit(pr, SamplingParams(
            temperature=0.9, top_k=8, seed=100 + i,
            presence_penalty=0.3, frequency_penalty=0.2, max_tokens=20))
            for i, pr in enumerate([REPETITIVE, PROMPTS[0]])]

    base, spec = _mk(), _mk("ngram")
    r0 = _run(base, submit_all(base))
    r1 = _run(spec, submit_all(spec))
    for ref, got in zip(r0, r1):
        assert got.output == ref.output, (got.output, ref.output)
        assert got.finish_reason == ref.finish_reason


def test_full_accept_drops_dispatches_per_token():
    # logit_bias pins greedy to one token: the drafter full-accepts and
    # K=4 windows commit ~4 tokens per dispatch (< 0.286 per ISSUE 12)
    p = SamplingParams(temperature=0.0, max_tokens=24,
                       logit_bias=((42, 90.0),))
    spec = _mk("ngram")
    _run(spec, [spec.submit([1, 2, 3, 42, 42, 42], p)])
    steps = list(spec.steps_obs)
    assert spec.spec_accepted_tokens == spec.spec_drafted_tokens > 0
    assert len(steps) / sum(steps) < 0.286


def test_draft_model_tier_parity():
    # tier B with a same-config random draft model (seed-matched => it IS
    # the target): full acceptance, exact parity
    base = _mk()
    spec = _mk("draft", draft_model="debug-tiny")
    p = SamplingParams(temperature=0.0, max_tokens=16)
    r0 = _run(base, [base.submit(PROMPTS[0], p)])
    r1 = _run(spec, [spec.submit(PROMPTS[0], p)])
    assert r1[0].output == r0[0].output
    assert spec.spec_accepted_tokens > 0


def test_rejection_midwindow_restores_pages_and_replays():
    """Draft rejections write KV past the accepted length; the tail is
    dead weight the next dispatch overwrites, never a page leak: after
    the stream finishes every page is reclaimable again and a request on
    the recycled slot decodes exactly like on a fresh engine (the PR-8
    mid-window abort harness, driven by rejections instead of aborts)."""
    eng = _mk("ngram")
    alloc = eng.allocator
    reclaimable0 = alloc.num_free_pages + alloc.num_evictable_pages
    # adversarial traffic: random-weights continuations rarely match the
    # lookup drafts => rejections happen mid-window
    reqs = _run(eng, [eng.submit(pr, SamplingParams(
        temperature=0.0, max_tokens=12)) for pr in [REPETITIVE, PROMPTS[1]]])
    assert all(r.finished for r in reqs)
    eng._drain_async()
    assert (alloc.num_free_pages + alloc.num_evictable_pages
            == reclaimable0), "pages leaked by rejected drafts"
    # recycled slot parity: same prompt, fresh engine
    replay = eng.submit([9, 10, 11],
                        SamplingParams(temperature=0.0, max_tokens=8))
    hard = time.monotonic() + 120
    while not replay.finished:
        assert time.monotonic() < hard
        eng.step()
    fresh_eng = _mk("ngram")
    fresh = fresh_eng.submit([9, 10, 11],
                             SamplingParams(temperature=0.0, max_tokens=8))
    while not fresh.finished:
        assert time.monotonic() < hard
        fresh_eng.step()
    assert replay.output == fresh.output
    assert replay.finish_reason == fresh.finish_reason


def test_stop_token_inside_drafted_suffix():
    """A stop token the model samples inside the drafted region must
    finish the stream at the same position as speculation off — the
    device masks the rest of the window, the host discards the tail."""
    probe_eng = _mk()
    probe = _run(probe_eng, [probe_eng.submit(
        REPETITIVE, SamplingParams(temperature=0.0, max_tokens=12))])
    stop_tok = probe[0].output[5]  # lands mid-window for K=4

    p = SamplingParams(temperature=0.0, max_tokens=12,
                       stop_token_ids=(stop_tok,))
    base, spec = _mk(), _mk("ngram")
    r0 = _run(base, [base.submit(REPETITIVE, p)])
    r1 = _run(spec, [spec.submit(REPETITIVE, p)])
    assert r0[0].finish_reason == "stop"  # it really fired
    assert r1[0].output == r0[0].output
    assert r1[0].finish_reason == "stop"


def test_grammar_row_accept_checks_through_fsm():
    """Grammar rows ride the spec window: each accept iteration masks
    logits through _fsm_apply, so a draft the grammar forbids can never
    be accepted — the stream stays a valid grammar path and matches the
    unspeculated engine exactly."""
    from llms_on_kubernetes_tpu.engine.grammar import (
        compile_response_format, token_bytes_of,
    )
    from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer

    eos = ByteTokenizer.EOS
    cfg = ModelConfig(
        "debug-grammar", vocab_size=258, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512)
    g = compile_response_format({"type": "json_object"},
                                token_bytes_of(ByteTokenizer()), [eos])

    def mk(speculation):
        return Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=4,
            page_size=4, num_pages=512, pages_per_slot=64,
            prefill_buckets=(16, 32), async_scheduling=True,
            async_depth=2, decode_steps=4, speculation=speculation),
            model_config=cfg)

    def submit_all(eng):
        con = eng.submit(REPETITIVE, SamplingParams(
            temperature=1.0, max_tokens=32, stop_token_ids=(eos,),
            seed=7, grammar=g))
        free = eng.submit(REPETITIVE, SamplingParams(
            temperature=0.0, max_tokens=16))
        return [con, free]

    e0, e1 = mk(None), mk("ngram")
    r0 = _run(e0, submit_all(e0))
    r1 = _run(e1, submit_all(e1))
    for ref, got in zip(r0, r1):
        assert got.output == ref.output, (got.output, ref.output)
        assert got.finish_reason == ref.finish_reason
    for r in (r0[0], r1[0]):  # valid grammar path on BOTH engines
        s = g.start
        for t in r.output:
            if t == eos:
                break
            s = g.next_state(s, t)
            assert s >= 0


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

def test_multihost_forces_speculation_off():
    cfg = EngineConfig(model="debug-tiny", decode_steps=8,
                       speculation="ngram", multihost=True)
    assert cfg.decode_steps == 1
    assert cfg.speculation is None


def test_speculation_env_and_validation(monkeypatch):
    monkeypatch.setenv("LLMK_SPECULATION", "ngram")
    assert EngineConfig(model="debug-tiny").speculation == "ngram"
    monkeypatch.delenv("LLMK_SPECULATION")
    assert EngineConfig(model="debug-tiny").speculation is None
    assert EngineConfig(model="debug-tiny",
                        speculation="off").speculation is None
    # a draft model alone implies the draft tier
    cfg = EngineConfig(model="debug-tiny", draft_model="debug-tiny")
    assert cfg.speculation == "draft"
    with pytest.raises(ValueError):
        EngineConfig(model="debug-tiny", speculation="banana")
    with pytest.raises(ValueError):
        EngineConfig(model="debug-tiny", speculation="draft")


def test_sync_scheduler_ignores_speculation():
    # sync scheduling has no fused-window substrate: the knob is inert,
    # outputs match
    eng = _mk("ngram", async_scheduling=False)
    assert eng._spec is None
    base = _mk(None, async_scheduling=False)
    p = SamplingParams(temperature=0.0, max_tokens=8)
    assert (eng.generate(REPETITIVE, p) == base.generate(REPETITIVE, p))
