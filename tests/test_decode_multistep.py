"""ISSUE 8: fused multi-step decode parity — K=1 vs K=4 must be
observably identical.

One jitted dispatch now runs ``decode_steps`` token-steps on device
(sampling, penalties, stop detection, grammar FSM, early-exit masks all
inside the scan). These tests pin the contract that fusing the loop is
a pure perf change: identical token streams and finish reasons for
greedy, seeded-sampled-with-penalties, stop-mid-window, and
grammar-constrained rows.

Divergence triage follows the PR-4 teacher-forced margin idiom
(test_quant.py): a fused-vs-unfused flip is only a failure when the
reference model's top-1/top-2 logprob margin at the flip position is
decisive — XLA may schedule the in-scan forward differently, and a
near-tie argmax flip cascades into a legitimately different greedy
stream.
"""

import pytest

from llms_on_kubernetes_tpu.configs import ModelConfig, get_config
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams,
)

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14]]


def _mk(decode_steps, **kw):
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=True, async_depth=2,
        decode_steps=decode_steps,
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


def _assert_parity(ref, fused, prompt, ref_eng, label):
    """Exact stream parity, with margin-aware triage on a greedy flip."""
    if (fused.output == ref.output
            and fused.finish_reason == ref.finish_reason):
        return
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.models.decoder import forward_score

    div = next((i for i, (a, b) in enumerate(zip(ref.output, fused.output))
                if a != b), min(len(ref.output), len(fused.output)))
    seq = list(prompt) + list(ref.output)
    tokens = jnp.asarray([seq], jnp.int32)
    lengths = jnp.asarray([len(seq)], jnp.int32)
    _lp, _ids, top = forward_score(
        ref_eng.params, get_config("debug-tiny"), tokens, lengths, top_k=2)
    pos = len(prompt) + div - 1  # logits at pos predict token pos+1
    margin = float(top[0, pos, 0] - top[0, pos, 1])
    assert margin <= 0.05, (
        f"{label}: fused K diverged at output {div} on a decisive "
        f"(margin {margin:.3f}) position: "
        f"{ref.output[div:div + 3]} -> {fused.output[div:div + 3]}")


def test_greedy_parity_k1_vs_k4():
    e1, e4 = _mk(1), _mk(4)
    p = SamplingParams(temperature=0.0, max_tokens=12)
    r1 = _run(e1, [e1.submit(pr, p) for pr in PROMPTS])
    r4 = _run(e4, [e4.submit(pr, p) for pr in PROMPTS])
    for ref, fused, pr in zip(r1, r4, PROMPTS):
        _assert_parity(ref, fused, pr, e1, "greedy")
    # the fused engine really amortized: fewer device launches for the
    # same committed tokens
    assert e4.decode_dispatches < e1.decode_dispatches
    assert e4.decode_tokens == e1.decode_tokens


def test_seeded_sampled_with_penalties_parity():
    """The PRNG chain is keyed on (seed, position), not on dispatch
    boundaries, so seeded sampling with output-dependent penalties must
    be bit-identical across K."""
    def params(i):
        return SamplingParams(temperature=0.9, top_k=8, seed=100 + i,
                              presence_penalty=0.5, frequency_penalty=0.3,
                              max_tokens=12)

    e1, e4 = _mk(1), _mk(4)
    r1 = _run(e1, [e1.submit(pr, params(i))
                   for i, pr in enumerate(PROMPTS)])
    r4 = _run(e4, [e4.submit(pr, params(i))
                   for i, pr in enumerate(PROMPTS)])
    for ref, fused in zip(r1, r4):
        assert fused.output == ref.output, (fused.output, ref.output)
        assert fused.finish_reason == ref.finish_reason


def test_stop_token_mid_window_parity():
    """A stop token landing inside the fused window must finish the row
    at the same position as K=1 — the device mask keeps later window
    steps from leaking into the stream — and the wasted tail shows up in
    the early-exit accounting."""
    probe_eng = _mk(1)
    probe = _run(probe_eng, [probe_eng.submit(
        PROMPTS[0], SamplingParams(temperature=0.0, max_tokens=12))])
    stop_tok = probe[0].output[5]  # mid-window for K=4 windows

    def params(_i):
        return SamplingParams(temperature=0.0, max_tokens=12,
                              stop_token_ids=(stop_tok,))

    e1, e4 = _mk(1), _mk(4)
    r1 = _run(e1, [e1.submit(pr, params(i))
                   for i, pr in enumerate(PROMPTS)])
    r4 = _run(e4, [e4.submit(pr, params(i))
                   for i, pr in enumerate(PROMPTS)])
    assert any(r.finish_reason == "stop" for r in r1)  # it really fired
    for ref, fused in zip(r1, r4):
        assert fused.output == ref.output, (fused.output, ref.output)
        assert fused.finish_reason == ref.finish_reason
    assert e4.early_exit_steps > 0


def test_grammar_constrained_row_parity():
    """A grammar row stays in the fused loop (on-device FSM transitions
    per window step) instead of forcing a host replay; constrained and
    free rows in the same batch both match K=1."""
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

    def mk(k):
        return Engine(EngineConfig(
            model="debug-tiny", dtype="float32", max_decode_slots=4,
            page_size=4, num_pages=512, pages_per_slot=64,
            prefill_buckets=(16, 32), async_scheduling=True,
            async_depth=2, decode_steps=k), model_config=cfg)

    def submit_all(eng):
        con = eng.submit([1, 2, 3], SamplingParams(
            temperature=1.0, max_tokens=32, stop_token_ids=(eos,),
            seed=7, grammar=g))
        free = [eng.submit(pr, SamplingParams(
            temperature=0.8, max_tokens=16, seed=20 + i))
            for i, pr in enumerate(PROMPTS[:2])]
        return [con] + free

    e1, e4 = mk(1), mk(4)
    r1 = _run(e1, submit_all(e1))
    r4 = _run(e4, submit_all(e4))
    for ref, fused in zip(r1, r4):
        assert fused.output == ref.output, (fused.output, ref.output)
        assert fused.finish_reason == ref.finish_reason
    # the constrained stream is a valid grammar path on BOTH engines
    for r in (r1[0], r4[0]):
        s = g.start
        for t in r.output:
            if t == eos:
                break
            s = g.next_state(s, t)
            assert s >= 0


def test_multihost_clamps_decode_steps():
    cfg = EngineConfig(model="debug-tiny", decode_steps=8, multihost=True)
    assert cfg.decode_steps == 1


def test_decode_steps_env_default(monkeypatch):
    monkeypatch.setenv("LLMK_DECODE_STEPS", "2")
    assert EngineConfig(model="debug-tiny").decode_steps == 2
    monkeypatch.delenv("LLMK_DECODE_STEPS")
    assert EngineConfig(model="debug-tiny").decode_steps == 4
    with pytest.raises(ValueError):
        EngineConfig(model="debug-tiny", decode_steps=0)


# ---------------------------------------------------------------------------
# PR 53: the optional logit transforms (penalty counts, penalties, logit
# bias) run only in a window where a live row asks for one. The decision is
# made inside the one executable, and changes nothing a request can see.
# ---------------------------------------------------------------------------

MIXES = {
    # one row of each kind, of different lengths, so that the run's windows
    # go from mixed to all-plain as the asking rows finish
    "mixed": [dict(max_tokens=26),
              dict(max_tokens=6, presence_penalty=1.5),
              dict(max_tokens=9, frequency_penalty=0.7),
              dict(max_tokens=7, logit_bias=((5, 4.0), (9, -3.0))),
              dict(max_tokens=11, presence_penalty=0.4,
                   frequency_penalty=0.9, logit_bias=((2, 6.0),))],
    "all_plain": [dict(max_tokens=n) for n in (26, 6, 9, 7, 11)],
}
MIX_PROMPTS = PROMPTS + [[15, 16, 17]]


def _unconditioned_window(params, cfg, K, packed, last_toks, prefill_toks,
                          k_pages, v_pages, counts, base_key):
    """The decode window as it was before it looked at its rows, in plain
    arithmetic: EVERY live row's input token counted, the penalties and the
    bias scatter applied to EVERY row's logits, every token step."""
    import jax
    import jax.numpy as jnp

    from llms_on_kubernetes_tpu.engine import engine as E
    from llms_on_kubernetes_tpu.engine.sampling import sample
    from llms_on_kubernetes_tpu.models.decoder import forward_decode

    def f32(col):
        return jax.lax.bitcast_convert_type(col, jnp.float32)

    lengths0, budget = packed[:, 0], packed[:, E._BUD_DEC]
    presence, frequency = f32(packed[:, 8]), f32(packed[:, 9])
    stop_ids = packed[:, E._STOP_DEC:E._STOP_DEC + E.STOP_SLOTS]
    b_ids, b_vals = E._unpack_bias(packed, E._BIAS_DEC)
    page_table = packed[:, E._DEC_COLS:]
    B = packed.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)
    toks0 = E._merge_tokens(last_toks, packed[:, 1], packed[:, 2],
                            prefill_toks, packed[:, 7])

    def body(carry, j):
        cur, alive, k_pages, v_pages, counts = carry
        lengths = jnp.where(alive, lengths0 + j, 0)
        counts = counts.at[rows, cur].add((lengths > 0).astype(counts.dtype))
        logits, k_pages, v_pages = forward_decode(
            params, cfg, cur, lengths, k_pages, v_pages, page_table,
            pos_delta=packed[:, 10], adapter_idx=packed[:, E._ADP_DEC])
        c = counts.astype(jnp.float32)
        x = (logits.astype(jnp.float32) - presence[:, None] * (c > 0)
             - frequency[:, None] * c)
        x = x.at[rows[:, None], jnp.maximum(b_ids, 0)].add(
            jnp.where(b_ids >= 0, b_vals, 0.0))
        res = sample(x, E._slot_keys(base_key, packed[:, 6], lengths),
                     f32(packed[:, 4]), packed[:, 3], f32(packed[:, 5]))
        stopped = ((stop_ids >= 0)
                   & (stop_ids == res.tokens[:, None])).any(axis=1)
        new = jnp.where(alive, res.tokens, cur)
        alive = alive & ~stopped & (j + 1 < budget)
        return (new, alive, k_pages, v_pages, counts), res.host_pack()

    carry0 = (toks0, (lengths0 > 0) & (budget > 0), k_pages, v_pages, counts)
    (toks, _a, _k, _v, counts), packs = jax.lax.scan(
        body, carry0, jnp.arange(K, dtype=jnp.int32))
    return packs, toks, counts


@pytest.fixture(scope="module")
def windows_beside_the_unconditioned():
    """Each mix run once on a K = 4 engine whose every decode window is
    first computed by ``_unconditioned_window`` on the same operands:
    ``{mix: (engine, requests, [(packed, packs, toks, counts, reference
    packs, toks, counts), ...])}``."""
    import jax
    import numpy as np

    reference = jax.jit(_unconditioned_window, static_argnums=(1, 2))
    out = {}
    for mix, rows in MIXES.items():
        eng = _mk(4, max_decode_slots=6)
        real, seen = eng._decode_multi, []

        def beside(params, cfg, K, packed, last, pre, kp, vp, counts, key,
                   fsm, conv, real=real, seen=seen):
            assert fsm is None and conv is None
            ref = reference(params, cfg, K, packed, last, pre, kp, vp,
                            counts, key)
            ref = [np.asarray(a) for a in ref]
            got = real(params, cfg, K, packed, last, pre, kp, vp, counts,
                       key, fsm, conv)
            seen.append((np.asarray(packed), np.asarray(got[0]),
                         np.asarray(got[1]), np.asarray(got[4]), *ref))
            return got

        eng._decode_multi = beside
        reqs = _run(eng, [
            eng.submit(pr, SamplingParams(temperature=0.0, **kw))
            for pr, kw in zip(MIX_PROMPTS, rows)])
        out[mix] = (eng, reqs, seen)
    return out


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_window_packs_are_the_unconditioned_formulas_bit_for_bit(
        windows_beside_the_unconditioned, mix):
    """Every K = 4 window of a run of mixed rows (plain, presence only,
    frequency only, bias only, penalised + bias), shaped or plain as its
    live rows make it, returns for its live rows the packs of the
    unconditioned formula, bit for bit, and the same next tokens; so does a
    run of plain rows alone."""
    import numpy as np

    from llms_on_kubernetes_tpu.engine import engine as E

    eng, reqs, seen = windows_beside_the_unconditioned[mix]
    assert len(seen) >= 4 and all(r.finished for r in reqs)
    kinds = set()
    for packed, packs, toks, counts, ref_packs, ref_toks, ref_counts in seen:
        penalised, shaped = E._window_asks(packed)
        kinds.add(bool(shaped))
        live = packed[:, 0] > 0
        assert live.any()
        np.testing.assert_array_equal(packs[:, live], ref_packs[:, live])
        np.testing.assert_array_equal(toks[live], ref_toks[live])
        # the counts a penalty reads are the counts the old step kept
        np.testing.assert_array_equal(counts[penalised],
                                      ref_counts[penalised])
    assert kinds == ({True, False} if mix == "mixed" else {False})
    assert eng.decode_windows == {
        "plain": sum(not E._window_asks(s[0])[1] for s in seen),
        "shaped": sum(bool(E._window_asks(s[0])[1]) for s in seen)}


def test_the_window_holds_conditionals_and_a_plain_run_leaves_the_counts(
        windows_beside_the_unconditioned):
    """The lowered decode window of debug-tiny holds the two conditionals
    (the count update's and the sampler's), in the one executable there
    was; run on all-plain rows it leaves ``engine.token_counts`` as the
    prompts' resets left it, where the mixed run counted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llms_on_kubernetes_tpu.engine import engine as E

    eng, _reqs, seen = windows_beside_the_unconditioned["all_plain"]
    assert not np.asarray(eng.token_counts).any()
    assert all(not s[3].any() for s in seen)
    mixed = windows_beside_the_unconditioned["mixed"][0]
    assert np.asarray(mixed.token_counts).any()
    packed = jnp.asarray(seen[0][0])
    hlo = jax.jit(E._decode_multi_packed_step, static_argnums=(1, 2)).lower(
        eng.params, eng.model_config, 4, packed,
        jnp.zeros((6,), jnp.int32), jnp.zeros((1,), jnp.int32),
        eng.k_pages, eng.v_pages, eng.token_counts, eng._key,
    ).as_text(dialect="hlo")
    assert hlo.count(" conditional(") == 2, hlo.count(" conditional(")
