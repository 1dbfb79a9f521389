"""Presence/frequency penalties, per-token logprobs, admission control.

OpenAI-parity features the reference served through vLLM's engine image
(SURVEY §2.3 row 1). Penalties are applied on device from per-slot
OUTPUT-token counts; logprobs ride the same device->host read as the
sampled tokens; a bounded waiting queue gives the API a 429 signal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, QueueFullError, SamplingParams,
)
from llms_on_kubernetes_tpu.engine.sampling import LOGPROB_TOPK, sample

GREEDY = dict(temperature=0.0)


def make_engine(**kw):
    defaults = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=4, num_pages=128, pages_per_slot=16,
        prefill_buckets=(16, 32),
    )
    defaults.update(kw)
    return Engine(EngineConfig(**defaults))


def test_sample_penalty_math():
    """penalized = logits - presence*(count>0) - frequency*count."""
    logits = jnp.asarray([[2.0, 1.9, 0.0, -1.0]], jnp.float32)
    counts = jnp.asarray([[3, 0, 0, 0]], jnp.int32)
    args = (jax.random.key(0), jnp.asarray([0.0]),
            jnp.asarray([0], jnp.int32), jnp.asarray([1.0]))
    # no penalty: argmax is token 0
    assert sample(logits, *args).tokens.tolist() == [0]
    # presence 0.2: 2.0 - 0.2 = 1.8 < 1.9 -> token 1 wins
    res = sample(logits, *args,
                 penalties=(jnp.asarray([0.2]), jnp.asarray([0.0]), counts))
    assert res.tokens.tolist() == [1]
    # frequency 0.05 with count 3: 2.0 - 0.15 = 1.85 < 1.9 -> token 1
    res = sample(logits, *args,
                 penalties=(jnp.asarray([0.0]), jnp.asarray([0.05]), counts))
    assert res.tokens.tolist() == [1]
    # penalties on tokens never generated are no-ops
    res = sample(logits, *args,
                 penalties=(jnp.asarray([2.0]), jnp.asarray([2.0]),
                            jnp.zeros_like(counts)))
    assert res.tokens.tolist() == [0]


@pytest.mark.parametrize("async_sched", [False, True])
def test_penalized_generation_deterministic_and_path_invariant(async_sched):
    """Penalties must behave identically on the sync and async schedulers
    and across preemption-resume (counts are rebuilt from the replayed
    output)."""
    p = SamplingParams(max_tokens=14, presence_penalty=1.5,
                       frequency_penalty=0.5, **GREEDY)
    prompt = [3, 17, 9, 5]
    base = make_engine(async_scheduling=async_sched).generate(prompt, p)
    again = make_engine(async_scheduling=async_sched).generate(prompt, p)
    assert base == again

    other = make_engine(async_scheduling=not async_sched).generate(prompt, p)
    assert base == other

    # tight pool forces preemption of the younger request mid-generation
    tight = make_engine(num_pages=7, pages_per_slot=8, max_decode_slots=2,
                        async_scheduling=async_sched)
    a = tight.submit([40, 2, 8], p)
    b = tight.submit(prompt, p)
    for _ in range(500):
        if not tight.has_work():
            break
        tight.step()
    assert a.finished and b.finished
    assert b.output == base
    assert tight.preemptions >= 1


def test_penalty_changes_output():
    """A strong presence penalty must change what greedy decoding repeats."""
    prompt = [7, 7, 7]
    free = make_engine().generate(prompt, SamplingParams(max_tokens=12, **GREEDY))
    pen = make_engine().generate(
        prompt, SamplingParams(max_tokens=12, presence_penalty=2.0,
                               frequency_penalty=2.0, **GREEDY))
    # the unpenalized run of a tiny random model repeats tokens; the
    # penalized run must diverge once the first repeat would occur
    assert free != pen


def test_output_logprobs_recorded():
    eng = make_engine()
    req = eng.submit([1, 2, 3], SamplingParams(max_tokens=6, **GREEDY))
    while not req.finished:
        eng.step()
    assert len(req.output_logprobs) == len(req.output)
    for tok, (lp, top_ids, top_lps) in zip(req.output, req.output_logprobs):
        assert lp <= 0.0 and np.isfinite(lp)
        assert len(top_ids) == len(top_lps) == LOGPROB_TOPK
        # greedy: the sampled token is the argmax == top-1 candidate
        assert top_ids[0] == tok
        assert abs(top_lps[0] - lp) < 1e-5
        assert all(top_lps[i] >= top_lps[i + 1] - 1e-6
                   for i in range(len(top_lps) - 1))


def test_queue_full_raises_429_signal():
    eng = make_engine(max_waiting=2)
    eng.submit([1], SamplingParams(max_tokens=1))
    eng.submit([2], SamplingParams(max_tokens=1))
    with pytest.raises(QueueFullError):
        eng.submit([3], SamplingParams(max_tokens=1))


def test_sampling_param_validation():
    eng = make_engine()
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1], SamplingParams(top_k=65))
    with pytest.raises(ValueError, match="presence_penalty"):
        eng.submit([1], SamplingParams(presence_penalty=3.0))
    with pytest.raises(ValueError, match="frequency_penalty"):
        eng.submit([1], SamplingParams(frequency_penalty=-2.5))
    # boundary values are accepted
    eng.submit([1], SamplingParams(top_k=64, presence_penalty=2.0,
                                   frequency_penalty=-2.0, max_tokens=1))


# ---------------------------------------------------------------------------
# PR 53: a slot's counts are kept only while its request asks for a penalty
# ---------------------------------------------------------------------------

def _finish(eng, reqs):
    for _ in range(2000):
        if all(r.finished for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("async_sched", [False, True])
@pytest.mark.parametrize("preempt", [False, True])
def test_slot_reuse_after_a_plain_request_starts_from_clean_counts(
        async_sched, preempt):
    """The stale-count hazard: a plain request runs and finishes on a slot
    and keeps no counts there, so whatever the slot's row held stays (here:
    fifty on every token the next answer holds, put there by hand), and a
    request with a frequency
    penalty admitted to that slot next produces exactly what it produces
    alone on a fresh engine: its admission resets the slot's counts, a
    preemption and resume in its middle rebuilds them from the replayed
    output."""
    pen = SamplingParams(max_tokens=14, frequency_penalty=0.8, **GREEDY)
    plain = SamplingParams(max_tokens=10, **GREEDY)
    prompt = [3, 17, 9, 5]
    alone = make_engine(async_scheduling=async_sched).generate(prompt, pen)
    assert alone != make_engine(async_scheduling=async_sched).generate(
        prompt, SamplingParams(max_tokens=14, **GREEDY))

    kw = dict(num_pages=7, pages_per_slot=8, max_decode_slots=2) if preempt \
        else dict(max_decode_slots=1)
    eng = make_engine(async_scheduling=async_sched, **kw)
    for s in range(eng.config.max_decode_slots):
        _finish(eng, [eng.submit([30 + s, 2, 8], plain)])
    assert not np.asarray(eng.token_counts).any()   # plain: nothing counted
    eng.token_counts = eng.token_counts.at[:, jnp.asarray(alone)].add(50)

    before = eng.preemptions
    first = eng.submit([40, 2, 8], SamplingParams(max_tokens=14, **GREEDY))
    req = eng.submit(prompt, pen)       # the younger: the one preempted
    _finish(eng, [first, req])
    assert req.output == alone
    if preempt:
        assert eng.preemptions > before
    assert eng.decode_windows["plain"] > 0 < eng.decode_windows["shaped"]


def _packed_rows(rows):
    """A decode window's packed rows [len(rows), _DEC_COLS + 1] from
    ``(length, presence, frequency, first bias id)`` a row."""
    from llms_on_kubernetes_tpu.engine import engine as E

    packed = np.zeros((len(rows), E._DEC_COLS + 1), np.int32)
    packed[:, E._BIAS_DEC:E._BIAS_DEC + E.LOGIT_BIAS_SLOTS] = -1
    for i, (length, presence, frequency, bias_id) in enumerate(rows):
        packed[i, 0] = length
        packed[i, 8] = np.float32(presence).view(np.int32)
        packed[i, 9] = np.float32(frequency).view(np.int32)
        packed[i, E._BIAS_DEC] = bias_id
    return packed


IDLE, LIVE = 0, 9
WINDOWS = {
    # name: (rows, penalised rows, shaped)
    "all_plain": ([(LIVE, 0.0, 0.0, -1), (LIVE, 0.0, 0.0, -1)], [], False),
    "presence": ([(LIVE, 0.0, 0.0, -1), (LIVE, 0.5, 0.0, -1)], [1], True),
    "frequency_negative": ([(LIVE, 0.0, -0.25, -1)], [0], True),
    "bias_only": ([(LIVE, 0.0, 0.0, -1), (LIVE, 0.0, 0.0, 7)], [], True),
    "bias_id_zero": ([(LIVE, 0.0, 0.0, 0)], [], True),
    "idle_row_with_penalty": ([(IDLE, 1.0, 1.0, -1), (LIVE, 0.0, 0.0, -1)],
                              [], False),
    "idle_row_with_bias": ([(IDLE, 0.0, 0.0, 3), (LIVE, 0.0, 0.0, -1)],
                           [], False),
    "minus_zero_penalty": ([(LIVE, -0.0, -0.0, -1)], [], False),
    "denormal_penalty": ([(LIVE, 1e-45, 0.0, -1)], [0], True),
    "nan_penalty": ([(LIVE, float("nan"), 0.0, -1)], [0], True),
    "no_live_row": ([(IDLE, 0.0, 0.0, -1)], [], False),
}


@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_host_booking_agrees_with_the_device_predicate(name):
    """``_window_asks`` on the host's numpy rows (what
    ``llm_decode_windows_total{sampler}`` books) and jitted on the same rows
    as the executable sees them give the same answer: idle rows do not
    count, ``-0.0`` is no penalty on either side, a denormal is one on
    both (bits, so no flush-to-zero rule can part them)."""
    from llms_on_kubernetes_tpu.engine import engine as E

    rows, penalised, shaped = WINDOWS[name]
    packed = _packed_rows(rows)
    host_pen, host_shaped = E._window_asks(packed)
    dev_pen, dev_shaped = jax.jit(E._window_asks)(jnp.asarray(packed))
    assert np.flatnonzero(host_pen).tolist() == penalised
    assert bool(host_shaped) is shaped
    np.testing.assert_array_equal(np.asarray(dev_pen), host_pen)
    assert bool(dev_shaped) is shaped

    eng = Engine.__new__(Engine)        # the booking alone
    eng.decode_windows = {"plain": 0, "shaped": 0}
    assert eng._book_sampler(packed) == ("shaped" if shaped else "plain")
    assert eng.decode_windows == {"plain": int(not shaped),
                                  "shaped": int(shaped)}


def test_windows_by_sampler_on_the_metrics_page_and_the_dispatch_records():
    """``llm_decode_windows_total{sampler}`` exists with both children, and
    a window's record in ``GET /debug/engine`` says which it was."""
    from llms_on_kubernetes_tpu.server import metrics

    text = metrics.Registry()
    m = metrics.engine_metrics(text)
    assert m["decode_windows"].name == "llm_decode_windows_total"
    page = text.render()
    for sampler in ("plain", "shaped"):
        assert f'llm_decode_windows_total{{sampler="{sampler}"}} 0' in page

    eng = make_engine()
    _finish(eng, [eng.submit([1, 2, 3], SamplingParams(max_tokens=5, **GREEDY))])
    _finish(eng, [eng.submit([1, 2, 3], SamplingParams(
        max_tokens=5, logit_bias=((4, 2.0),), **GREEDY))])
    records = [d for d in eng.ledger.dispatches_view(64)
               if d["kind"] == "decode"]
    assert {d["sampler"] for d in records} == {"plain", "shaped"}
    assert sum(d["sampler"] == "shaped" for d in records) == \
        eng.decode_windows["shaped"] > 0
    assert not any("sampler" in d for d in eng.ledger.dispatches_view(64)
                   if d["kind"] != "decode")
