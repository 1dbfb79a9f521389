"""A prompt past the largest bucket is a chain of chunks. The pipelined
scheduler launches ONE chunk a ``step()``: a decode window runs behind
every chunk, and a bucket's waiting prompts get every other turn, so a
stream waits behind a chunk and never behind a chain."""

import pytest

from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams)


def _engine(**kw):
    base = dict(model="debug-tiny", dtype="float32", max_decode_slots=4,
                page_size=4, num_pages=512, pages_per_slot=64,
                prefill_buckets=(16, 32), async_scheduling=True,
                async_depth=2, decode_steps=4, ledger=True)
    base.update(kw)
    return Engine(EngineConfig(**base))


def _greedy(n):
    return SamplingParams(temperature=0.0, max_tokens=n)


LONG = [3 + (7 * i) % 200 for i in range(120)]      # four chunks of 32
SHORT = [9, 8, 7, 6, 5]


def _run(eng, reqs, limit=4000):
    for _ in range(limit):
        if all(r.finished for r in reqs):
            break
        eng.step()
    eng._drain_async()
    assert all(r.finished for r in reqs)


def _kinds(eng):
    """(kind, shape) of the booked dispatches, in launch order."""
    recs = sorted(eng.ledger.dispatches_view(4096), key=lambda d: d["seq"])
    return [(d["kind"], d["shape"]) for d in recs]


def test_a_decode_window_runs_behind_every_chunk():
    eng = _engine()
    stream = eng.submit(SHORT, _greedy(64))
    while len(stream.output) < 4:
        eng.step()
    long = eng.submit(LONG, _greedy(6))
    _run(eng, [stream, long])
    kinds = _kinds(eng)
    chunks = [i for i, (k, _s) in enumerate(kinds) if k == "chunk"]
    assert [kinds[i][1] for i in chunks] == ["1x32"] * 3 + ["1x32"]
    assert len(chunks) == 4
    for a, b in zip(chunks, chunks[1:]):
        between = [k for k, _s in kinds[a + 1:b]]
        assert "decode" in between, kinds
    # the stream rode the windows between the chunks: it got tokens
    # while the long prompt was still being written
    assert eng.path_tokens["chunk"] == len(LONG)


@pytest.mark.parametrize("mode", ["interleaved", "sync"])
def test_the_tokens_are_those_of_an_engine_that_runs_nothing_between(mode):
    """Greedy outputs of the long prompt, of the stream decoding while it
    is written and of a short prompt let through between its chunks are
    what each gives alone."""
    alone = {}
    for name, prompt, n in (("long", LONG, 12), ("stream", SHORT, 40),
                            ("short", SHORT[::-1], 10)):
        eng = _engine(async_scheduling=False, decode_steps=1)
        req = eng.submit(prompt, _greedy(n))
        _run(eng, [req])
        alone[name] = list(req.output)
    eng = _engine(**({} if mode == "interleaved" else
                     dict(async_scheduling=False, decode_steps=1)))
    stream = eng.submit(SHORT, _greedy(40))
    while len(stream.output) < 4:
        eng.step()
    long = eng.submit(LONG, _greedy(12))
    eng.step()                          # the chain's first chunk
    short = eng.submit(SHORT[::-1], _greedy(10))
    _run(eng, [stream, long, short])
    assert list(long.output) == alone["long"]
    assert list(stream.output) == alone["stream"]
    assert list(short.output) == alone["short"]


def test_a_waiting_bucket_prompt_goes_between_two_chunks():
    eng = _engine()
    long = eng.submit(LONG, _greedy(4))
    eng.step()                          # chunk 1 of 4
    assert eng._chain is not None and eng._chain.pos == 32
    short = eng.submit(SHORT, _greedy(4))
    _run(eng, [long, short])
    kinds = [k for k, _s in _kinds(eng)]
    first_prefill = kinds.index("prefill")
    chunk_at = [i for i, k in enumerate(kinds) if k == "chunk"]
    assert chunk_at[0] < first_prefill < chunk_at[-1], kinds
    # its first token left before the long prompt's did
    assert short.first_token_at < long.first_token_at


def test_a_second_long_prompt_waits_for_the_chain_under_way():
    eng = _engine()
    a = eng.submit(LONG, _greedy(4))
    b = eng.submit(LONG[::-1], _greedy(4))
    eng.step()
    assert eng._chain.req is a and b.slot < 0
    _run(eng, [a, b])
    assert a.first_token_at < b.first_token_at
    assert eng.path_tokens["chunk"] == 2 * len(LONG)


@pytest.mark.parametrize("how", ["abort", "preempt"])
def test_a_chain_cut_part_way_leaves_nothing_behind(how):
    eng = _engine()
    stream = eng.submit(SHORT, _greedy(24))
    while len(stream.output) < 4:
        eng.step()
    long = eng.submit(LONG, _greedy(6))
    eng.step()
    assert eng._chain is not None and eng._chain.req is long
    if how == "abort":
        eng.abort(long)
    else:
        eng._drain_async()
        assert not long.finished
        eng._preempt_youngest()
        assert long.slot < 0
    _run(eng, [stream, long])
    assert eng._chain is None and not eng._pending_first
    if how == "abort":
        assert long.finish_reason == "abort" and not long.output
    else:
        # re-admitted and prefilled again from its first chunk
        ref = _engine(async_scheduling=False, decode_steps=1)
        alone = ref.submit(LONG, _greedy(6))
        _run(ref, [alone])
        assert list(long.output) == list(alone.output)
    assert all(s is None for s in eng.slots)


def test_the_prefill_span_ends_at_the_last_chunks_read():
    eng = _engine()
    long = eng.submit(LONG, _greedy(4))
    _run(eng, [long])
    assert (long.prefill_launched_at <= long.prefill_started_at
            < long.prefill_read_at <= long.first_token_at)
    recs = [d for d in eng.ledger.dispatches_view(4096)
            if d["kind"] == "chunk"]
    assert len(recs) == 4 and all(d["device_ms"] > 0.0 for d in recs)
    # no chunk's segment was closed by a later dispatch's read
    assert not any(d.get("end_clamped") for d in recs)
