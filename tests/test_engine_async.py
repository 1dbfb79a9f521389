"""Async (pipelined) scheduling must be observably identical to sync
scheduling: same greedy tokens, same finish reasons, same preemption
recovery — only the host/device overlap differs (engine.py async_*).
"""

import numpy as np
import pytest

from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig, SamplingParams


def _mk(async_scheduling, depth=2, **kw):
    base = dict(
        model="debug-tiny", dtype="float32", max_decode_slots=4,
        page_size=8, num_pages=64, pages_per_slot=8,
        prefill_buckets=(16, 32), async_scheduling=async_scheduling,
        async_depth=depth,
    )
    base.update(kw)
    return Engine(EngineConfig(**base))


def _run_batch(eng, prompts, max_tokens=12, stop=()):
    reqs = [eng.submit(p, SamplingParams(temperature=0.0, max_tokens=max_tokens,
                                         stop_token_ids=stop))
            for p in prompts]
    steps = 0
    while any(not r.finished for r in reqs):
        eng.step()
        steps += 1
        assert steps < 10_000
    return reqs


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10], [11, 12, 13, 14],
           [2, 4, 6, 8, 10, 12], [3, 1, 4, 1, 5]]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_async_matches_sync_greedy(depth):
    sync = _run_batch(_mk(False), PROMPTS)
    asyn = _run_batch(_mk(True, depth=depth), PROMPTS)
    for s, a in zip(sync, asyn):
        assert a.output == s.output, (a.output, s.output)
        assert a.finish_reason == s.finish_reason


def test_async_matches_sync_with_stop_tokens():
    # pick the stop token from a sync run's outputs so it actually triggers
    probe = _run_batch(_mk(False), PROMPTS, max_tokens=12)
    stop_tok = probe[0].output[3]
    sync = _run_batch(_mk(False), PROMPTS, stop=(stop_tok,))
    asyn = _run_batch(_mk(True), PROMPTS, stop=(stop_tok,))
    for s, a in zip(sync, asyn):
        assert a.output == s.output
        assert a.finish_reason == s.finish_reason


def test_async_preemption_recovers_and_matches():
    # tiny page pool: 4 slots x 8 pages needed but only 12 pages available.
    # max_tokens kept small enough that a preempted request's re-prefill
    # (prompt + generated so far) always fits the largest bucket, so greedy
    # outputs are identical regardless of WHEN each engine preempts.
    kw = dict(num_pages=11)
    sync_eng = _mk(False, **kw)
    async_eng = _mk(True, **kw)
    long = SamplingParams(temperature=0.0, max_tokens=20)
    sync = [sync_eng.submit([1, 2, 3], long) for _ in range(4)]
    asyn = [async_eng.submit([1, 2, 3], long) for _ in range(4)]
    for eng, reqs in ((sync_eng, sync), (async_eng, asyn)):
        steps = 0
        while any(not r.finished for r in reqs):
            eng.step()
            steps += 1
            assert steps < 10_000
    assert async_eng.preemptions > 0  # the pool really was oversubscribed
    for s, a in zip(sync, asyn):
        assert a.output == s.output
        assert a.finish_reason == s.finish_reason


def test_async_abort_mid_stream():
    eng = _mk(True)
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=200))
    other = eng.submit([4, 5], SamplingParams(temperature=0.0, max_tokens=10))
    for _ in range(3):
        eng.step()
    eng.abort(req, "client_disconnect")
    steps = 0
    while not (req.finished and other.finished):
        eng.step()
        steps += 1
        assert steps < 1_000
    assert req.finish_reason == "client_disconnect"
    assert other.finish_reason == "length"
    assert len(other.output) == 10


def test_async_continuous_admission():
    """Requests submitted while others are mid-decode join the batch and
    produce the same outputs as a fresh sync engine would."""
    eng = _mk(True)
    first = eng.submit([1, 2, 3], SamplingParams(temperature=0.0, max_tokens=15))
    for _ in range(4):
        eng.step()
    second = eng.submit([9, 10], SamplingParams(temperature=0.0, max_tokens=15))
    steps = 0
    while not (first.finished and second.finished):
        eng.step()
        steps += 1
        assert steps < 1_000

    ref = _run_batch(_mk(False), [[1, 2, 3], [9, 10]], max_tokens=15)
    assert first.output == ref[0].output
    assert second.output == ref[1].output


def test_async_single_request_generate():
    out_sync = _mk(False).generate([5, 6, 7], SamplingParams(temperature=0.0,
                                                             max_tokens=10))
    out_async = _mk(True).generate([5, 6, 7], SamplingParams(temperature=0.0,
                                                             max_tokens=10))
    assert out_async == out_sync


def test_harvester_read_failure_surfaces_on_engine_thread():
    """A device_get failure in a harvester reader (an error surfacing
    mid-read) must raise on the engine thread — round 4: the silent-reader-death
    mode deadlocked the bench (every wait_done blocked forever)."""
    import pytest

    from llms_on_kubernetes_tpu.engine.engine import _Harvester

    class Boom(RuntimeError):
        pass

    class BadArray:
        def copy_to_host_async(self):
            pass

        def __getattr__(self, name):  # tokens/logprobs/... leaves
            return self

    h = _Harvester(readers=1, batch=1)

    def failing_get(_):
        raise Boom("INTERNAL: read body: response body closed")

    import jax

    orig = jax.device_get
    jax.device_get = failing_get
    try:
        h.start()
        h.push(0, BadArray())
        with pytest.raises(Boom):
            h.wait_done(0)
        # every later query keeps raising (no silent hang)
        with pytest.raises(Boom):
            h.is_done(0)
        with pytest.raises(Boom):
            h.wait_key(-1)
    finally:
        jax.device_get = orig
        h.stop()
