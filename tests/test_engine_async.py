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
    """A device_get failure in the harvester's reader (an error surfacing
    mid-read) must raise on the engine thread — round 4: the silent-reader-death
    mode deadlocked the bench (every wait_done blocked forever)."""
    import pytest

    from llms_on_kubernetes_tpu.engine.engine import _Harvester

    class Boom(RuntimeError):
        pass

    class BadArray:
        def copy_to_host_async(self):
            pass

        def block_until_ready(self):
            return self

        def __getattr__(self, name):  # tokens/logprobs/... leaves
            return self

    h = _Harvester()

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


# ---------------------------------------------------------------------------
# the hand-over of a first token (PR 27): the backpressure wait wakes for
# a landed first token, and first tokens are published one by one
# ---------------------------------------------------------------------------

import threading
import time


class _Held:
    """A device result that is complete on the device only once its gate
    is set and ``delay`` seconds have passed (the harvester waits on
    ``block_until_ready``)."""

    def __init__(self, res, gate=None, delay=0.0):
        self.res, self.gate, self.delay = res, gate, delay

    def copy_to_host_async(self):
        self.res.copy_to_host_async()

    def block_until_ready(self):
        assert self.gate is None or self.gate.wait(60.0)
        time.sleep(self.delay)
        self.res.block_until_ready()
        return self

    def __array__(self, *a, **kw):
        return np.asarray(self.res)


class _Gates:
    """Wraps every result the engine pushes in a ``_Held``; ``hold(key)``
    decides at push whether its gate starts closed."""

    def __init__(self, eng, hold):
        self.hold = hold
        self.gates: dict[int, threading.Event] = {}
        self.order: list[int] = []
        real = eng._harvester.push

        def push(key, res):
            gate = self.gates[key] = threading.Event()
            if not self.hold(key):
                gate.set()
            self.order.append(key)
            real(key, _Held(res, gate))

        eng._harvester.push = push

    def decodes(self):
        return [k for k in self.order if k >= 0]

    def prefills(self):
        return [k for k in self.order if k < 0]

    def release_all(self):
        self.hold = lambda key: False
        for gate in list(self.gates.values()):
            gate.set()


class _Loop(threading.Thread):
    """The serving loop's part: step() while there is work, keeping every
    returned event."""

    def __init__(self, eng):
        super().__init__(daemon=True)
        self.eng, self.steps, self.returned = eng, 0, []
        self.error = None
        self._halt = threading.Event()

    def run(self):
        try:
            while not self._halt.is_set():
                if not self.eng.has_work():
                    time.sleep(0.001)
                    continue
                self.returned += self.eng.step()
                self.steps += 1
        except BaseException as e:  # noqa: BLE001 — the test re-raises it
            self.error = e

    def halt(self):
        self._halt.set()
        self.join(30.0)
        assert not self.is_alive()
        if self.error is not None:
            raise self.error


def _until(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.002)


def _settled(loop, gates, for_s=0.1):
    """The engine thread sits in a wait: no step() returned and nothing
    was pushed for ``for_s`` seconds."""
    while True:
        seen = (loop.steps, len(gates.order))
        time.sleep(for_s)
        if seen == (loop.steps, len(gates.order)):
            return seen


def _tokens(payloads):
    return [t for toks, _fin, _why in payloads for t in toks]


def _drain(q):
    """Everything on a request's queue (the engine only ever puts)."""
    got = []
    while not q.empty():
        got.append(q.get_nowait())
    return got


def test_first_token_leaves_from_inside_the_backpressure_wait():
    """Device order: ..., prefill P, decode D. The engine thread waits for
    D at full depth; P's read lands meanwhile. The request's queue holds
    its first token BEFORE D is done, and the thread never left its wait:
    no step() returned and nothing was launched."""
    greedy = SamplingParams(temperature=0.0, max_tokens=24)
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3], [9, 10]],
                     max_tokens=24)
    eng = _mk(True, depth=2, decode_steps=4)
    held_prefill = []
    gates = _Gates(eng, lambda key: key >= 0 or bool(held_prefill))
    loop = _Loop(eng)
    seen_b = []
    a = eng.submit([1, 2, 3], greedy)
    loop.start()
    try:
        # A's prefill lands at once; two decode windows held in flight
        _until(lambda: len(gates.decodes()) == 2)
        _until(lambda: not a.events.empty())
        assert _tokens(_drain(a.events)) == a.output[:1] != []
        # B arrives: its prefill P and the window D launched behind it
        held_prefill.append(True)
        b = eng.submit([9, 10], greedy, on_event=seen_b.append)
        _until(lambda: len(gates.decodes()) == 3)
        p_key = gates.prefills()[-1]
        d0, d1, d2 = gates.decodes()
        # the two windows ahead of P complete; the engine launches one
        # more and waits for D at full depth
        gates.gates[d0].set()
        gates.gates[d1].set()
        _until(lambda: len(gates.decodes()) == 4)
        steps, pushed = _settled(loop, gates)
        assert b.events.empty() and b.first_token_at is None
        before = dict(eng.first_tokens_handed)
        gates.gates[p_key].set()                # P's read lands
        _until(lambda: not b.events.empty())
        time.sleep(0.1)
        assert not eng._harvester.is_done(d2)   # D is still on the device
        assert (loop.steps, len(gates.order)) == (steps, pushed)
        assert b.first_token_at is not None
        assert eng.first_tokens_handed["backpressure"] == (
            before["backpressure"] + 1)
        assert eng.first_tokens_handed["step"] == before["step"]
        assert _tokens(seen_b) == b.output[:1] != []
        gates.release_all()
        _until(lambda: a.finished and b.finished)
    finally:
        gates.release_all()
        loop.halt()
    # through the woken path: the synchronous engine's tokens
    assert [a.output, b.output] == [r.output for r in ref]


def test_first_tokens_are_published_one_by_one():
    """Two first-token results pushed together, the second held back on
    the device: the first is done while the second is not."""
    from llms_on_kubernetes_tpu.engine.engine import _Harvester

    class _Res:
        def __init__(self, gate=None):
            self.gate = gate

        def copy_to_host_async(self):
            pass

        def block_until_ready(self):
            if self.gate is not None:
                assert self.gate.wait(30.0)
            return self

        def __array__(self, *a, **kw):
            return np.zeros((1,), np.int32)

    hv = _Harvester()
    gate = threading.Event()
    hv.start()
    try:
        hv.push(-1, _Res())
        hv.push(-2, _Res(gate))
        hv.push(0, _Res())          # a decode step launched behind both
        hv.wait_key(-1, timeout_s=10.0)
        time.sleep(0.05)
        assert hv.key_done(-1) and not hv.key_done(-2)
        assert not hv.is_done(0)
        # the backpressure wait wakes for the landed key, not for the step
        hv.wait_done(0, keys=(-2, -1), timeout_s=10.0)
        assert not hv.is_done(0)
        gate.set()
        hv.wait_done(0, keys=(-2,), timeout_s=10.0)
        assert hv.key_done(-2)      # launch order: never after the step
        hv.wait_done(0, timeout_s=10.0)
        assert hv.done_time(-1) <= hv.done_time(-2) <= hv.done_time(0)
    finally:
        gate.set()
        hv.stop()


def test_decode_step_is_not_consumed_before_its_first_token():
    """The head-blocking rule stands: a completed decode step whose
    request's first token has not been collected stays in flight."""
    eng = _mk(True, depth=2, decode_steps=4)
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_tokens=12))
    eng._admit_wake.clear()
    admitted = eng._admit_async([])
    assert eng._launch_decode_async(admitted, []) == "launched"
    (_r, key, _row), = eng._pending_first
    step = eng._inflight[0]
    eng._harvester.wait_done(step.seq, timeout_s=30.0)
    assert eng._harvester.key_done(key)
    real = eng._harvester.key_done
    eng._harvester.key_done = lambda k: False       # ...not seen yet
    events = []
    assert eng._collect_ready(events) == 0
    assert eng._head_blocking_first() == key
    assert not events and list(eng._inflight) == [step] and not req.output
    eng._harvester.key_done = real
    assert eng._collect_ready(events) == 1
    assert events[0].first and not any(ev.first for ev in events[1:])
    assert _tokens([(ev.new_tokens, 0, 0) for ev in events]) == req.output
    assert len(req.output) == 5 and not eng._inflight
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3]], max_tokens=12)
    assert req.output == ref[0].output[:5]


def _slow_decodes(eng, delay_s):
    """Every decode window takes ``delay_s`` on the device: the engine
    thread spends its time in the backpressure wait."""
    real = eng._harvester.push

    def push(key, res):
        real(key, _Held(res, delay=delay_s) if key >= 0 else res)

    eng._harvester.push = push


@pytest.mark.parametrize("depth,decode_steps", [(1, 1), (2, 4), (3, 4)])
def test_bursts_through_the_woken_path_match_sync(depth, decode_steps):
    """Bursts of admissions into an engine whose thread sits in the
    backpressure wait: greedy outputs are the synchronous engine's, every
    event is returned by step() once and seen by on_event once, in order
    (which first tokens leave from inside the wait is the scheduler's
    timing: test_first_token_leaves_from_inside_the_backpressure_wait
    pins one)."""
    ref = _run_batch(_mk(False, decode_steps=decode_steps), PROMPTS)
    eng = _mk(True, depth=depth, decode_steps=decode_steps)
    _slow_decodes(eng, 0.03)
    loop = _Loop(eng)
    loop.start()
    seen: dict[int, list] = {i: [] for i in range(len(PROMPTS))}
    reqs = []
    try:
        for i, p in enumerate(PROMPTS):
            reqs.append(eng.submit(
                p, SamplingParams(temperature=0.0, max_tokens=12),
                on_event=seen[i].append))
            if i % 2:
                time.sleep(0.035)       # the next burst lands mid-wait
        _until(lambda: all(r.finished for r in reqs))
    finally:
        loop.halt()
    for i, (r, s) in enumerate(zip(reqs, ref)):
        assert r.output == s.output and r.finish_reason == s.finish_reason
        assert _tokens(seen[i]) == r.output
        assert _tokens(_drain(r.events)) == r.output
        mine = [(ev.new_tokens, ev.finished, ev.finish_reason)
                for ev in loop.returned if ev.request is r]
        assert mine == seen[i]
        assert r.first_token_at is not None
    assert len({id(ev) for ev in loop.returned}) == len(loop.returned)
    assert all(ev.handed_over for ev in loop.returned)
    handed = eng.first_tokens_handed
    assert handed["backpressure"] + handed["step"] == len(PROMPTS)


def test_decode_launch_with_nothing_in_flight_traces_nothing_new():
    """A fused decode launch with nothing in flight and no admission passes
    stand-ins for the two token inputs no row reads. They are the newest
    real ones, so the step is not traced again for them: with the zeros
    (other sharding annotations) it was, under whichever request first
    found the pipeline empty."""
    eng = _mk(True, depth=2, decode_steps=4)
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_tokens=40))
    for _ in range(3):
        eng.step()
    traced = eng._decode_multi._cache_size()
    for _ in range(3):
        eng._drain_async()          # the pipeline runs dry...
        assert not eng._inflight and not req.finished
        eng.step()                  # ...and the next launch reads host values
    assert eng._decode_multi._cache_size() == traced
    while not req.finished:
        eng.step()
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3]], max_tokens=40)
    assert req.output == ref[0].output
