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


def _decode_steps_of(eng):
    """The engine's jitted decode steps: the plain one and the verify."""
    return sorted(n for n in vars(eng) if n.startswith("_decode_"))


@pytest.mark.parametrize("k", [1, 4])
def test_every_decode_dispatch_is_the_one_step(k):
    """K is a number, not a path: at decode_steps 1 and 4 alike every
    decode dispatch the ledger books (``dispatches`` of GET /debug/engine)
    is ``_decode_multi_packed_step`` at a shape ``Kx<rows>``, launched by
    the one window launcher; the engine holds no other plain decode step,
    and the stream is the synchronous reference's."""
    eng = _mk(True, depth=2, decode_steps=k)
    reqs = _run_batch(eng, PROMPTS[:4], max_tokens=9)
    eng._drain_async()
    decodes = [d for d in eng.ledger.dispatches_view()
               if d["kind"] == "decode"]
    assert decodes
    for d in decodes:
        assert d["name"] == "_decode_multi_packed_step"
        assert d["shape"].startswith(f"{k}x")
    assert _decode_steps_of(eng) == ["_decode_multi", "_decode_spec"]
    assert sum(eng.decode_launches.values()) == len(decodes)
    ref_eng = _mk(False, decode_steps=k)
    ref = _run_batch(ref_eng, PROMPTS[:4], max_tokens=9)
    for r, s in zip(reqs, ref):
        assert r.output == s.output and r.finish_reason == s.finish_reason
    # the reference loop enters the same step, a window of one at a time
    assert {(d["name"], d["shape"][:2])
            for d in ref_eng.ledger.dispatches_view()
            if d["kind"] == "decode"} == {("_decode_multi_packed_step", "1x")}


@pytest.mark.parametrize("k", [1, 4])
def test_a_row_whose_budget_is_in_flight_rides_masked(k):
    """The window planner's rule holds at every K: once what is in flight
    covers a request's max_tokens, no further token is planned for it (the
    launch reports "paced" where every row is covered), so no dispatch's
    tokens are thrown away for want of budget."""
    eng = _mk(True, depth=3, decode_steps=k)
    reqs = _run_batch(eng, PROMPTS[:2], max_tokens=6)
    eng._drain_async()
    assert [len(r.output) for r in reqs] == [6, 6]
    assert all(r.finish_reason == "length" for r in reqs)
    assert eng.early_exit_steps == 0
    # max_tokens = 1: the first token is the whole answer and the
    # admission's launch has nothing to plan
    one = eng.submit([5, 6, 7], SamplingParams(temperature=0.0,
                                               max_tokens=1))
    eng._admit_wake.clear()
    admitted = eng._admit_async([])
    assert eng._launch_decode_async(admitted, []) == "paced"
    while not one.finished:
        eng.step()
    assert len(one.output) == 1 and not eng._inflight


# ---------------------------------------------------------------------------
# when a steady-state decode window is launched (PR 31): a lead before the
# device is estimated to run dry, not as soon as the pipeline has room.
# A simulated device on an injected clock: no thread, no sleeping
# ---------------------------------------------------------------------------


class _SimClock:
    """The engine's clock. It moves when a wait is waited out and, by
    ``tick`` a reading, while the host works."""

    def __init__(self, tick=0.0):
        self.t, self.tick = 1000.0, tick

    def __call__(self):
        self.t += self.tick
        return self.t


class _SimDevice:
    """Stands where the engine's ``_Harvester`` does: a device that runs
    what is pushed in launch order, each result taking ``cost(key)``
    seconds of the test's clock, and stamps each completion on it. The
    results themselves are the real ones (the CPU's). A wait moves the
    clock to whatever ends it first; ``at(t, fn)`` lets something happen
    at a moment inside one (a submission)."""

    device_time_s = 0.0

    def __init__(self, eng, cost, tick=0.0, estimates=()):
        self.eng, self.cost = eng, cost
        self.clock = _SimClock(tick)
        self.free = self.clock.t
        self.ends: dict[int, float] = {}
        self.pushed: list[tuple[int, float]] = []       # (key, when)
        self._res: dict[int, object] = {}
        self._calls: list[tuple[float, object]] = []
        eng._harvester.stop()
        eng._harvester, eng._clock = self, self.clock
        # what warm-up and the first requests would have taught
        eng.timeline._est.update(estimates)

    # -- what the engine calls ------------------------------------------

    def push(self, key, res):
        now = self.clock.t
        self.free = max(self.free, now) + self.cost(key)
        self.ends[key] = self.free
        self._res[key] = res
        self.pushed.append((key, now))

    def is_done(self, seq):
        return self.ends[seq] <= self.clock.t

    key_done = is_done

    def get(self, key):
        import jax
        return jax.device_get(self._res[key])

    def done_time(self, key):
        return self.ends[key]

    def wait_done(self, seq, wake=None, keys=(), timeout_s=None, until=None):
        while not self.is_done(seq):
            if wake is not None and wake.is_set():
                return
            if any(self.is_done(k) for k in keys):
                return
            if until is not None and self.clock.t >= until:
                return
            self._advance([self.ends[seq], until]
                          + [self.ends[k] for k in keys])

    def wait_key(self, key, timeout_s=None):
        while not self.is_done(key):
            self._advance([self.ends[key]])

    def discard_upto(self, seq):
        for k in [k for k in self._res if 0 <= k <= seq]:
            del self._res[k]

    def discard_key(self, key):
        self._res.pop(key, None)

    def poke(self):
        pass

    def stop(self):
        pass

    # -- what the test calls --------------------------------------------

    def at(self, t, fn):
        self._calls.append((t, fn))
        self._calls.sort(key=lambda c: c[0])

    def _advance(self, moments):
        """To the earliest of ``moments`` still ahead, or to a scheduled
        call that comes before it (which then runs)."""
        ahead = [m for m in moments if m is not None and m > self.clock.t]
        target = min(ahead) if ahead else None
        if self._calls and (target is None or self._calls[0][0] <= target):
            t, fn = self._calls.pop(0)
            self.clock.t = max(self.clock.t, t)
            fn()
            return
        assert target is not None, "a wait that nothing ends"
        self.clock.t = target

    def drive(self, done):
        steps = 0
        while not done():
            if self.eng.has_work():
                self.eng.step()
            else:
                self._advance([])
            steps += 1
            assert steps < 5000

    def decodes(self):
        return [(k, t) for k, t in self.pushed if k >= 0]

    def ahead_at(self, t):
        """Decode windows launched before ``t`` and not complete at it."""
        return [k for k, when in self.decodes()
                if when < t and self.ends[k] > t]


WINDOW, PREFILL = 0.064, 0.026
_SEEDED = {("decode", f"4x{n}"): WINDOW for n in range(1, 5)}
_SEEDED.update({("prefill", "1x16"): PREFILL, ("prefill", "4x16"): PREFILL})


def _sim(cost=None, **kw):
    eng = _mk(True, depth=2, decode_steps=4)
    cost = cost or (lambda key: WINDOW if key >= 0 else PREFILL)
    return eng, _SimDevice(eng, cost, **kw)


_GREEDY = SamplingParams(temperature=0.0, max_tokens=40)


def test_a_steady_window_is_launched_a_lead_before_the_device_runs_dry():
    """(a) With nothing waiting, no window is launched before the end of
    the work ahead less the lead, and none after that end: the device
    never holds more than the rest of one window and never idles."""
    eng, sim = _sim(estimates=_SEEDED)
    req = eng.submit([1, 2, 3], _GREEDY)
    sim.drive(lambda: req.finished)
    launches = sim.decodes()
    assert len(launches) == 10 and eng.decode_launches == {
        "timed": 9, "late": 0, "admission": 1, "depth": 0}
    lead = eng._lead
    for (_k, when), (ahead, _w) in zip(launches[1:], launches):
        assert sim.ends[ahead] - lead - 1e-9 <= when <= sim.ends[ahead]
        assert len(sim.ahead_at(when)) == 1
    idle = eng.ledger.snapshot()["idle_host_ms"]
    assert idle["scheduling"] == 0.0 and eng.ledger.snapshot()["lost"] == 0
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3]], max_tokens=40)
    assert req.output == ref[0].output


def test_a_submission_in_the_timed_wait_finds_one_window_ahead():
    """(b) A request that arrives while the thread waits for the launch
    moment is admitted at that instant, and its prefill is enqueued with
    at most ONE decode window not yet complete ahead of it."""
    eng, sim = _sim(estimates=_SEEDED)
    a = eng.submit([1, 2, 3], _GREEDY)
    late = []
    arrives = sim.clock.t + PREFILL + 3 * WINDOW + 0.020
    sim.at(arrives, lambda: late.append(eng.submit([9, 10], _GREEDY)))
    sim.drive(lambda: a.finished and late and late[0].finished)
    (p_key, p_when), = [(k, t) for k, t in sim.pushed if k < 0][1:]
    assert p_when == arrives
    assert len(sim.ahead_at(p_when)) == 1
    # behind: the rest of that window, not a whole one and a rest
    assert sim.ends[p_key] - PREFILL - arrives < WINDOW
    assert eng.decode_launches["admission"] == 2
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3], [9, 10]],
                     max_tokens=40)
    assert [a.output, late[0].output] == [r.output for r in ref]


def test_a_first_token_in_the_timed_wait_leaves_from_inside_it():
    """(c) The timed wait is the backpressure wait: a first token whose
    read lands during it is handed over "backpressure", and the wait goes
    on to the launch moment."""
    eng, sim = _sim(estimates=_SEEDED)
    a = eng.submit([1, 2, 3], _GREEDY)
    late, seen = [], []
    arrives = sim.clock.t + PREFILL + 3 * WINDOW + 0.020
    sim.at(arrives, lambda: late.append(
        eng.submit([9, 10], _GREEDY, on_event=lambda ev: seen.append(
            (sim.clock.t, list(eng.decode_launches.values()))))))
    sim.drive(lambda: bool(late))
    before = dict(eng.first_tokens_handed)
    sim.drive(lambda: bool(seen))
    p_key = [k for k, _t in sim.pushed if k < 0][-1]
    handed_at, launches = seen[0]
    # as its read landed: the window launched behind it still runs, and
    # nothing was launched to hand it over
    assert handed_at == sim.ends[p_key]
    assert len(sim.ahead_at(handed_at)) == 1
    assert eng.first_tokens_handed["backpressure"] == (
        before["backpressure"] + 1)
    assert eng.first_tokens_handed["step"] == before["step"]
    assert launches == list(eng.decode_launches.values())
    sim.drive(lambda: a.finished and late[0].finished)
    assert eng.decode_launches["late"] == eng.decode_launches["depth"] == 0


def test_an_overshooting_estimate_books_late_and_widens_the_lead():
    """(d) The device turns out faster than the estimate: the window ahead
    completes before the launch moment, the launch comes at that
    completion ("late"), the gap is the ledger's "scheduling" idle, and
    the lead has grown by it; the estimate follows at once, so the next
    launches are timed again."""
    fast = 0.040
    eng, sim = _sim(cost=lambda key: fast if key >= 0 else PREFILL,
                    tick=0.0002, estimates=_SEEDED)
    req = eng.submit([1, 2, 3], _GREEDY)
    sim.drive(lambda: eng.decode_launches["late"] == 1)
    (first, _w), (second, when) = sim.decodes()
    assert sim.ends[first] <= when < sim.ends[first] + 0.01
    assert eng._lead > 0.004
    widened = eng._lead
    sim.drive(lambda: req.finished)
    assert eng.decode_launches["late"] == 1
    assert eng.decode_launches["timed"] == 8
    assert eng.timeline.estimate("decode", "4x1") == pytest.approx(
        fast, abs=0.002)
    assert 0.004 <= eng._lead < widened     # and falls again, slowly
    idle = eng.ledger.snapshot()["idle_host_ms"]
    gap_ms = (when - sim.ends[first]) * 1000.0
    assert idle["scheduling"] == pytest.approx(gap_ms, abs=1.0) and gap_ms > 0
    assert idle["no_work"] == idle["compile"] == 0.0


@pytest.mark.parametrize("case", ["unknown_shape", "short_window"])
def test_what_cannot_be_timed_is_launched_on_the_depth_rule(case):
    """(e) A shape that never ran, or a window no longer than two leads:
    the next window is launched as soon as the pipeline has room, two in
    flight, as before. A shape that has run once is timed from then on."""
    short = 0.006
    if case == "unknown_shape":
        eng, sim = _sim()
    else:
        eng, sim = _sim(cost=lambda key: short,
                        estimates={k: short for k in _SEEDED})
    req = eng.submit([1, 2, 3], SamplingParams(temperature=0.0,
                                               max_tokens=12))
    sim.drive(lambda: len(sim.decodes()) == 3)
    (d0, t0), (d1, t1), (_d2, t2) = sim.decodes()
    assert t1 == t0                 # behind the admission's, at once
    if case == "unknown_shape":
        # the first window's completion taught the timeline its shape
        assert eng.timeline.estimate("decode", "4x1") == pytest.approx(WINDOW)
        assert t2 == sim.ends[d1] - eng._lead
        assert eng.decode_launches == {
            "timed": 1, "late": 0, "admission": 1, "depth": 1}
    else:
        assert t2 == sim.ends[d0]   # when the first made room
        assert eng.decode_launches == {
            "timed": 0, "late": 0, "admission": 1, "depth": 2}
    sim.drive(lambda: req.finished)
    ref = _run_batch(_mk(False, decode_steps=4), [[1, 2, 3]], max_tokens=12)
    assert req.output == ref[0].output


def test_the_timeline_estimates_without_the_ledger():
    """The launch timing reads the dispatch timeline, which the engine
    keeps whether or not chip time is attributed."""
    eng = _mk(True, depth=2, decode_steps=4, ledger=False)
    assert eng.ledger is None
    sim = _SimDevice(eng, lambda key: WINDOW if key >= 0 else PREFILL)
    req = eng.submit([1, 2, 3], _GREEDY)
    sim.drive(lambda: req.finished)
    assert eng.timeline.estimate("decode", "4x1") == pytest.approx(WINDOW)
    assert eng.timeline.estimate("prefill", "1x16") == pytest.approx(PREFILL)
    assert eng.decode_launches["timed"] >= 5
    assert eng.decode_launches["late"] == 0
    assert req.chip_ms == {} and req.prefill_launched_at is None
