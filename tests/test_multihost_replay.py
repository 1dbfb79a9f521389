"""engine/multihost.py in one process: what a coordinator broadcasts is
recorded and replayed into ``follower_loop`` on a second engine of the
same seed. The follower must arrive at the coordinator's device state (KV
pools, penalty counts, grammar state) through the same executables: it
enters the engine's one decode step with K from the control word and
passes its own newest decode and prefill outputs, as the coordinator does.

A two-process run (tests/test_multihost_e2e.py, ``slow``: this jaxlib
has no multi-process CPU collectives) is what proves the collectives
match; this pins the call sequence and the arguments.
"""

import jax
import numpy as np
import pytest

from llms_on_kubernetes_tpu.configs import ModelConfig
from llms_on_kubernetes_tpu.engine import multihost as mh
from llms_on_kubernetes_tpu.engine.engine import (
    Engine, EngineConfig, SamplingParams,
)
from llms_on_kubernetes_tpu.engine.grammar import (
    compile_response_format, token_bytes_of,
)
from llms_on_kubernetes_tpu.engine.tokenizer import ByteTokenizer

MODEL = ModelConfig(
    "debug-grammar", vocab_size=258, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512)


KW = dict(model="debug-tiny", dtype="float32", max_decode_slots=4,
          page_size=4, num_pages=256, pages_per_slot=32,
          prefill_buckets=(16, 32), async_depth=2)


def _mk(async_scheduling, decode_steps=None):
    cfg = EngineConfig(multihost=True, async_scheduling=async_scheduling,
                       **KW)
    assert cfg.decode_steps == 1            # the clamp stands
    if decode_steps is not None:
        # past the clamp, as the two-process run that lifts it would be
        # (ROADMAP D6): nothing a deployment can configure
        cfg.decode_steps = decode_steps
    return Engine(cfg, model_config=MODEL)


def _drive(eng):
    """A few requests over every message kind but the multimodal one:
    a batched prefill, a lone one, a chunked prompt, a grammar row, and
    an answer of one token (an admission with no decode launch)."""
    eos = ByteTokenizer.EOS
    g = compile_response_format({"type": "json_object"},
                                token_bytes_of(ByteTokenizer()), [eos])
    waves = [
        [([1, 2, 3], dict(max_tokens=9)), ([4, 5, 6, 7, 8], dict(max_tokens=7)),
         ([9, 10], dict(max_tokens=5, temperature=0.8, seed=3,
                        presence_penalty=0.5))],
        [([5, 6, 7], dict(max_tokens=1))],
        [(list(range(1, 41)), dict(max_tokens=4))],
        [([1, 2, 3], dict(max_tokens=12, temperature=1.0, seed=7,
                          stop_token_ids=(eos,), grammar=g)),
         ([11, 12, 13, 14], dict(max_tokens=6))],
    ]
    outputs = []
    for wave in waves:
        reqs = [eng.submit(p, SamplingParams(**{"temperature": 0.0, **kw}))
                for p, kw in wave]
        steps = 0
        while any(not r.finished for r in reqs):
            eng.step()
            steps += 1
            assert steps < 10_000
        outputs += [(r.output, r.finish_reason) for r in reqs]
    if eng._harvester is not None:
        eng._drain_async()
    eng.stop_followers()
    return outputs


def _leaves(eng):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (eng.k_pages, eng.v_pages, eng.token_counts, eng._fsm_state))]


@pytest.mark.parametrize("async_scheduling,decode_steps", [
    (False, None), (True, None), (True, 4)])
def test_follower_replays_the_coordinator(monkeypatch, async_scheduling,
                                          decode_steps):
    sent = []
    monkeypatch.setattr(mh, "_broadcast", lambda v: (sent.append(v), v)[1])
    coord = _mk(async_scheduling, decode_steps)
    outputs = _drive(coord)
    k = coord.config.decode_steps if async_scheduling else 1

    ctrls = [m["ctrl"] for m in sent if isinstance(m, dict) and "ctrl" in m]
    assert all(c.shape == (mh.CTRL_LEN,) for c in ctrls)
    ops = [int(c[0]) for c in ctrls]
    assert {mh.MSG_PREFILL, mh.MSG_CHUNK, mh.MSG_DECODE, mh.MSG_GRAMMAR,
            mh.MSG_SHUTDOWN} == set(ops)
    # a decode message carries the window K where a prefill's carries its
    # rows; nothing else of the call rides the control word
    assert {int(c[1]) for c in ctrls if int(c[0]) == mh.MSG_DECODE} == {k}
    assert len(outputs) == 7 and outputs[3] == (outputs[3][0][:1], "length")

    traced = coord._decode_multi._cache_size()
    replay = iter(sent)
    monkeypatch.setattr(mh, "_broadcast", lambda v: next(replay))
    follower = _mk(async_scheduling, decode_steps)
    mh.follower_loop(follower)
    assert next(replay, None) is None       # every message was taken

    for a, b in zip(_leaves(coord), _leaves(follower), strict=True):
        np.testing.assert_array_equal(a, b)
    # the same executables: the follower traced no variant of the decode
    # step that the coordinator had not (jit caches by function, so the
    # two engines of this process share the count)
    assert follower._decode_multi._cache_size() == traced
    for a, b in ((coord._unread_toks, follower._unread_toks),
                 (coord._unread_prefill_toks, follower._unread_prefill_toks)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and what the coordinator served is the single-host engine's
    monkeypatch.setattr(mh, "_broadcast", lambda v: pytest.fail("broadcast"))
    ref = Engine(EngineConfig(async_scheduling=async_scheduling,
                              decode_steps=k, **KW), model_config=MODEL)
    assert _drive(ref) == outputs
