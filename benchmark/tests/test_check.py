"""The output check behind ``correct``: it asks for token ids by name, and
it fails a server that computes something else. Each case runs for the
dense rehearsal configuration against ``reference/forward.py`` and for the
sparse one against ``reference/moe.py``, the module its file names."""

import math
import random

import pytest

from harness import manifest, setup_steps

# configuration -> the layer matrices its lower-precision control cuts to
# 4 bits (None: all of them). The stated type of both is int8; for the
# sparse one the cut is to the experts alone, the part its block adds
CONFIGS = {"debug-tiny": None, "debug-moe": ("w_gate", "w_up", "w_down")}
GOLDENS = {c: manifest.load_json("golden", f"{c}.json") for c in CONFIGS}
GOLDEN = GOLDENS["debug-tiny"]
TOL = GOLDEN["tolerance"]["nats"]
PROMPTS = {p["name"]: p for p in GOLDEN["prompts"]}
NAMES = ("bucket32", "bucket128", "chunk")


def tol_of(config):
    return GOLDENS[config]["tolerance"]["nats"]


def prompt_of(config, name):
    return next(p for p in GOLDENS[config]["prompts"] if p["name"] == name)


def served_by(logprobs, prompt, n=8):
    """What ``probe`` would report of a server whose first-position
    log-probabilities for ``prompt`` are ``logprobs``: the bias goes in,
    the argmax's biased log-probability comes out, ``unbias`` inverts."""
    out = []
    for tid, ref in zip(prompt["top_ids"][0][:n], prompt["top_logprobs"][0][:n]):
        bias = setup_steps.probe_bias(ref)
        biased = [lp + (bias if i == tid else 0.0)
                  for i, lp in enumerate(logprobs)]
        lse = math.log(sum(math.exp(x) for x in biased))
        top = max(range(len(biased)), key=biased.__getitem__)
        out.append({"id": tid, "reference": ref,
                    "served": setup_steps.unbias(biased[top] - lse, bias),
                    "prompt_tokens": prompt["prompt_tokens"]})
    return out


@pytest.fixture(scope="module")
def reference():
    """First-position float32 log-probabilities of the golden prompts from
    each configuration's plain reference, and from the same reference over
    weights cut to 4 bits (a lower-precision path)."""
    import jax
    import numpy as np

    from reference.make_golden import chat_token_ids, seeded_weights

    def four_bits(w):
        if hasattr(w, "data") and hasattr(w, "scale"):
            return type(w)(data=(w.data // 16) * 16, scale=w.scale)
        return w

    out = {}
    for config, cut in CONFIGS.items():
        cfg = manifest.load_json("configs", f"{config}.json")
        logits_at = manifest.reference_of(cfg).logits_at
        params, _ = seeded_weights(cfg)
        coarse = dict(params, layers={
            k: four_bits(w) if cut is None or k in cut else w
            for k, w in params["layers"].items()})
        for kind, ps in (("exact", params), ("4bit", coarse)):
            for name in NAMES:
                ids = chat_token_ids(prompt_of(config, name)["content"])
                lg = logits_at(cfg, ps, ids, [len(ids) - 1])[0]
                out[config, kind, name] = [
                    float(x) for x in np.asarray(jax.nn.log_softmax(lg))]
    return out


@pytest.mark.parametrize("seed", range(4))
def test_unbias_recovers_a_token_log_probability_from_its_biased_one(seed):
    rng = random.Random(seed)
    logits = [rng.gauss(0.0, 0.73) for _ in range(32000)]
    lse = math.log(sum(math.exp(x) for x in logits))
    tid = rng.randrange(32000)
    true = logits[tid] - lse
    bias = setup_steps.probe_bias(true + rng.uniform(-0.5, 0.5))
    logits[tid] += bias
    biased = logits[tid] - math.log(sum(math.exp(x) for x in logits))
    assert biased == max(x - lse for x in logits) or biased > -2.0
    assert setup_steps.unbias(biased, bias) == pytest.approx(true, abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config", CONFIGS)
def test_the_reference_itself_passes(reference, config, name):
    p = prompt_of(config, name)
    got = setup_steps.judge_probes(
        served_by(reference[config, "exact", name], p), p["prompt_tokens"],
        tol_of(config))
    assert got["ok"] and got["max_abs_diff"] < 1e-4


@pytest.mark.parametrize("name,other", [
    ("bucket32", "bucket128"), ("bucket128", "chunk"), ("chunk", "bucket32")])
@pytest.mark.parametrize("config", CONFIGS)
def test_a_server_that_answers_with_another_prompts_logits_fails(
        reference, config, name, other):
    p = prompt_of(config, name)
    got = setup_steps.judge_probes(
        served_by(reference[config, "exact", other], p), p["prompt_tokens"],
        tol_of(config))
    assert not got["ok"] and got["max_abs_diff"] > 2 * tol_of(config)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("config", CONFIGS)
def test_a_lower_precision_path_fails(reference, config, name):
    p = prompt_of(config, name)
    got = setup_steps.judge_probes(
        served_by(reference[config, "4bit", name], p), p["prompt_tokens"],
        tol_of(config))
    assert not got["ok"] and got["max_abs_diff"] > 2.5 * tol_of(config)


def test_the_sparse_reference_is_not_the_dense_one():
    """The two references over the sparse configuration's own weights and
    prompt: the dense block cannot even take its expert-stacked matrices,
    so a golden file written by the wrong module is not a near miss."""
    from reference import forward
    from reference.make_golden import chat_token_ids, seeded_weights

    cfg = manifest.load_json("configs", "debug-moe.json")
    assert manifest.reference_of(cfg).__name__ == "reference.moe"
    params, _ = seeded_weights(cfg)
    ids = chat_token_ids(prompt_of("debug-moe", "bucket32")["content"])
    with pytest.raises((TypeError, ValueError)):
        forward.logits_at(cfg, params, ids, [len(ids) - 1])


@pytest.mark.parametrize("kind,quantization", [("int8", "int8"),
                                               ("bfloat16", None)])
def test_seeded_weights_are_the_tree_the_engine_serves(kind, quantization):
    """``make_golden.py`` takes its weights from what the configuration is
    served as: leaf for leaf the tree ``serve --random-weights`` builds
    with and without ``--quantization`` (engine/engine.py, its own seed)."""
    import jax
    import numpy as np

    from llms_on_kubernetes_tpu.engine.engine import Engine, EngineConfig
    from reference.make_golden import seeded_weights

    cfg = manifest.load_json("configs", "debug-moe.json")
    served = dict(cfg, served_as=dict(cfg["served_as"], weights=kind))
    params, said = seeded_weights(served)
    engine = Engine(EngineConfig(
        model=cfg["registry_name"], quantization=quantization, num_pages=16,
        max_decode_slots=2, pages_per_slot=4, prefill_buckets=(32,)))
    mine, theirs = (jax.tree_util.tree_leaves(t)
                    for t in (params, engine.params))
    assert len(mine) == len(theirs)
    assert {"int8": "random_quantized_params",
            "bfloat16": "init_params"}[kind] in said
    for a, b in zip(mine, theirs):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    with pytest.raises(manifest.ManifestError):
        seeded_weights(dict(cfg, served_as={"weights": "fp8"}))


@pytest.mark.parametrize("fault", ["one id off", "token count", "no answer"])
def test_one_bad_probe_fails_the_prompt(fault):
    p = PROMPTS["bucket32"]
    probes = [{"id": t, "reference": r, "served": r, "prompt_tokens": 19}
              for t, r in zip(p["top_ids"][0][:8], p["top_logprobs"][0][:8])]
    assert setup_steps.judge_probes(probes, 19, TOL)["ok"]
    if fault == "one id off":
        probes[3]["served"] += 1.5 * TOL
    elif fault == "token count":
        probes[0]["prompt_tokens"] = 18
    else:
        probes[7]["served"] = None
    assert not setup_steps.judge_probes(probes, 19, TOL)["ok"]
    assert not setup_steps.judge_probes([], 19, TOL)["ok"]


def test_compared_lists_each_number_beside_its_limit():
    report = {"tolerance_nats": 0.01, "repeat_identical": True,
              "finite": False, "prompts": [
                  {"name": "bucket32", "max_abs_diff": 0.004,
                   "prompt_tokens_ok": True},
                  {"name": "chunk", "max_abs_diff": None,
                   "prompt_tokens_ok": False}]}
    got = setup_steps.compared(report)
    assert list(got) == ["bucket32.nats", "bucket32.tokens_ok", "chunk.nats",
                         "chunk.tokens_ok", "repeat_identical", "finite"]
    assert got["bucket32.nats"] == {"value": 0.004, "limit": 0.01,
                                    "better": "lower"}
    assert got["chunk.nats"]["value"] is None     # an id got no answer
    assert got["chunk.tokens_ok"]["value"] == 0 and got["finite"] == {
        "value": 0, "limit": 1, "better": "higher"}
    assert setup_steps.compared(dict(report, prompts=[],
                                     tolerance_nats=None)).keys() == {
        "repeat_identical", "finite"}


def test_the_foreign_probe_is_another_prompts_best_token_not_in_ours():
    for p in GOLDEN["prompts"]:
        tid, lp = setup_steps.foreign_probe(GOLDEN["prompts"], p)
        assert tid not in p["top_ids"][0]
        assert any(q["content"] != p["content"] and tid in q["top_ids"][0]
                   and lp in q["top_logprobs"][0] for q in GOLDEN["prompts"])


def test_the_chips_recorded_probes_pass_and_its_foreign_probes_would_fail():
    """``data/mistral-7b.check.json`` is the check's report from a run on
    the chip (deterministic: the weights' seed and the prompts are fixed).
    The committed tolerance passes every id asked for, and is under what
    the check would have seen had the server answered with another
    prompt's logits (the foreign probes)."""
    golden = manifest.load_json("golden", "mistral-7b.json")
    tol = golden["tolerance"]["nats"]
    seen = manifest.load_json("tests", "data", "mistral-7b.check.json")
    assert [p["name"] for p in seen["prompts"]] == [
        p["name"] for p in golden["prompts"]]
    for p, want in zip(seen["prompts"], golden["prompts"]):
        assert [q[0] for q in p["probes"]] == want["top_ids"][0][:8]
        probes = [{"id": i, "reference": r, "served": s,
                   "prompt_tokens": want["prompt_tokens"]}
                  for i, r, s in p["probes"]]
        got = setup_steps.judge_probes(probes, want["prompt_tokens"], tol)
        assert got["ok"] and got["max_abs_diff"] <= 0.6 * tol
        tid, there, here = p["foreign"]
        assert there - here > 1.5 * tol
