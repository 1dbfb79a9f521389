"""The output check behind ``correct``: it asks for token ids by name, and
it fails a server that computes something else."""

import math
import random

import pytest

from harness import manifest, setup_steps

GOLDEN = manifest.load_json("golden", "debug-tiny.json")
TOL = GOLDEN["tolerance"]["nats"]
PROMPTS = {p["name"]: p for p in GOLDEN["prompts"]}


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
    the plain reference, and from the same reference over weights cut to
    4 bits (a lower-precision path)."""
    import jax
    import numpy as np

    from llms_on_kubernetes_tpu.configs import get_config
    from llms_on_kubernetes_tpu.ops.quant import random_quantized_params
    from reference.forward import logits_at
    from reference.make_golden import chat_token_ids

    cfg = manifest.load_json("configs", "debug-tiny.json")
    params = random_quantized_params(get_config("debug-tiny"), 0,
                                     dtype="bfloat16")

    def four_bits(w):
        if hasattr(w, "data") and hasattr(w, "scale"):
            return type(w)(data=(w.data // 16) * 16, scale=w.scale)
        return w

    coarse = dict(params, layers=jax.tree_util.tree_map(
        four_bits, params["layers"],
        is_leaf=lambda w: hasattr(w, "data")))

    def first(ps, name):
        ids = chat_token_ids(PROMPTS[name]["content"])
        lg = logits_at(cfg, ps, ids, [len(ids) - 1])[0]
        return [float(x) for x in np.asarray(jax.nn.log_softmax(lg))]

    return {(kind, name): first(ps, name)
            for kind, ps in (("exact", params), ("4bit", coarse))
            for name in ("bucket32", "bucket128", "chunk")}


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


@pytest.mark.parametrize("name", ["bucket32", "bucket128", "chunk"])
def test_the_reference_itself_passes(reference, name):
    p = PROMPTS[name]
    got = setup_steps.judge_probes(served_by(reference["exact", name], p),
                                   p["prompt_tokens"], TOL)
    assert got["ok"] and got["max_abs_diff"] < 1e-4


@pytest.mark.parametrize("name,other", [
    ("bucket32", "bucket128"), ("bucket128", "chunk"), ("chunk", "bucket32")])
def test_a_server_that_answers_with_another_prompts_logits_fails(
        reference, name, other):
    p = PROMPTS[name]
    got = setup_steps.judge_probes(served_by(reference["exact", other], p),
                                   p["prompt_tokens"], TOL)
    assert not got["ok"] and got["max_abs_diff"] > 2 * TOL


@pytest.mark.parametrize("name", ["bucket32", "bucket128", "chunk"])
def test_a_lower_precision_path_fails(reference, name):
    p = PROMPTS[name]
    got = setup_steps.judge_probes(served_by(reference["4bit", name], p),
                                   p["prompt_tokens"], TOL)
    assert not got["ok"]


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
