"""What a run does before its window: warm every shape the mix can reach,
then check the server's outputs. Both go through the router."""

from __future__ import annotations

import json
import math
import os
import random
import threading
import time

from . import client, manifest
from .launcher import Failed
from .schedule import random_text

# how long a set-up request waits out 503s: a replica busy compiling can
# miss the router's probe and stay ejected for as long as the compile lasts
PATIENCE_S = 900.0


def reachable_buckets(config: dict, mix: dict) -> list:
    """(bucket, a prompt length that lands in it) for each prefill bucket
    some prompt of the mix can fall into; no other shape is warmed."""
    lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    out, prev = [], 0
    for b in sorted(int(x) for x in config["prefill_buckets"]):
        if lo <= b and hi > prev:
            out.append((b, max(prev + 1, lo, min(b, hi) - 7)))
        prev = b
    return out


def chunk_path_lengths(config: dict, mix: dict) -> list:
    """Prompt lengths of the mix that are over the largest prefill bucket,
    one for each SMALLER bucket the last chunk of such a prompt can land
    in, longest first; none for a mix that stays inside its buckets. The
    program cuts a longer prompt into chunks of the largest bucket and
    pads the rest to the bucket that holds it, one executable a bucket.
    Every such prompt runs a full chunk first, so a last chunk that lands
    in the largest bucket is no executable of its own; where every one
    does, the mix's longest prompt alone warms the full chunk."""
    buckets = sorted(int(x) for x in config["prefill_buckets"])
    top = buckets[-1]
    lo, hi = int(mix["prompt_tokens"]["min"]), int(mix["prompt_tokens"]["max"])
    out, seen = [], {top}
    for n in range(hi, max(lo, top + 1) - 1, -1):
        last = (n - 1) % top + 1
        bucket = next(b for b in buckets if b >= last)
        if bucket not in seen:
            seen.add(bucket)
            out.append(n)
    return out or ([hi] if hi > top else [])


def _complete(port: int, model: str, prompt: "str | list",
              max_tokens: int, waited: list) -> None:
    client.stream_docs(
        port, "/v1/completions",
        dict(model=model, prompt=prompt, max_tokens=max_tokens,
             temperature=0, logprobs=1),
        timeout=1100.0, patience_s=PATIENCE_S, waited=waited)


def compiles(server_port: int) -> float:
    return client.metric_sum(client.scrape(server_port),
                             "llm_jit_compiles_total")


def load_shapes(router_port: int, server_port: int, model: str,
                config: dict, mix: dict, waited: list) -> dict:
    """One lone request and one burst for each reachable bucket, then,
    for a mix whose prompts outgrow the largest bucket, one lone request
    for each executable of the chunk path: these compile the big
    executables, or load them from the persistent cache."""
    warm = mix["warmup"]
    shapes = reachable_buckets(config, mix)
    counts = [compiles(server_port)]
    rng = random.Random("warmup")
    for bucket, length in shapes:
        _complete(router_port, model, random_text(rng, length),
                  int(warm["output_tokens"]), waited)
        errors: list = []

        def one(text: str) -> None:
            try:
                _complete(router_port, model, text,
                          int(warm["output_tokens"]), waited)
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(random_text(rng, length),))
                   for _ in range(int(warm["burst"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise Failed(f"warm-up burst at bucket {bucket}: {errors[0]!r}")
    # the burst once more as ONE request that carries all its prompts: the
    # server submits them together, so the idle engine admits them in one
    # prefill while no decode dispatch is in flight. Requests that arrive
    # one by one reach that variant of the decode step only by chance (a
    # burst at a moment when the pipeline has just drained): two of four
    # runs met it inside or just after their window and lost 17-20 s to
    # the re-trace (my chip run, PR 23)
    # (a mix wholly over its buckets has no multi-row prefill to reach)
    if shapes:
        _complete(router_port, model,
                  [random_text(rng, shapes[0][1])
                   for _ in range(int(warm["burst"]))],
                  int(warm["output_tokens"]), waited)
    # last, so that a mix inside its buckets sends what it always sent
    chunked = chunk_path_lengths(config, mix)
    for length in chunked:
        _complete(router_port, model, random_text(rng, length),
                  int(warm["output_tokens"]), waited)
    counts.append(compiles(server_port))
    return {"jit_compiles_before_and_after": counts,
            "shapes": [list(s) for s in shapes], "chunk_path": chunked}


def storms(router_port: int, server_port: int, model: str, mix: dict,
           knee_rps: float, deadline: float, waited: list) -> dict:
    """A few seconds of the mix itself above the knee, repeated until a
    round adds nothing to ``llm_jit_compiles_total`` or ``deadline``
    passes. They are there because the program re-traces a step whenever
    an argument arrives with another sharding annotation (a token array
    fresh from a 1-row prefill, a 4-row one, a chunk, the host), and only
    traffic reaches those combinations; each costs seconds even when the
    persistent cache has the executable. Run after the output check, whose
    chunk-path requests leave such arrays behind."""
    import asyncio

    from . import schedule

    storm = mix["warmup"]["storm"]
    counts = [compiles(server_port)]
    rounds, failed = 0, []
    for n in range(int(storm["max_rounds"])):
        if time.monotonic() > deadline:
            break
        span = float(storm["seconds"])
        planned = schedule.plan(
            mix, knee_rps * float(storm["rate_share_of_knee"]), span,
            n, "storm")
        got = asyncio.run(client.open_loop(
            router_port, model, mix, [("storm", planned, span)],
            float(mix["drain_limit_s"])))
        bad = sum(1 for r in got["records"] if not r.ok)
        failed.append(bad)
        if bad:
            # a round that met a cold compile: its requests sat behind
            # minutes of XLA and were cut at the drain limit. That is what
            # a storm is for; wait the stall out on one patient request
            _complete(router_port, model, random_text(random.Random(n), 32),
                      2, waited)
        rounds += 1
        counts.append(compiles(server_port))
        if counts[-1] == counts[-2] and not bad:
            break
    return {"rounds": rounds, "jit_compiles_after_each_round": counts,
            "requests_cut_in_each_round": failed}


# --------------------------------------------------------------------------
# the output check behind "correct"
# --------------------------------------------------------------------------

def chat_logprobs(port: int, model: str, content: str, max_tokens: int,
                  top: int, waited: list, logit_bias: "dict | None" = None
                  ) -> dict:
    """One greedy chat completion with ``top`` alternatives per position.
    The chat form is used because it returns the alternatives as a LIST:
    the completions form keys them by token text, and the byte tokenizer
    spells most ids of a large vocabulary as the same empty string."""
    body = dict(model=model, max_tokens=max_tokens, temperature=0,
                logprobs=True, top_logprobs=top,
                messages=[{"role": "user", "content": content}])
    if logit_bias:
        body["logit_bias"] = logit_bias
    docs = client.stream_docs(port, "/v1/chat/completions", body,
                              timeout=1100.0, patience_s=PATIENCE_S,
                              waited=waited)
    entries = [e for d in docs for ch in d.get("choices", ())
               for e in (ch.get("logprobs") or {}).get("content", ())]
    usage = next((d["usage"] for d in docs if d.get("usage")), {})
    return {"prompt_tokens": usage.get("prompt_tokens"),
            "chosen": [e["logprob"] for e in entries],
            "top": [[a["logprob"] for a in e["top_logprobs"]]
                    for e in entries]}


def probe_bias(ref_logprob: float) -> float:
    """The bias that lifts a token the reference gives ``ref_logprob`` to
    e times the rest of the vocabulary's whole mass: it is then the argmax
    for any server within a nat of the reference, and the biased
    probability stays near 0.7, where a float32 log-probability inverts
    without loss."""
    return round(min(100.0, max(0.0, 1.0 - ref_logprob)), 3)


def unbias(biased_logprob: float, bias: float) -> float:
    """The log-probability a token had before ``bias`` was added to its
    logit, from the one it was reported with afterwards: with p the
    unbiased probability and x the biased one, x = p e^b / (1 - p + p e^b),
    so log p = log x - b - log(1 - x + x e^-b)."""
    x = math.exp(biased_logprob)
    return biased_logprob - bias - math.log1p(-x + x * math.exp(-bias))


def probe(port: int, model: str, content: str, token_id: int,
          ref_logprob: float, waited: list) -> dict:
    """The served log-probability of ONE token id at the first generated
    position. Answers carry no token ids (``top_logprobs`` spells a token
    as text, and most ids of a 32000-token vocabulary spell as nothing),
    so the id is asked for: ``logit_bias`` lifts it to the argmax, greedy
    decoding reports the biased log-probability of the argmax, which is
    exact on every sampling path, and ``unbias`` takes the bias out."""
    bias = probe_bias(ref_logprob)
    got = chat_logprobs(port, model, content, 1, 1, waited,
                        {str(int(token_id)): bias})
    lp = got["chosen"][0] if got["chosen"] else None
    ok = isinstance(lp, float) and math.isfinite(lp) and lp < 0.0
    return {"id": int(token_id), "reference": ref_logprob,
            "served": unbias(lp, bias) if ok else None,
            "prompt_tokens": got["prompt_tokens"]}


def judge_probes(probes: list, prompt_tokens: int, tol: float) -> dict:
    """A prompt's probes against the reference: every id's served
    log-probability within ``tol`` nats of the reference's for that id."""
    diffs = [abs(p["served"] - p["reference"]) if p["served"] is not None
             else math.inf for p in probes]
    counted = all(p["prompt_tokens"] == prompt_tokens for p in probes)
    worst = max(diffs, default=math.inf)
    return {"max_abs_diff": worst if math.isfinite(worst) else None,
            "prompt_tokens_ok": counted,
            "ok": bool(counted and worst <= tol)}


def foreign_probe(golden_prompts: list, mine: dict) -> "tuple | None":
    """(id, its reference log-probability under ITS prompt) of the best
    token of another golden prompt that is not among this prompt's
    reference top-20: what a server that answered this prompt with that
    one's logits would be asked about."""
    here = set(mine["top_ids"][0])
    for other in golden_prompts:
        if other["content"] == mine["content"]:
            continue
        for tid, lp in zip(other["top_ids"][0], other["top_logprobs"][0]):
            if tid not in here:
                return int(tid), float(lp)
    return None


def load_golden(config_name: str):
    path = os.path.join(manifest.BENCH_DIR, "golden", f"{config_name}.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def check_outputs(port: int, model: str, config_name: str, config: dict,
                  waited: list) -> dict:
    """``correct``: for every golden prompt, the served log-probability of
    each of the reference's best ``check.probe_ids`` token ids at the
    first generated position, asked for BY ID (see ``probe``), is within
    the golden file's tolerance of the reference's. A prompt's first probe
    goes through the prefill path its length selects; the later ones, and
    the prompt the golden file repeats, are answered by the prefix cache
    and the chunk path. Then a short request sent twice must be bit-
    identical over 8 greedy tokens (the decode path), every value finite.

    Beside that, and deciding nothing, each prompt is asked for the best
    token of ANOTHER prompt (``foreign``): how far its served value lies
    under that token's reference value is what the check would see of a
    server that answered this prompt with the other's logits.

    Where a configuration has no golden file yet, the self-consistency
    checks stand alone and the report says so."""
    golden = load_golden(config_name)
    report: dict = {"golden": golden is not None, "prompts": []}
    ok = True
    n_ids = int(config.get("check", {}).get("probe_ids", 8))
    diffs: list = []
    for p in (golden["prompts"] if golden else ()):
        tol = float(golden["tolerance"]["nats"])
        probes = [probe(port, model, p["content"], tid, lp, waited)
                  for tid, lp in zip(p["top_ids"][0][:n_ids],
                                     p["top_logprobs"][0][:n_ids])]
        row = dict(name=p["name"], probes=[
            [q["id"], q["reference"], q["served"]] for q in probes],
            **judge_probes(probes, int(p["prompt_tokens"]), tol))
        other = foreign_probe(golden["prompts"], p)
        if other is not None:
            q = probe(port, model, p["content"], *other, waited)
            row["foreign"] = [q["id"], q["reference"], q["served"]]
        diffs += [abs(s - r) for _, r, s in row["probes"] if s is not None]
        ok = ok and row["ok"]
        report["prompts"].append(row)
    if diffs:
        report["max_abs_diff"] = max(diffs)
        report["rms_diff"] = math.sqrt(sum(d * d for d in diffs) / len(diffs))
    # the same short request twice: under a page, so both take the same
    # path (no cached prefix to adopt) and must agree bit for bit
    page = int(config["serve_flags"]["--page-size"])
    top = int(config.get("check", {}).get("top_logprobs", 8))
    short = random_text(random.Random("repeat"), max(1, page - 24))
    a = chat_logprobs(port, model, short, 8, top, waited)
    b = chat_logprobs(port, model, short, 8, top, waited)
    vals = a["chosen"] + [x for row in a["top"] for x in row]
    report["finite"] = bool(vals) and all(
        isinstance(v, float) and math.isfinite(v) and v <= 1e-6
        for v in vals)
    report["repeat_identical"] = a == b
    report["correct"] = bool(ok and a == b and report["finite"])
    report["tolerance_nats"] = float(golden["tolerance"]["nats"]) if golden \
        else None
    return report


def compared(report: dict) -> dict:
    """Every number ``check_outputs`` compared, beside its limit, under
    short plain names: per golden prompt the largest difference in nats
    between a served and a reference log-probability over the ids asked
    for (``None`` where an id got no answer) and whether the server
    counted the reference's prompt tokens; then the two self-consistency
    checks. ``better`` says which side of the limit passes."""
    tol = report.get("tolerance_nats")
    out = {}
    for p in report["prompts"]:
        out[f"{p['name']}.nats"] = {"value": p["max_abs_diff"],
                                    "limit": tol, "better": "lower"}
        out[f"{p['name']}.tokens_ok"] = {
            "value": int(p["prompt_tokens_ok"]), "limit": 1,
            "better": "higher"}
    for key in ("repeat_identical", "finite"):
        out[key] = {"value": int(report[key]), "limit": 1,
                    "better": "higher"}
    return out
