"""The open-loop schedule: a function of the mix's parameters, the rate,
the window and the seed, and of nothing else.

A mix is a FIXED TRACE, replayed. Its requests are stratified quantiles of
the mix's distributions (prompt lengths, answer lengths, inter-arrival
gaps), so the total work and the arrival span are the same in every run;
they are joined into one sequence of (gap, prompt, answer) by a shuffle
drawn from a constant. ``--seed`` chooses where in that sequence a run
starts (a rotation) and the prompts' text, and nothing else: every run
sees the same coincidences of bursts and long prompts, from another
starting point.

That is a choice with a cost, and the mix's ``arrivals.replay`` names it.
A tail percentile of a few hundred requests is set by a handful of such
coincidences: when the seed reshuffled the joins, two 20 s runs read
``ttft_p95_ms`` 1084 and 716 (my chip run, PR 23), which no bound of a
tenth can hold. The cost is that the spread over seeds is the spread of
one trace over its starting points, not of the traffic it was drawn from,
and that a change can be tuned to this one sequence.
"""

from __future__ import annotations

import dataclasses
import math
import random
import statistics

_ALPHABET = "abcdefghijklmnopqrstuvwxyz     "


@dataclasses.dataclass(frozen=True)
class Planned:
    due_s: float        # seconds after the first due request of the part
    prompt: str         # one byte per token under the served tokenizer
    prompt_tokens: int
    max_tokens: int


def _lengths(spec: dict, n: int) -> list:
    """n stratified quantiles of the clipped distribution ``spec``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = statistics.NormalDist()
    out = []
    for i in range(n):
        v = float(spec["median"]) * math.exp(
            float(spec["sigma"]) * z.inv_cdf((i + 0.5) / n))
        out.append(min(hi, max(lo, round(v))))
    return out


def _gaps(arrivals: dict, n: int, span_s: float) -> list:
    """n inter-arrival gaps that sum to ``span_s``: the stratified
    quantiles of an exponential distribution (the gaps a Poisson process
    would have, without its run-to-run variation in their number)."""
    if arrivals["gaps"] != "exponential_quantiles":
        raise ValueError(f"unknown arrivals.gaps {arrivals['gaps']!r}")
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def random_text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(_ALPHABET, k=n))


def plan(mix: dict, rate_rps: float, span_s: float, seed: int,
         part: str) -> list:
    """The requests of one part of a run (``preroll``, ``window``, ...),
    in due order. ``span_s`` is exactly covered: the last gap ends at it."""
    if mix["arrivals"]["replay"] != "fixed_trace_from_seed_offset":
        raise ValueError(
            f"unknown arrivals.replay {mix['arrivals']['replay']!r}")
    n = max(1, round(rate_rps * span_s))
    prompts = _lengths(mix["prompt_tokens"], n)
    answers = _lengths(mix["output_tokens"], n)
    gaps = _gaps(mix["arrivals"], n, span_s)
    joins = random.Random(f"{part}:fixed")
    joins.shuffle(prompts)
    joins.shuffle(answers)
    joins.shuffle(gaps)
    rng = random.Random(f"{part}:{int(seed)}")
    k = rng.randrange(n)
    out, t = [], 0.0
    for i in range(k, k + n):
        p = prompts[i % n]
        out.append(Planned(t, random_text(rng, p), p, answers[i % n]))
        t += gaps[i % n]
    return out
