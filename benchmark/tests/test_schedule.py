import collections

import pytest

from harness import manifest, schedule

MIX = manifest.load_json("traffic", "chat.json")


def test_schedule_is_a_function_of_the_seed_alone():
    a = schedule.plan(MIX, 4.8, 50.0, 2**31 + 12345, "window")
    b = schedule.plan(MIX, 4.8, 50.0, 2**31 + 12345, "window")
    c = schedule.plan(MIX, 4.8, 50.0, 7, "window")
    assert a == b
    assert a != c
    assert [p.prompt for p in a] != [p.prompt for p in c]


@pytest.mark.parametrize("field", ["prompt_tokens", "max_tokens"])
def test_every_seed_gets_the_same_multiset_of_lengths(field):
    bags = [collections.Counter(getattr(p, field) for p in
                                schedule.plan(MIX, 4.8, 50.0, s, "window"))
            for s in (0, 1, 2**31 + 5)]
    assert bags[0] == bags[1] == bags[2]


def test_arrivals_cover_the_window_exactly_and_gaps_are_a_fixed_multiset():
    def gaps(seed):
        due = [p.due_s for p in schedule.plan(MIX, 4.8, 50.0, seed, "window")]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 50.0
        return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))
    g0, g1 = gaps(3), gaps(4)
    # all but the last gap (which runs to the window's end) are in `due`
    assert len(g0) == len(g1) == round(4.8 * 50.0) - 1
    assert sum(abs(a - b) for a, b in zip(g0, g1)) < 1.0


def test_lengths_follow_the_mix_and_prompts_are_one_byte_a_token():
    plan = schedule.plan(MIX, 4.8, 50.0, 1, "window")
    lens = sorted(p.prompt_tokens for p in plan)
    spec = MIX["prompt_tokens"]
    assert lens[0] >= spec["min"] and lens[-1] <= spec["max"]
    assert abs(lens[len(lens) // 2] - spec["median"]) <= 0.05 * spec["median"]
    assert all(len(p.prompt.encode()) == p.prompt_tokens for p in plan)
    assert len({p.prompt[:64] for p in plan}) == len(plan)   # nothing shared


def test_an_unknown_distribution_or_replay_is_an_error_not_a_default():
    for key, bad in (("arrivals", dict(MIX["arrivals"], gaps="nope")),
                     ("arrivals", dict(MIX["arrivals"], replay="reshuffle")),
                     ("prompt_tokens", dict(MIX["prompt_tokens"],
                                            dist="constant"))):
        with pytest.raises(ValueError):
            schedule.plan(dict(MIX, **{key: bad}), 1, 1, 0, "w")


def test_a_seed_replays_the_one_trace_from_another_offset():
    mix = MIX
    a = schedule.plan(mix, 4.8, 50.0, 11, "window")
    b = schedule.plan(mix, 4.8, 50.0, 2**31 + 99, "window")
    seq = lambda plan: [(p.prompt_tokens, p.max_tokens) for p in plan]  # noqa: E731
    sa, sb = seq(a), seq(b)
    assert sa != sb and a[0].prompt != b[0].prompt
    k = next(k for k in range(len(sa)) if sa[k:] + sa[:k] == sb)
    gaps = lambda plan: [round(y.due_s - x.due_s, 9)  # noqa: E731
                         for x, y in zip(plan, plan[1:])]
    ga, gb = gaps(a), gaps(b)
    # the same gap follows the same request (but for the one that wraps)
    assert sum(1 for x, y in zip((ga + [None])[k:] + (ga + [None])[:k],
                                 gb + [None]) if x != y) <= 2
