"""The generators: the same schedule for the same seed, the same work for
every seed, and a report of what was made."""

import json
import os

from chipbench.lib import traffic
from chipbench.lib.stats import percentile

HERE = os.path.dirname(os.path.abspath(__file__))


def _mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_schedule():
    mix = _mix("serve-chat")
    a = traffic.serve_schedule(mix, 3000000019, 45, 49152)
    b = traffic.serve_schedule(mix, 3000000019, 45, 49152)
    assert a == b


def test_every_seed_offers_the_same_schedule_with_other_tokens():
    mix = _mix("serve-chat")
    a = traffic.serve_schedule(mix, 1, 45, 49152)
    b = traffic.serve_schedule(mix, 3000000019, 45, 49152)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),  # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b)
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    other = traffic.serve_schedule(dict(mix, draw_seed=24), 1, 45, 49152)
    assert shape(other) != shape(a)


def test_schedule_is_what_the_mix_says():
    mix = _mix("serve-chat")
    s = traffic.serve_schedule(mix, 7, 45, 49152)
    d = traffic.describe(s, 45)
    assert d["requests"] == round(mix["rate_per_s"] * 45)
    assert 0 <= s[0]["due_s"] and d["last_due_s"] < 45
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(s, s[1:]))
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in s)
    assert all(mix["output_len"]["min"] <= r["max_new_tokens"]
               <= mix["output_len"]["max"] for r in s)
    assert all(1 <= t < 49152 for r in s for t in r["prompt"])
    # the medians the mix names, within what 72 draws allow
    assert 150 < d["prompt_len_p50"] < 450
    assert 80 < d["output_len_p50"] < 200


def test_gamma_arrivals_are_burstier_than_poisson():
    base = dict(_mix("serve-chat"), rate_per_s=20)
    cv = lambda s: (lambda g: (sum((x - sum(g) / len(g)) ** 2  # noqa: E731
                                   for x in g) / len(g)) ** 0.5
                    / (sum(g) / len(g)))(
        [y["due_s"] - x["due_s"] for x, y in zip(s, s[1:])])
    poisson = traffic.serve_schedule(base, 1, 100, 100)
    bursty = traffic.serve_schedule(
        dict(base, arrivals={"process": "gamma", "cv": 3.0}), 1, 100, 100)
    assert 0.8 < cv(poisson) < 1.2 < 2.0 < cv(bursty)


def test_percentile():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(range(101), 95) == 95
    assert percentile([], 95) is None and percentile([7], 95) == 7
