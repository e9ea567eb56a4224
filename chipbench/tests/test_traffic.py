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


# serve-reason's 45 s as PR 26 drew them (draw_seed 26, 0.60/s): due
# time, prompt length, answer length.  A PR that steadies the cell may
# fix what --seed draws, not what is offered (PR 29).
SERVE_REASON_45 = [
    (0.0, 784, 718), (0.638205, 849, 3586), (2.708552, 1109, 595),
    (9.693358, 174, 964), (11.414547, 808, 2875), (12.683676, 930, 2592),
    (12.783534, 1365, 1317), (13.385113, 635, 697), (19.291528, 227, 1615),
    (19.407591, 1008, 1147), (19.517789, 64, 4096), (21.347411, 345, 1367),
    (22.076164, 120, 3590), (23.886348, 1276, 1450), (24.41056, 161, 1057),
    (24.902576, 125, 2121), (25.47789, 417, 2153), (27.960939, 436, 3614),
    (30.308138, 375, 910), (30.364702, 901, 2770), (32.81254, 269, 827),
    (35.662227, 457, 1535), (36.913094, 256, 2093), (37.110229, 865, 1890),
    (37.136814, 156, 1068), (44.790021, 341, 1759), (44.997838, 802, 493)]


def test_serve_reason_offers_what_it_offered_for_every_seed():
    mix = _mix("serve-reason")
    assert mix["rate_per_s"] == 0.6 and mix["draw_seed"] == 26
    for seed in (1, 2900000017, mix["contents_seed"]):
        s = traffic.serve_schedule(mix, seed, 45, 151936)
        assert [(round(r["due_s"], 6), len(r["prompt"]),
                 r["max_new_tokens"]) for r in s] == SERVE_REASON_45
    assert (849, 3586) in [row[1:] for row in SERVE_REASON_45]


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
