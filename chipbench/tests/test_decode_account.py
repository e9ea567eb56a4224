"""The five readers of the engine's account of the time after the first
token (ISSUE 41), on hand-made records: the three ``tpot_*_ms`` means
are over the same requests and add up; each reader finds nothing, and
does not raise, on a record of a parent commit's shape."""

import importlib
import os

import pytest

from chipbench.lib import decode_account, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
TPOT = ("tpot_stepping_ms", "tpot_prefill_stall_ms", "tpot_block_tail_ms")
READERS = TPOT + ("prefill_padded_share", "prefill_stall_trace_error")


def _reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}").read


def _reply(n, ttft, stepping, stall, tail):
    return {"done": 1.0, "summary": {
        "num_tokens": n, "time_to_first_token_s": ttft,
        "latency_s": ttft + stepping + stall + tail, "stepping_s": stepping,
        "prefill_stall_s": stall, "block_tail_s": tail}}


def test_the_three_means_add_up_over_the_same_requests():
    reqs = [_reply(11, 0.5, 0.20, 0.03, 0.01),
            _reply(3, 0.1, 0.04, 0.0, 0.02),
            _reply(101, 0.2, 2.5, 0.6, 0.004),
            _reply(1, 0.3, 0.0, 0.0, 0.0),        # ended at its first token
            {"error": "lost"},
            {"done": 2.0, "summary": None}]
    run = {"serve": {"requests": reqs}}
    got = [_reader(name)(run) for name in TPOT]
    decoded = [r["summary"] for r in reqs[:3]]
    want = sum(1e3 * (s["latency_s"] - s["time_to_first_token_s"])
               / (s["num_tokens"] - 1) for s in decoded) / 3
    assert sum(got) == pytest.approx(want)
    assert got[1] == pytest.approx(1e3 * (0.03 / 10 + 0.0 + 0.6 / 100) / 3)
    # the one-token request is out of all three alike: with it gone the
    # means are the same numbers
    del reqs[3]
    assert [_reader(name)(run) for name in TPOT] == got
    assert decode_account.mean_ms(run, "block_tail_s") == got[2]


def test_counters_feed_the_padded_share_and_the_trace_error():
    run = {"serve": {"requests": [],
                     "stats0": {"prefill_prompt_tokens": 100,
                                "prefill_padded_tokens": 200},
                     "stats1": {"prefill_prompt_tokens": 400,
                                "prefill_padded_tokens": 600}},
           "traced": {"stats0": {"prefill_wave_s": 1.0},
                      "stats1": {"prefill_wave_s": 1.27}},
           "spans": {"modules": {
               "engine_prefill": {"count": 3, "total_s": 0.2},
               "engine_prefill_suffix": {"count": 1, "total_s": 0.05},
               "engine_decode_block": {"count": 9, "total_s": 3.0}}}}
    assert _reader("prefill_padded_share")(run) == pytest.approx(0.25)
    assert _reader("prefill_stall_trace_error")(run) == pytest.approx(0.08)
    # no prefill program ran inside the trace: nothing to hold it to
    del run["spans"]["modules"]["engine_prefill"]
    del run["spans"]["modules"]["engine_prefill_suffix"]
    assert _reader("prefill_stall_trace_error")(run) is None
    # no wave in the window
    run["serve"]["stats1"] = dict(run["serve"]["stats0"])
    assert _reader("prefill_padded_share")(run) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_a_parent_commits_record(name):
    """Replies without the three keys, counters without
    ``prefill_wave_s`` (a trace WITH the prefill programs beside them),
    a trace without the names, and no trace at all: None each time,
    never an exception."""
    read = _reader(name)
    replies = [{"done": 1.0, "summary": {
        "num_tokens": 9, "time_to_first_token_s": 0.5, "latency_s": 0.9,
        "queue_wait_s": 0.1, "prefill_s": 0.4, "slot_wait_s": 0.0}},
        {"error": "x"}]
    parent = {"spans": spans.reduce_spans(os.path.join(HERE,
                                                      "spans.xplane.pb")),
              "traced": {"window_s": 1.0, "stats0": {"steps": 0},
                         "stats1": {"steps": 64}},
              "serve": {"requests": replies,
                        "stats0": {"steps": 0}, "stats1": {"steps": 64}}}
    assert parent["spans"]["modules"]["engine_prefill"]["total_s"] > 0
    assert read(parent) is None
    old = dict(parent, spans=spans.reduce_spans(
        os.path.join(HERE, "mini.xplane.pb")),
        trace=trace.reduce_trace(os.path.join(HERE, "mini.xplane.pb")))
    assert read(old) is None
    assert read({}) is None
    assert read({"trace_dir": "/nonexistent", "trace": {},
                 "traced": None, "serve": None}) is None
