"""``sampler_draw_share`` (ISSUE 52) on recorded snapshot pairs: the
steps of the window whose sampler drew over the steps its blocks ran;
nothing, and no exception, on a parent commit's record."""

import pytest

from chipbench.metrics import sampler_draw_share


def _record(stats0, stats1):
    return {"serve": {"stats0": stats0, "stats1": stats1}}


@pytest.mark.parametrize("drawn,share", [
    ((0, 0), 0.0),               # greedy traffic: no step drew
    ((3_200, 29_632), 1.0),      # every request sampled: every step did
    ((100, 6_708), 0.25),        # a sampled row live a quarter of the steps
], ids=["greedy", "sampled", "mixed"])
def test_the_share_is_the_window_s_own(drawn, share):
    run = _record({"block_steps_run": 3_200, "block_steps_drawn": drawn[0],
                   "block_steps_offered": 6_400},
                  {"block_steps_run": 29_632, "block_steps_drawn": drawn[1],
                   "block_steps_offered": 44_160})
    assert sampler_draw_share.read(run) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    # a parent commit: the steps run, not those that drew
    _record({"block_steps_run": 0}, {"block_steps_run": 64}),
    # one snapshot of the two lacks it
    _record({"block_steps_run": 0},
            {"block_steps_run": 3, "block_steps_drawn": 3}),
    # no step in the window
    _record({"block_steps_run": 7, "block_steps_drawn": 0},
            {"block_steps_run": 7, "block_steps_drawn": 0}),
    _record({}, {}), {"serve": None}, {}],
    ids=["parent", "half", "no-step", "empty", "no-serve", "nothing"])
def test_the_reader_finds_nothing_and_does_not_raise(run):
    assert sampler_draw_share.read(run) is None
