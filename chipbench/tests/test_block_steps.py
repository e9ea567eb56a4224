"""``block_steps_run_share`` (ISSUE 48) on recorded snapshot pairs: the
steps the window's blocks ran over those they were offered; nothing, and
no exception, on a parent commit's record."""

import pytest

from chipbench.metrics import block_steps_run_share


def _record(stats0, stats1):
    return {"serve": {"stats0": stats0, "stats1": stats1}}


def test_the_share_is_the_window_s_own():
    # a serve-chat window's pair: 1,180 blocks of 32 offered, 70% run
    run = _record({"block_steps_run": 3_200, "block_steps_offered": 6_400,
                   "steps": 3_200, "quanta": 200},
                  {"block_steps_run": 29_632, "block_steps_offered": 44_160,
                   "steps": 29_632, "quanta": 1_380})
    assert block_steps_run_share.read(run) == pytest.approx(
        26_432 / 37_760)
    # every block ran to its end
    full = _record({"block_steps_run": 64, "block_steps_offered": 64},
                   {"block_steps_run": 6_464, "block_steps_offered": 6_464})
    assert block_steps_run_share.read(full) == 1.0


@pytest.mark.parametrize("run", [
    # a parent commit: steps and quanta, neither counter
    _record({"steps": 0, "quanta": 0}, {"steps": 64, "quanta": 2}),
    # one snapshot of the two lacks them
    _record({"steps": 0}, {"block_steps_run": 3, "block_steps_offered": 32}),
    # no block in the window
    _record({"block_steps_run": 7, "block_steps_offered": 32},
            {"block_steps_run": 7, "block_steps_offered": 32}),
    _record({}, {}), {"serve": None}, {}],
    ids=["parent", "half", "no-block", "empty", "no-serve", "nothing"])
def test_the_reader_finds_nothing_and_does_not_raise(run):
    assert block_steps_run_share.read(run) is None
