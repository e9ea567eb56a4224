"""``moe_prefill_rows_run_share`` (ISSUE 56) on recorded snapshot pairs:
the pair rows the window's prefill programs ran their grouped expert
products over, of the pairs they were given; nothing, and no exception,
on a parent commit's record or in an engine that holds every expert."""

import pytest

from chipbench.metrics import moe_prefill_rows_run_share


def _record(stats0, stats1):
    return {"serve": {"stats0": stats0, "stats1": stats1}}


@pytest.mark.parametrize("ran,share", [
    ((92_160, 1_566_720), 0.15625),   # serve-docqa: 1,024 of 6,144 a chunk
    ((589_824, 10_027_008), 1.0),     # every slab of every program
    ((46_080, 783_360), 0.078125),    # a sixteenth held, 5,120 of 65,536
], ids=["an-eighth", "every-row", "a-sixteenth"])
def test_the_share_is_the_window_s_own(ran, share):
    run = _record({"moe_prefill_pairs": 589_824,
                   "moe_prefill_pairs_run": ran[0], "prefill_waves": 12},
                  {"moe_prefill_pairs": 10_027_008,
                   "moe_prefill_pairs_run": ran[1], "prefill_waves": 75})
    assert moe_prefill_rows_run_share.read(run) == pytest.approx(share)


@pytest.mark.parametrize("run", [
    # a parent commit: neither counter
    _record({"prefill_waves": 2}, {"prefill_waves": 9}),
    # one snapshot of the two lacks them
    _record({"prefill_waves": 2},
            {"moe_prefill_pairs": 6_144, "moe_prefill_pairs_run": 1_024}),
    # every expert held, or no prefill wave in the window: nothing given
    _record({"moe_prefill_pairs": 0, "moe_prefill_pairs_run": 0},
            {"moe_prefill_pairs": 0, "moe_prefill_pairs_run": 0}),
    _record({}, {}), {"serve": None}, {}],
    ids=["parent", "half", "nothing-given", "empty", "no-serve", "nothing"])
def test_the_reader_finds_nothing_and_does_not_raise(run):
    assert moe_prefill_rows_run_share.read(run) is None
