"""The trace reduction, on one small trace recorded on a TPU v5e
(``mini.xplane.pb``: three calls of a jitted flash-attention + matmul,
2 ms of host sleep between them; my chip run, PR 23) and on hand-made
events."""

import os

import pytest

from chipbench.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(os.path.join(HERE, "mini.xplane.pb"))


def test_finds_the_device_plane_and_its_ops(reduced):
    assert reduced["devices"] == 1
    assert "XLA Ops" in reduced["planes"]["/device:TPU:0"]


def test_busy_is_the_union_of_op_intervals(reduced):
    # as read by hand from the recorded file: 47.6 us busy in a 6.9 ms span
    assert reduced["busy_s"] == pytest.approx(4.7591e-05, rel=1e-6)
    assert reduced["span_s"] == pytest.approx(0.006916169, rel=1e-6)
    assert sum(reduced["self_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)


def test_kernel_is_found_by_its_own_name_only(reduced):
    # the Pallas call is a custom-call named after the jitted function
    share = trace.share_of_busy(reduced, r"^f\.\d+ = .* custom-call$")
    assert share == pytest.approx(3.641e-05 / 4.7591e-05, rel=1e-3)
    assert trace.share_of_busy(reduced, r"no_such_kernel") is None


def test_gaps_are_named_by_the_host_event_over_them(reduced):
    name, seconds = reduced["gaps"][0]
    assert name == "$time sleep" and seconds > 0.004
    bd = trace.breakdown(reduced)
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 0


def test_self_time_does_not_count_children_twice():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (50, 60, "c"), (200, 210, "a")]
    assert trace._self_times(events) == {
        "while": 30, "a": 30, "b": 40, "c": 10}
    assert trace._union([(0, 100), (10, 30), (200, 210), (205, 220)]) == (
        120, [[0, 100], [200, 220]])


def test_short_name_drops_operands():
    line = ("%slice.86 = bf16[33,15,64]{2,1,0:T(8,128)(2,1)S(1)} "
            "slice(bf16[33,15,128]{2,1,0} %attn._decode_attend_paged.10), "
            "slice={[0:33]}")
    assert trace.short_name(line) == "slice.86 = bf16[33,15,64] slice"
    tup = ("%attn._train_attend.45 = (bf16[240,1024,64]{2,1,0:T(8,128)}, "
           "bf16[240,1024,64]{2,1,0}) custom-call(bf16[240,1024,64]{2,1,0} "
           "%bitcast.458), custom_call_target=\"tpu_custom_call\"")
    assert trace.short_name(tup) == (
        "attn._train_attend.45 = (bf16[240,1024,64], bf16[240,1024,64]) "
        "custom-call")
    assert trace.short_name("$time sleep") == "$time sleep"
