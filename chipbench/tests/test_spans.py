"""``lib/spans.py`` and the eight readers built on it (ISSUE 24): on a
small trace recorded on a TPU v5e from this tree (``spans.xplane.pb``,
my chip run, PR 24), on ``mini.xplane.pb``, which has none of the names,
and on hand-made events.

What the recorded trace holds.  A whole step of either cell would cost
megabytes (every distinct device operation brings its HLO text), so the
programs are tiny stand-ins under the program's own names (``train_grad``:
the flash kernels forward and backward and a matmul; ``train_apply``;
``engine_decode_block``: a scan of two steps of the paged decode kernel;
``engine_prefill``), jitted as the program jits its own.  The kernels
are the program's, and the host spans are opened by the program's own
``StepClock`` (three steps, 1 ms of sleep in ``batch`` and ``report``)
and the engine's own ``_Phase`` on a second thread (two quanta, the
first with a prefill).  Python tracer off."""

import importlib
import os

import pytest

from chipbench.lib import spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = ("decode_step_device_ms", "prefill_time_share",
               "slot_wait_mean_ms", "engine_queue_wait_p95_ms",
               "engine_host_share", "grad_device_ms", "apply_device_ms",
               "flash_bwd_time_share")


def _reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}").read


@pytest.fixture(scope="module")
def recorded():
    return spans.reduce_spans(os.path.join(HERE, "spans.xplane.pb"))


def test_programs_are_found_by_their_own_names(recorded):
    mods = recorded["modules"]
    assert {"train_grad", "train_apply", "engine_decode_block",
            "engine_prefill"} <= set(mods)
    assert mods["train_grad"]["count"] == 3
    assert mods["train_apply"]["count"] == 3
    assert mods["engine_decode_block"]["count"] == 2
    assert mods["engine_prefill"]["count"] == 1
    assert all(m["total_s"] > 0 for m in mods.values())
    # the forward and backward pass outweigh the optimizer's update
    assert mods["train_grad"]["total_s"] > mods["train_apply"]["total_s"]


def test_host_spans_are_counted_once_per_phase(recorded):
    host = recorded["host"]
    for name in ("train.batch", "train.grad_dispatch",
                 "train.apply_dispatch", "train.loss_fetch",
                 "train.report", "train_step"):
        assert host[name]["count"] == 3, name
    assert host["engine.fetch_block"]["count"] == \
        host["engine.deliver_block"]["count"] == 2
    assert host["engine.dispatch_prefill"]["count"] == 1
    # the loop's thread and the engine's: two lines, no more
    assert len(recorded["host_lines"]) == 2
    # the step marker holds its phases
    assert host["train_step"]["total_s"] >= sum(
        host[n]["total_s"] for n in host if n.startswith("train."))


def test_idle_time_lies_under_the_programs_spans(recorded):
    idle = recorded["idle"]
    assert idle["gaps"] >= 3 and idle["total_s"] > 0
    assert sum(idle["by_span"].values()) == pytest.approx(
        idle["total_s"], rel=1e-9)
    # the 1 ms sleeps of `batch` and `report` leave the device idle
    assert idle["by_span"]["train.batch"] > 0.002
    assert idle["by_span"]["train.report"] > 0.002
    assert 0.5 < idle["attributed_share"] <= 1.0


def test_kernels_carry_their_own_names(recorded):
    red = trace.reduce_trace(os.path.join(HERE, "spans.xplane.pb"))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                 "paged_attention_decode"):
        assert trace.share_of_busy(red, name) is not None, name
    run = {"trace": red}
    assert 0 < _reader("flash_bwd_time_share")(run) < 1


def test_readers_on_the_recorded_trace():
    path = os.path.join(HERE, "spans.xplane.pb")
    run = {"spans": spans.reduce_spans(path),
           "trace": trace.reduce_trace(path), "traced": {"steps": 3}}
    mods = run["spans"]["modules"]
    assert _reader("grad_device_ms")(run) == pytest.approx(
        1e3 * mods["train_grad"]["total_s"] / 3)
    assert _reader("apply_device_ms")(run) == pytest.approx(
        1e3 * mods["train_apply"]["total_s"] / 3)
    # one layer here: the paged kernel runs once a decode step
    run["config"] = {"num_hidden_layers": 1}
    steps = run["spans"]["kernel_runs"]["paged_attention_decode"]
    assert steps == mods["engine_decode_block"]["count"] * 2   # blocks of 2
    assert _reader("decode_step_device_ms")(run) == pytest.approx(
        1e3 * mods["engine_decode_block"]["total_s"] / steps)
    share = _reader("prefill_time_share")(run)
    assert share == pytest.approx(
        mods["engine_prefill"]["total_s"] / run["trace"]["busy_s"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_where_the_names_are_missing(name):
    """A parent commit's trace (``mini.xplane.pb``: no named program, no
    span), replies and counters without the new keys, and no trace at
    all: None each time, never an exception."""
    read = _reader(name)
    old = {"spans": spans.reduce_spans(os.path.join(HERE,
                                                   "mini.xplane.pb")),
           "trace": trace.reduce_trace(os.path.join(HERE,
                                                    "mini.xplane.pb")),
           "traced": {"steps": 3, "window_s": 1.0},
           "config": {"num_hidden_layers": 32},
           "serve": {"requests": [
               {"done": 1.0, "summary": {"time_to_first_token_s": 0.5}},
               {"error": "x"}],
               "stats0": {"steps": 0}, "stats1": {"steps": 64}}}
    assert read(old) is None
    assert read({}) is None
    assert read({"trace_dir": "/nonexistent", "trace": {},
                 "traced": None, "serve": None}) is None


def test_replies_and_counters_feed_the_scheduler_metrics():
    reqs = [{"done": 1.0, "summary": {"queue_wait_s": q, "prefill_s": 1.0,
                                      "slot_wait_s": w}}
            for q, w in ((0.1, 0.0), (0.2, 0.01), (0.3, 0.02), (2.0, 0.05))]
    run = {"serve": {"requests": reqs + [{"error": "lost"}],
                     "stats0": {"loop_s": 10.0, "idle_wait_s": 4.0,
                                "fetch_wait_s": 5.0},
                     "stats1": {"loop_s": 30.0, "idle_wait_s": 4.0,
                                "fetch_wait_s": 24.0}}}
    assert _reader("slot_wait_mean_ms")(run) == pytest.approx(20.0)
    assert _reader("engine_queue_wait_p95_ms")(run) == pytest.approx(
        1e3 * (0.3 + (2.0 - 0.3) * 0.85))
    assert _reader("engine_host_share")(run) == pytest.approx(
        1 - 19.0 / 20.0)


def test_gaps_are_shared_out_among_the_spans_over_them():
    spans_ = [(0, 100, "engine.fetch_block"),
              (100, 130, "engine.deliver_block"),
              (200, 300, "engine.admit")]
    by = spans._attribute([(50, 150), (180, 190), (250, 400)], spans_)
    assert by == {"engine.fetch_block": 50, "engine.deliver_block": 30,
                  "engine.admit": 50, "unattributed": 20 + 10 + 100}
    assert spans._attribute([(0, 10)], []) == {"unattributed": 10}


def test_module_names_drop_the_jit_prefix_and_the_run_id():
    assert spans.module_name("jit_train_grad(1234567)") == "train_grad"
    assert spans.module_name("jit_engine_decode_block") == \
        "engine_decode_block"
    assert spans.module_name("train_apply(9)") == "train_apply"
