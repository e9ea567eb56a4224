"""Training performance plane (_private/step_stats.py,
docs/observability.md): step clock + goodput ledger units, the GCS step
table's straggler detection and retention, profiler line-stable keys,
gang profile merging, the daemon-spawn connect retry, and the 2/4-rank
gang end-to-end paths (timeline slices, training_summary, chaos
straggler)."""

import json
import threading
import time

import pytest

from ray_tpu._private import step_stats as sst
from ray_tpu._private.config import CONFIG


def _wait_for(pred, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------- units
def test_step_clock_and_goodput_ledger():
    """Phases cut by the clock land in the step; out-of-step phases in
    the ledger; the summary's buckets + MFU arithmetic are exact."""
    run = sst.start_run("unit-run", group="g", rank=0, world=1,
                        flops_per_token=1000.0, peak_flops=1e6)
    assert run is not None
    clock = sst.step_clock()
    for _ in range(4):
        clock.begin()
        with clock.phase("data_wait"):
            time.sleep(0.001)
        with clock.phase("host_dispatch"):
            time.sleep(0.003)
        clock.end(tokens=50)
    # a checkpoint between steps counts in the ledger, not a step
    sst.record_phase("checkpoint", 25.0)
    summary = sst.end_run(run)
    assert summary["steps"] == 4 and summary["tokens"] == 200
    assert summary["phase_ms"]["checkpoint"] == 25.0
    assert summary["phase_ms"]["host_dispatch"] >= 4 * 3.0
    assert summary["productive_ms"] > 0
    assert 0.0 < summary["goodput"] <= 1.0
    # mfu = fpt * tokens / productive_s / peak, exactly
    expect = 1000.0 * 200 / (summary["productive_ms"] / 1e3) / 1e6
    assert summary["mfu"] == pytest.approx(expect, rel=1e-3)
    # ledger time buckets cover the wall clock (idle absorbs the rest)
    parts = (summary["init_ms"] + summary["compile_ms"]
             + summary["productive_ms"] + summary["idle_ms"])
    assert parts <= summary["wall_ms"] * 1.01 + 26.0


def test_kill_switch_hands_out_noop_clock(monkeypatch):
    monkeypatch.setenv("RAY_TPU_STEP_STATS", "0")
    assert not sst.enabled()
    assert sst.start_run("killed") is None
    clock = sst.step_clock()
    assert clock is sst.NOOP_CLOCK
    clock.begin()
    with clock.phase("host_dispatch"):
        pass
    assert clock.end() is None
    sst.record_phase("checkpoint", 1.0)   # cheap no-op, not a crash
    monkeypatch.delenv("RAY_TPU_STEP_STATS")
    assert sst.enabled()


def test_begin_auto_finalizes_open_step():
    """A loop that only calls begin() still records every step."""
    run = sst.start_run("unit-auto")
    clock = sst.step_clock()
    for _ in range(3):
        clock.begin()
        with clock.phase("host_dispatch"):
            pass
    summary = sst.end_run(run)   # end_run closes the last open step
    assert summary["steps"] == 3


def test_step_report_sink_batches_and_survives_outage():
    """Reports buffer off the step path and a sink failure re-queues
    bounded instead of dropping or growing without bound."""
    shipped = []
    fail = {"on": True}

    def sink(reports):
        if fail["on"]:
            raise ConnectionError("gcs away")
        shipped.extend(reports)

    run = sst.start_run("unit-sink", sink=sink, meta={"pid": 1})
    clock = sst.step_clock()
    for _ in range(5):
        clock.begin()
        clock.end()
    run.flush()         # sink down: re-queued
    assert not shipped
    fail["on"] = False
    summary = sst.end_run(run)   # close flushes + pushes the summary
    steps = [r for r in shipped if "step" in r]
    assert len(steps) == 5
    assert steps[0]["meta"]["pid"] == 1      # rank meta rides the first
    assert all("meta" not in r for r in steps[1:])
    assert any("summary" in r for r in shipped)
    assert summary["steps"] == 5


# ------------------------------------------------------- GCS step table
def _reports(run, step, ms_by_rank, world=None, phases=None):
    world = world or len(ms_by_rank)
    out = []
    for rank, ms in ms_by_rank.items():
        ph = dict(phases[rank]) if phases else {"host_dispatch": ms}
        out.append({"run": run, "group": "gg", "rank": rank,
                    "world": world, "step": step, "ts": time.time(),
                    "step_ms": ms, "phases": ph,
                    **({"meta": {"pid": rank}} if step == 0 else {})})
    return out


def test_straggler_detection_edge_triggers_and_names_phase():
    events = []
    tbl = sst.GcsStepStatsTable(
        emit=lambda sev, src, label, msg, **f:
        events.append((sev, label, f)))
    # step 0: healthy; steps 1-3: rank 2 +100ms in host_dispatch
    tbl.put(_reports("ru", 0, {0: 10.0, 1: 11.0, 2: 10.0, 3: 10.5}))
    for step in range(1, 4):
        tbl.put(_reports(
            "ru", step, {0: 10.0, 1: 11.0, 2: 110.0, 3: 10.5},
            phases={0: {"data_wait": 2.0, "host_dispatch": 8.0},
                    1: {"data_wait": 2.0, "host_dispatch": 9.0},
                    2: {"data_wait": 2.0, "host_dispatch": 108.0},
                    3: {"data_wait": 2.0, "host_dispatch": 8.5}}))
    strag = [e for e in events if e[1] == "TRAIN_STRAGGLER"]
    # edge-triggered: THREE straggling steps -> ONE event
    assert len(strag) == 1
    sev, _, fields = strag[0]
    assert sev == "WARNING"
    assert fields["rank"] == 2 and fields["run"] == "ru"
    assert fields["phase"] == "host_dispatch"
    assert fields["overshoot_ms"] > 50
    # recovery re-arms the trigger
    tbl.put(_reports("ru", 4, {0: 10.0, 1: 11.0, 2: 10.0, 3: 10.5}))
    tbl.put(_reports("ru", 5, {0: 10.0, 1: 11.0, 2: 120.0, 3: 10.5}))
    strag = [e for e in events if e[1] == "TRAIN_STRAGGLER"]
    assert len(strag) == 2
    # the run row names the live straggler set
    runs = tbl.list_runs()
    assert runs[0]["straggling"] == {2: True}
    assert runs[0]["skew"], "per-step skew must be recorded"


def test_two_rank_gang_records_skew_but_never_flags():
    events = []
    tbl = sst.GcsStepStatsTable(
        emit=lambda *a, **f: events.append(a))
    for step in range(3):
        tbl.put(_reports("r2", step, {0: 10.0, 1: 150.0}))
    assert not events, "2-rank gangs can't name a straggler"
    assert tbl.list_runs()[0]["skew"][0]["skew_ms"] >= 69.0


def test_step_table_retention_bounds():
    tbl = sst.GcsStepStatsTable(max_runs=3, max_steps=8)
    for r in range(6):
        for step in range(20):
            tbl.put(_reports(f"run{r}", step, {0: 1.0, 1: 1.0}))
    st = tbl.stats()
    assert st["runs"] <= 3
    assert st["steps_retained"] <= 3 * 8
    # oldest runs evicted first
    kept = {row["run"] for row in tbl.list_runs()}
    assert kept == {"run3", "run4", "run5"}
    # per-run steps keep the newest tail
    steps = tbl.steps("run5")
    assert len(steps) <= 8
    assert steps[-1]["step"] == 19
    # summaries survive and aggregate
    tbl.put([{"run": "run5", "rank": 0, "world": 2,
              "summary": {"goodput": 0.5, "mfu": 0.25, "tokens": 10,
                          "steps": 20, "tokens_per_s": 100.0}}])
    s = tbl.summary("run5")
    assert s["aggregate"]["mfu"] == 0.25


# ------------------------------------------------------- profiler plane
def test_profiler_keys_line_stable_with_leaf_detail():
    """Folded keys carry `co_name (file)` only — a hot line shifting by
    one line can't split counts across captures; the line numbers live
    in the reserved leaf-detail entry and the top_summary column."""
    from ray_tpu._private import profiler

    stop = threading.Event()

    def busy():
        while not stop.is_set():
            sum(range(500))

    t = threading.Thread(target=busy, daemon=True)
    t.start()
    try:
        counts = profiler.sample_folded(0.3, interval_s=0.005)
    finally:
        stop.set()
        t.join()
    clean, detail = profiler.split_leaf_detail(counts)
    assert clean, "sampler saw no stacks"
    for key in clean:
        for frame in key.split(";"):
            assert frame.endswith(")") and ":" not in \
                frame[frame.rfind("("):], f"line number leaked: {frame}"
    busy_leaves = [k.rsplit(";", 1)[-1] for k in clean
                   if "busy" in k]
    assert busy_leaves
    lines = detail.get(busy_leaves[0])
    assert lines and any(":" in ln for ln in lines), \
        "leaf line detail missing"
    top = profiler.top_summary(counts)
    assert "[" in top and ":" in top, "top_summary lost the line column"
    # folded_text never renders the reserved entry
    assert profiler.LEAF_LINES_KEY not in profiler.folded_text(counts)


def test_merged_profile_trace_keys_ranks_and_correlates_steps():
    from ray_tpu._private.profiler import LEAF_LINES_KEY

    per_rank = {
        0: {"main (a.py);hot (b.py)": 10,
            LEAF_LINES_KEY: {"hot (b.py)": {"b.py:7": 10}}},
        1: {"main (a.py);cold (c.py)": 4},
    }
    t0 = 1000.0
    task_rows = [{"task_id": "step-runx-r1", "events": [
        {"state": "STEP", "ts": t0 + 0.5, "dur_ms": 100.0, "step": 3,
         "trace_id": "step-runx:3", "phases": {"host_dispatch": 90.0}},
        {"state": "RUNNING", "ts": t0},   # non-STEP events are ignored
    ]}]
    steps = sst.step_trace_events(task_rows, window=(t0, t0 + 10))
    assert len(steps) == 1 and steps[0]["pid"] == "rank 1"
    assert steps[0]["args"]["trace_id"] == "step-runx:3"
    trace = sst.merged_profile_trace(per_rank, interval_s=0.01,
                                     t_start=t0, step_events=steps)
    pids = {ev["pid"] for ev in trace}
    assert pids == {"rank 0", "rank 1"}
    hot = next(ev for ev in trace if ev["name"] == "hot (b.py)")
    assert hot["dur"] == pytest.approx(10 * 0.01 * 1e6)
    assert hot["args"]["top_line"] == "b.py:7"
    assert hot["ts"] >= t0 * 1e6


# ------------------------------------------------- daemon connect retry
def test_gcs_client_retries_initial_connect():
    """The startup-race deflake: a client (raylet at spawn) created
    BEFORE the GCS accepts connections retries with backoff inside
    daemon_connect_retry_s instead of dying on the first refusal."""
    import socket
    from ray_tpu.runtime.gcs import GcsClient, GcsServer

    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    holder = {}

    def later():
        time.sleep(0.7)
        holder["server"] = GcsServer("127.0.0.1", port)

    t = threading.Thread(target=later, daemon=True)
    t.start()
    client = GcsClient(("127.0.0.1", port), connect_retry=True)
    try:
        assert client.call("list_nodes", timeout=10) == []
    finally:
        client.close()
        t.join(timeout=10)
        if "server" in holder:
            holder["server"].stop()
    # interactive clients keep fail-fast semantics: no retry by default
    # (fresh port: the stopped server's listener may linger on the old)
    s2 = socket.socket()
    s2.bind(("127.0.0.1", 0))
    dead_port = s2.getsockname()[1]
    s2.close()
    t0 = time.monotonic()
    with pytest.raises((ConnectionError, OSError)):
        GcsClient(("127.0.0.1", dead_port))
    assert time.monotonic() - t0 < 5.0, "default client must not retry"


# ------------------------------------------------------------ end to end
def test_gang_training_produces_slices_summary_and_matching_mfu(
        ray_start_regular):
    """THE acceptance path: a 2-rank gang drives the step clock; the
    run lands per-step phase slices in the timeline, a
    training_summary() whose MFU matches the loop's own wall-clock
    computation within 2%, and a step-table row carrying rank RPC
    metadata for gang profiling."""
    from ray_tpu.air import RunConfig, ScalingConfig, session
    from ray_tpu.experimental import state
    from ray_tpu.train import JaxConfig, JaxTrainer

    def loop(config):
        import time as _t
        from ray_tpu import train

        # an MFU near 0.2: the ledger rounds it to four decimals
        train.set_model_info(flops_per_token=1e8, peak_flops=1e9,
                             tokens_per_step=128)
        clock = train.step_clock()
        steps = 6
        # 65 ms a step: 2% of the run is ~8 ms, several times what the
        # scheduler adds between an ``end()`` and the next ``begin()``
        # on a loaded box (the ledger counts inside the steps only)
        t0 = _t.perf_counter()      # the first begin()
        for _ in range(steps):
            clock.begin()
            with clock.phase("data_wait"):
                _t.sleep(0.005)
            with clock.phase("host_dispatch"):
                _t.sleep(0.06)
            clock.end()
        dt = _t.perf_counter() - t0     # the last end()
        # the same run's MFU by hand, from the wall clock
        hand_mfu = 1e8 * (128 * steps / dt) / 1e9
        session.report({"hand_mfu": hand_mfu,
                        "rank": session.get_world_rank()})

    trainer = JaxTrainer(
        loop,
        jax_config=JaxConfig(init_distributed=False,
                             host_collective=False),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="stepstats-e2e"))
    result = trainer.fit()
    assert result.error is None, result.error
    hand_mfu = result.metrics["hand_mfu"]

    # the goodput ledger reached the GCS (end_run flushes before the
    # worker reports done, but ride out a slow box)
    def _summary_ready():
        s = state.training_summary("stepstats-e2e")
        return s and len(s.get("ranks") or {}) == 2
    _wait_for(_summary_ready, msg="training summary with both ranks")
    s = state.training_summary("stepstats-e2e")
    assert s["world"] == 2
    led0 = s["ranks"].get(0) or s["ranks"].get("0")
    assert led0["steps"] == 6
    assert led0["mfu"] == pytest.approx(hand_mfu, rel=0.02), \
        f"ledger mfu {led0['mfu']} vs by hand {hand_mfu}"
    assert 0 < led0["goodput"] <= 1.0
    assert led0["phase_ms"]["host_dispatch"] >= 6 * 60.0

    # step-table run row: both ranks with RPC metadata (profile --group)
    table = state.list_step_stats("stepstats-e2e")
    row = next(r for r in table["runs"]
               if r["group"] == "stepstats-e2e")
    assert row["world"] == 2 and row["steps_seen"] >= 6
    metas = row["ranks"]
    assert len(metas) == 2
    assert all(m.get("address") and m.get("worker_id")
               for m in metas.values())
    assert table.get("steps"), "per-step cross-rank records missing"
    assert row["skew"], "cross-rank skew not computed"

    # per-step phase slices in the Chrome trace (task events flush on
    # their own 500ms cadence)
    def _slices():
        evs = state.timeline()
        return any(e["cat"] == "train_step" for e in evs) and \
            any(e["cat"] == "train_phase"
                and e["name"] == "host_dispatch" for e in evs)
    _wait_for(_slices, msg="STEP timeline slices")
    evs = state.timeline()
    step_slices = [e for e in evs if e["cat"] == "train_step"]
    assert any(e["args"].get("trace_id", "").startswith("step-")
               for e in step_slices)


def test_chaos_pinned_rank_names_itself_as_straggler(ray_start_regular):
    """Chaos: pin one rank of a 4-rank gang with an injected per-step
    sleep — a TRAIN_STRAGGLER event must name that rank and the slow
    phase, and the step table stays inside its retention bounds."""
    from ray_tpu.air import RunConfig, ScalingConfig, session
    from ray_tpu.experimental import state
    from ray_tpu.train import JaxConfig, JaxTrainer

    def loop(config):
        import time as _t
        from ray_tpu import train

        rank = session.get_world_rank()
        clock = train.step_clock()
        for _ in range(5):
            clock.begin()
            with clock.phase("data_wait"):
                _t.sleep(0.001)
            with clock.phase("host_dispatch"):
                _t.sleep(0.005 + (0.1 if rank == 3 else 0.0))
            clock.end()
        session.report({"rank": rank})

    trainer = JaxTrainer(
        loop,
        jax_config=JaxConfig(init_distributed=False,
                             host_collective=False),
        scaling_config=ScalingConfig(num_workers=4),
        run_config=RunConfig(name="stepstats-chaos"))
    result = trainer.fit()
    assert result.error is None, result.error

    def _event():
        return state.list_cluster_events(type="TRAIN_STRAGGLER")
    _wait_for(lambda: _event(), msg="TRAIN_STRAGGLER event")
    evs = _event()
    ours = [e for e in evs if e.get("group") == "stepstats-chaos"
            or "stepstats-chaos" in str(e.get("run", ""))
            or e.get("rank") == 3]
    assert ours, f"no straggler event for this run in {evs}"
    ev = ours[-1]
    assert ev["rank"] == 3, f"wrong rank named: {ev}"
    assert ev["phase"] == "host_dispatch", f"wrong phase named: {ev}"
    assert ev["severity"] == "WARNING"
    assert ev["overshoot_ms"] >= 50
    # only the pinned rank is flagged, and retention invariants hold
    table = state.list_step_stats("stepstats-chaos")
    row = next(r for r in table["runs"]
               if r["group"] == "stepstats-chaos")
    assert set(row["straggling"]) <= {3, "3"}
    st = table["stats"]
    assert st["steps_retained"] <= st["max_runs"] * st["max_steps"]
    assert st["runs"] <= st["max_runs"]
