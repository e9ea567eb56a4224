"""The program's own spans on the ``jax.profiler`` trace's clock, the
per-request phase stamps and the loop's time accounts (ISSUE 24).

The spans are JAX's ``TraceAnnotation`` (``_private/profiler.py span``):
written only while a profiler session is on, into the same ``.xplane.pb``
as the device's operations.  Here the traces are CPU traces: they show
which spans exist, how often and on which thread — never a time.
"""

import asyncio
import collections
import glob
import json
import os
import threading
import time

import pytest

import jax
import jax.numpy as jnp

ENGINE_KW = dict(num_slots=2, block_size=4, max_seq_len=64, page_size=8)


def _host_spans(trace_dir, prefixes=("engine.", "train")):
    """``{line: [(name, {arg: value}), ...]}`` of the program's spans in
    the newest trace under ``trace_dir``, by host thread line."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out[(plane.name, n, line.name)].append(
                        (ev.name, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def server():
    from ray_tpu.serve.llm import LLMServer
    srv = LLMServer("tiny", **ENGINE_KW)
    yield srv
    srv.engine.close()


def _settle(eng):
    """Wait until the loop thread has nothing in flight (the block it
    dispatched ahead of the last reply has been fetched too)."""
    deadline = time.monotonic() + 30
    seen = None
    while time.monotonic() < deadline:
        now = (eng.stats.quanta, eng.load_snapshot()["busy_slots"])
        if now == seen and now[1] == 0:
            return
        seen = now
        time.sleep(0.05)
    raise AssertionError("engine never went idle")


async def _reply(server, entry, n_tokens):
    req = {"prompt": [3, 1, 4, 1, 5, 9, 2, 6], "max_new_tokens": n_tokens}
    if entry == "call":
        return await server(req)
    items = [item async for item in server.stream(req)]
    assert [set(i) for i in items[:-1]] == [{"token"}] * n_tokens
    return items[-1]


@pytest.mark.parametrize("entry", ["call", "stream"])
@pytest.mark.parametrize("n_tokens", [1, 11])
def test_reply_splits_time_to_first_token(server, entry, n_tokens):
    """queue wait + prefill IS the time to the first token (no remainder),
    every part is non-negative, and a request that never decodes here
    never waited for a slot."""
    reply = asyncio.run(_reply(server, entry, n_tokens))
    q, p, w = (reply[k] for k in
               ("queue_wait_s", "prefill_s", "slot_wait_s"))
    assert q + p == reply["time_to_first_token_s"]
    assert q >= 0 and p > 0 and w >= 0
    assert reply["time_to_first_token_s"] <= reply["latency_s"]
    if n_tokens == 1:
        assert w == 0.0
    else:
        # installed after its first token was known, before it finished
        assert w <= reply["latency_s"] - reply["time_to_first_token_s"]


@pytest.mark.parametrize("entry", ["call", "stream"])
@pytest.mark.parametrize("n_tokens", [1, 11])
def test_reply_splits_time_after_first_token(server, entry, n_tokens):
    """stepping + prefill stall + block tail IS the time after the first
    token (ISSUE 41), on ``__call__``'s reply and ``.stream``'s summary;
    a request that ends at its first token has none of the three."""
    reply = asyncio.run(_reply(server, entry, n_tokens))
    parts = [reply[k] for k in
             ("stepping_s", "prefill_stall_s", "block_tail_s")]
    assert all(p >= 0.0 for p in parts)
    if n_tokens == 1:
        assert parts == [0.0, 0.0, 0.0]
    else:
        assert sum(parts) == pytest.approx(
            reply["latency_s"] - reply["time_to_first_token_s"], abs=1e-9)
        assert parts[1] == 0.0          # nobody else's prompt came
        assert parts[0] > 0.0 and parts[2] > 0.0


def test_engine_time_accounts_and_prefill_counters(server):
    """The loop thread's accounts only grow, the parts never exceed the
    whole, padded prefill tokens are at least the real ones, and one
    quantum is block_size steps offered and no more than that run."""
    eng = server.engine
    snaps = [eng.stats.snapshot(eng.num_slots)]
    for n in (1, 6, 9):
        asyncio.run(_reply(server, "call", n))
        snaps.append(eng.stats.snapshot(eng.num_slots))
        _settle(eng)                    # the loop goes to sleep in between
    accounts = ("loop_s", "idle_wait_s", "fetch_wait_s", "deliver_s")
    counters = ("quanta", "prefill_waves", "prefill_prompt_tokens",
                "prefill_padded_tokens", "steps", "prefills")
    for a, b in zip(snaps, snaps[1:]):
        assert all(b[k] >= a[k] for k in accounts + counters)
        assert b["prefill_waves"] > a["prefill_waves"]
        assert b["prefill_prompt_tokens"] - a["prefill_prompt_tokens"] == 8
    for s in snaps:
        assert s["loop_s"] >= (s["idle_wait_s"] + s["fetch_wait_s"]
                               + s["deliver_s"])
        assert s["prefill_padded_tokens"] >= s["prefill_prompt_tokens"]
        assert s["block_steps_offered"] == s["quanta"] * eng.block_size
        assert s["steps"] == s["block_steps_run"] <= s["block_steps_offered"]
    # a reply of n tokens is n - 1 decode steps, with nobody beside it
    assert [b["steps"] - a["steps"] for a, b in zip(snaps, snaps[1:])] == [
        0, 5, 8]
    last = snaps[-1]
    assert last["fetch_wait_s"] > 0 and last["deliver_s"] > 0
    assert last["idle_wait_s"] > 0      # and its sleeps are on the account
    json.dumps(last)                    # the /stats reply stays plain


def test_engine_spans_on_the_trace_clock(server, tmp_path):
    """A trace of a few quanta holds each phase's span once per phase,
    all on the engine's own thread; a trace taken while the engine has
    nothing to do holds none."""
    eng = server.engine
    asyncio.run(_reply(server, "call", 2))       # loop thread is up
    _settle(eng)
    before = eng.stats.snapshot(eng.num_slots)
    jax.profiler.start_trace(str(tmp_path / "busy"))
    try:
        asyncio.run(_reply(server, "call", 10))
        _settle(eng)
        asyncio.run(_reply(server, "call", 1))
        _settle(eng)
    finally:
        jax.profiler.stop_trace()
    after = eng.stats.snapshot(eng.num_slots)
    by_line = _host_spans(str(tmp_path / "busy"))
    assert len(by_line) == 1, f"engine spans on several threads: {by_line}"
    (line, spans), = by_line.items()
    count = collections.Counter(name for name, _ in spans)
    quanta = after["quanta"] - before["quanta"]
    assert quanta >= 2
    assert count["engine.fetch_block"] == quanta
    assert count["engine.deliver_block"] == quanta
    assert count["engine.dispatch_prefill"] == 2
    assert count["engine.deliver_prefill"] == 2
    assert count["engine.dispatch_block"] >= quanta
    assert count["engine.admit"] >= count["engine.dispatch_block"]
    assert count["engine.wait_work"] >= 1       # between the two requests
    assert count["engine.fetch_prefill"] == 2
    assert set(count) <= {"engine." + n for n in (
        "wait_work", "admit", "dispatch_import", "dispatch_prefill",
        "dispatch_block", "fetch_block", "deliver_block", "fetch_prefill",
        "deliver_prefill", "export")}
    # arguments are small scalars, known at the span's end
    prefill = [a for n, a in spans if n == "engine.dispatch_prefill"]
    assert all(a["waves"] == 1 and a["prompt_tokens"] == 8
               and a["padded_tokens"] == 16 for a in prefill)
    delivered = sum(a["tokens"] for n, a in spans
                    if n == "engine.deliver_block")
    assert delivered == 9               # 10 asked, the first from prefill
    # a block's fetch and its delivery carry one number, the quantum's,
    # and the fetch says where the interval since the last fetch went
    fetched = [a for n, a in spans if n == "engine.fetch_block"]
    numbers = [a["block"] for a in fetched]
    assert numbers == list(range(before["quanta"] + 1,
                                 after["quanta"] + 1))
    assert numbers == [a["block"] for n, a in spans
                       if n == "engine.deliver_block"]
    assert sum(a["waves"] for a in fetched) == 1    # the one that decoded
    assert all(a["rows"] >= 1 and 0.0 <= a["wave_ms"] <= a["interval_ms"]
               for a in fetched)
    assert all((a["wave_ms"] > 0.0) == (a["waves"] > 0) for a in fetched)
    assert 1e3 * (after["prefill_wave_s"] - before["prefill_wave_s"]) \
        >= sum(a["wave_ms"] for a in fetched) - 1e-2
    # not this (the test's) thread: its own marker lies on another line
    jax.profiler.start_trace(str(tmp_path / "idle"))
    try:
        with jax.profiler.TraceAnnotation("engine.not_the_engine"):
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    idle = _host_spans(str(tmp_path / "idle"))
    names = {name for spans in idle.values() for name, _ in spans}
    assert names == {"engine.not_the_engine"}
    assert list(idle)[0][:2] != line[:2]


def test_step_clock_spans_on_the_trace_clock(tmp_path):
    """Three clocked steps put three of each phase and three step
    markers on the loop's own thread; the no-op clock puts none."""
    from ray_tpu._private import step_stats

    def loop(clock, done):
        for _ in range(3):
            clock.begin()
            for name in ("batch", "grad_dispatch", "loss_fetch"):
                with clock.phase(name):
                    jnp.ones((4,)).sum().block_until_ready()
            clock.end()
        done.set()

    run = step_stats.start_run("spans-test")
    assert run is not None
    jax.profiler.start_trace(str(tmp_path / "steps"))
    try:
        done = threading.Event()
        t = threading.Thread(target=loop, args=(run.clock, done))
        t.start()
        t.join(60)
        assert done.is_set()
        loop(step_stats.NOOP_CLOCK, threading.Event())   # this thread
    finally:
        jax.profiler.stop_trace()
        summary = step_stats.end_run(run)
    assert summary["steps"] == 3
    assert set(summary["phase_ms"]) == {"batch", "grad_dispatch",
                                        "loss_fetch"}
    by_line = _host_spans(str(tmp_path / "steps"))
    assert len(by_line) == 1
    spans, = by_line.values()
    count = collections.Counter(name for name, _ in spans)
    assert count == {"train.batch": 3, "train.grad_dispatch": 3,
                     "train.loss_fetch": 3, "train_step": 3}
    assert sorted(a["step_num"] for n, a in spans
                  if n == "train_step") == [0, 1, 2]


def test_sharded_loop_is_cut_where_the_host_works(ray_start_regular):
    """The gang loop records its dispatches and its one wait under their
    own names, and no longer books an unfenced dispatch as device time."""
    from ray_tpu.air.config import RunConfig
    from ray_tpu.experimental import state
    from ray_tpu.train.sharded import (ShardedRunConfig, ShardedTrainer,
                                       ShardingConfig)

    tag = "t-sharded-phases"
    # the worker sees the eight CPU devices tests/conftest.py forces
    run = ShardedRunConfig(sharding=ShardingConfig(fsdp=2, tp=4),
                           model="tiny", num_workers=1, steps=3,
                           batch_per_worker=4, seq_len=32,
                           checkpoint_interval=3)
    result = ShardedTrainer(run, run_config=RunConfig(name=tag),
                            resources_per_worker={"CPU": 1}, tag=tag).fit()
    assert result.error is None, result.error
    assert result.metrics["summary"]["device"]["platform"] == "cpu"
    assert "peak_bytes_in_use" in result.metrics["summary"]

    def ledger():
        s = state.training_summary(tag)
        ranks = (s or {}).get("ranks") or {}
        return ranks.get(0) or ranks.get("0")

    deadline = time.monotonic() + 15
    while ledger() is None and time.monotonic() < deadline:
        time.sleep(0.1)
    phases = ledger()["phase_ms"]
    assert {"batch", "grad_dispatch", "grad_sync", "apply_dispatch",
            "loss_fetch", "checkpoint", "report"} <= set(phases)
    assert not {"device_compute", "optimizer", "host_dispatch"} & set(phases)
    assert ledger()["steps"] == 3


def test_loop_and_benchmark_count_the_same_operations():
    """One operations count: the loop's ``flops_per_token`` (the goodput
    ledger's MFU) and the benchmark's (``chipbench/lib/flops.py``, the
    ``mfu`` metric) agree at SmolLM2-360M's widths, sequence 1024."""
    from chipbench.lib import configs, flops
    from ray_tpu.models.configs import get_config

    published = configs.load_json("chipbench/configs/smollm2-360m.json")
    cfg = get_config(published["program"]["preset"],
                     **configs.model_overrides(published))
    ours = cfg.train_flops_per_token(1024)
    theirs = flops.train_flops_per_token(published, 1024)
    assert ours == pytest.approx(theirs, rel=0.01)


def test_sandbox_train_bench_counts_the_trainer_s_operations():
    """``benchmarks/sharded_train_bench.py``'s MFU column counts
    ``TransformerConfig.train_flops_per_token`` and nothing of its own:
    one FLOP count in the tree."""
    import importlib.util
    import os

    from ray_tpu.models.configs import get_config

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "sharded_train_bench.py")
    spec = importlib.util.spec_from_file_location("sharded_train_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    cfg = get_config("gpt-small")
    seq, tokens, ms, peak = 1024, 4 * 16 * 1024, 2000.0, 197e12
    want = cfg.train_flops_per_token(seq) * tokens / (ms / 1e3) / peak
    assert bench._mfu(cfg, seq, tokens, ms, peak) == round(want, 6)
    assert bench._mfu(cfg, seq, tokens, ms, 0.0) == 0.0
