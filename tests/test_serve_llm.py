"""Continuous-batching LLM engine + Serve LLM deployment.

The north-star serving path (BASELINE.md llama-3-8b row): requests are
admitted into free KV-cache slots mid-decode, so a slot-scheduled batch
must reproduce exactly what each request would generate alone
(greedy), interleave admissions, reuse slots, and ride a Serve replica.
CPU-sized model; the real-chip numbers live in benchmarks/serve_llm.py.
"""

import threading
import time

import pytest

import ray_tpu


def _tiny():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT

    cfg = get_config("tiny")
    model = GPT(cfg, decode=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def tiny_engine_parts():
    return _tiny()


def test_interleaved_admission_and_slot_reuse(tiny_engine_parts):
    """More requests than slots, submitted in two waves mid-decode: all
    complete, slots are reused, and occupancy shows real batching."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_engine_parts
    # block_size sized to the generations so occupancy measures overlap,
    # not block-tail junk
    eng = LLMEngine(cfg, params, num_slots=4, block_size=4)
    try:
        results = {}
        lock = threading.Lock()

        def go(rid, prompt, n):
            r = eng.submit(prompt, max_new_tokens=n, temperature=0.0)
            with lock:
                results[rid] = r

        threads = []
        # wave 1: 8 requests into 4 slots — the second 4 must wait for
        # evictions, proving admission happens mid-decode
        for i in range(8):
            t = threading.Thread(target=go,
                                 args=(i, [i + 1, i + 2], 6 + (i % 3)))
            t.start()
            threads.append(t)
        time.sleep(0.3)
        # wave 2 arrives while wave 1 decodes
        for i in range(8, 12):
            t = threading.Thread(target=go, args=(i, [i + 1], 4))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=180)
        assert sorted(results) == list(range(12))
        for i in range(8):
            assert len(results[i].tokens) == 6 + (i % 3)
            assert results[i].finish_reason == "length"
        for i in range(8, 12):
            assert len(results[i].tokens) == 4
        st = eng.stats.snapshot(eng.num_slots)
        assert st["requests_completed"] == 12
        assert st["prefills"] == 12
        # 12 requests through 4 slots: decode steps must have overlapped.
        # (Junk steps past eos / block tails count against occupancy, and
        # these generations are shorter than one block.)
        assert st["batch_occupancy"] > 0.25
    finally:
        eng.close()


def _disagg_app(**kw):
    """2-pool tiny app with fast-compile shapes shared by the disagg
    tests; kwargs override decode-pool / shared engine settings."""
    from ray_tpu import serve

    base = dict(preset="tiny", disaggregated=True, num_replicas=2,
                prefill_replicas=2, num_slots=4, block_size=4,
                page_size=8, max_concurrent_queries=32)
    base.update(kw)
    return serve.llm.build_app(**base)


def _stream_all(handle, requests, timeout=300):
    """Drive N concurrent streams through a DisaggHandle; returns
    (tokens, summary, retries) per request, in order."""
    import asyncio

    async def one(req):
        toks, summary, retries = [], None, 0
        async for item in handle.stream(req):
            if "token" in item:
                toks.append(item["token"])
            elif "retry" in item:
                retries = item["retry"]
            else:
                summary = item
        return toks, summary, retries

    async def main():
        return await asyncio.gather(*[one(r) for r in requests])

    return asyncio.run(asyncio.wait_for(main(), timeout=timeout))


def test_disagg_streaming_smoke(ray_start_regular, tiny_engine_parts):
    """Tier-1 disaggregated smoke (docs/serve_disagg.md): 2 prefill + 2
    decode replicas, 32 concurrent streaming requests.  Greedy tokens
    must match lone generation EXACTLY across the export -> transfer ->
    import path, prefill replicas must never decode, decode replicas
    must never prefill."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.generate import Generator
    from ray_tpu.serve.controller import REPLICA_PREFIX, SERVE_NAMESPACE

    cfg, params = tiny_engine_parts
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [50, 60], [9] * 17]
    lone = Generator(cfg, params)
    expect = {
        tuple(p): [int(t) for t in lone.generate(
            jnp.asarray([p], jnp.int32), max_new_tokens=6,
            temperature=0.0)[0]]
        for p in prompts
    }

    serve.start()
    serve.run(_disagg_app())
    try:
        handle = serve.llm.disagg_handle("tiny")
        reqs = [{"prompt": prompts[i % len(prompts)],
                 "max_new_tokens": 6, "temperature": 0.0}
                for i in range(32)]
        outs = _stream_all(handle, reqs)
        for req, (toks, summary, _) in zip(reqs, outs):
            assert toks == expect[tuple(req["prompt"])], (req, toks)
            assert summary["finish_reason"] == "length"
            assert summary["num_tokens"] == 6
        # pool separation: every prefill came from the prefill pool,
        # every decode step from the decode pool
        st = serve.status()
        roles = {"prefill": [], "decode": []}
        for name, s in st.items():
            role = name.rsplit("-", 1)[-1]
            for tag in s["replicas"]:
                a = ray_tpu.get_actor(REPLICA_PREFIX + tag,
                                      namespace=SERVE_NAMESPACE)
                roles[role].append(ray_tpu.get(
                    a.handle_request.remote("stats", (), {}), timeout=60))
        assert sum(r["prefills"] for r in roles["prefill"]) == 32
        assert sum(r["exports"] for r in roles["prefill"]) == 32
        assert all(r["steps"] == 0 for r in roles["prefill"])
        assert sum(r["imports"] for r in roles["decode"]) == 32
        assert all(r["prefills"] == 0 for r in roles["decode"])
        # the decode pool saw BOTH replicas (queue-depth p2c routing)
        assert sum(1 for r in roles["decode"] if r["imports"] > 0) == 2
        # handoffs are visible as HANDOFF timeline slices on both the
        # exporting and importing replicas' rows (docs/serve_disagg.md)
        from ray_tpu.experimental.state.api import timeline
        deadline = time.monotonic() + 30
        stages = set()
        while time.monotonic() < deadline and \
                stages != {"export", "import"}:
            stages = {e["args"]["stage"] for e in timeline()
                      if e.get("cat") == "handoff"}
            time.sleep(0.5)
        assert stages == {"export", "import"}, stages
    finally:
        serve.shutdown()


def test_disagg_prefill_death_after_handoff(ray_start_regular):
    """A prefill replica dying AFTER its handoff was imported is
    invisible: the stream completes entirely from the KV object, with
    no retry."""
    import asyncio

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import REPLICA_PREFIX, SERVE_NAMESPACE

    serve.start()
    serve.run(_disagg_app(prefill_replicas=1, num_replicas=1))
    try:
        handle = serve.llm.disagg_handle("tiny")

        async def run():
            toks, summary, retries = [], None, 0
            killed = False
            async for item in handle.stream(
                    {"prompt": [5, 6, 7], "max_new_tokens": 24,
                     "temperature": 0.0}):
                if "token" in item:
                    toks.append(item["token"])
                elif "retry" in item:
                    retries = item["retry"]
                else:
                    summary = item
                if len(toks) >= 3 and not killed:
                    # >= 2 decoded tokens: the handoff was imported;
                    # the prefill replica is now irrelevant
                    killed = True
                    st = serve.status()["llm-tiny-prefill"]
                    for tag in st["replicas"]:
                        a = ray_tpu.get_actor(REPLICA_PREFIX + tag,
                                              namespace=SERVE_NAMESPACE)
                        ray_tpu.kill(a)
            return toks, summary, retries, killed

        toks, summary, retries, killed = asyncio.run(
            asyncio.wait_for(run(), timeout=240))
        assert killed, "stream finished before the kill fired"
        assert retries == 0, "prefill death after handoff must be invisible"
        assert len(toks) == 24
        assert summary["finish_reason"] == "length"
    finally:
        serve.shutdown()


def test_disagg_decode_death_mid_stream(ray_start_regular):
    """Killing the decode replica mid-stream surfaces a retry marker
    and the stream still completes (re-prefill + resume: no duplicated
    tokens, greedy suffix identical)."""
    import asyncio

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import REPLICA_PREFIX, SERVE_NAMESPACE

    serve.start()
    serve.run(_disagg_app(prefill_replicas=1, num_replicas=2))
    try:
        handle = serve.llm.disagg_handle("tiny")
        probe = _stream_all(handle, [{"prompt": [5, 6, 7],
                                      "max_new_tokens": 24,
                                      "temperature": 0.0}])[0][0]

        async def run():
            toks, summary, retries = [], None, 0
            killed = False
            async for item in handle.stream(
                    {"prompt": [5, 6, 7], "max_new_tokens": 24,
                     "temperature": 0.0}):
                if "token" in item:
                    toks.append(item["token"])
                elif "retry" in item:
                    retries = item["retry"]
                else:
                    summary = item
                if len(toks) >= 3 and not killed:
                    killed = True
                    # kill the decode replica serving THIS stream (the
                    # one with an ongoing request)
                    st = serve.status()["llm-tiny-decode"]
                    for tag in st["replicas"]:
                        a = ray_tpu.get_actor(REPLICA_PREFIX + tag,
                                              namespace=SERVE_NAMESPACE)
                        m = ray_tpu.get(a.get_metrics.remote(),
                                        timeout=30)
                        if m["num_ongoing"] > 0:
                            ray_tpu.kill(a)
            return toks, summary, retries, killed

        toks, summary, retries, killed = asyncio.run(
            asyncio.wait_for(run(), timeout=240))
        assert killed, "stream finished before the kill fired"
        assert retries >= 1, "decode death must surface a retry marker"
        assert toks == probe, (toks, probe)   # resumed, not restarted
        assert summary["finish_reason"] == "length"
    finally:
        serve.shutdown()


def test_disagg_pool_full_rejection_requeues(ray_start_regular):
    """Import admission under a pool sized for ONE resident request:
    the second import FIFO-waits in the engine (pages free as the
    first completes — no polling, no wedge), and the third hits the
    import_queue_max cap and is REJECTED (typed, synchronous), then
    re-queued by the decode replica's retry loop until the queue
    drains.  All three requests complete."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import REPLICA_PREFIX, SERVE_NAMESPACE

    serve.start()
    # decode pool sized for exactly ONE request: prompt 3 + 96 new
    # tokens at page_size 8 -> 13 pages; pool = scratch + 13.  Wait
    # queue capped at ONE import, so a third concurrent request must
    # take the rejection path.
    serve.run(_disagg_app(prefill_replicas=1, num_replicas=1,
                          kv_pool_pages=14, import_queue_max=1,
                          prefill_server_kwargs={"kv_pool_pages": None,
                                                 "import_queue_max":
                                                     None}))
    try:
        handle = serve.llm.disagg_handle("tiny")
        req = {"prompt": [5, 6, 7], "max_new_tokens": 96,
               "temperature": 0.0}
        outs = [None, None, None]
        errs = []

        def drive(i, delay):
            try:
                time.sleep(delay)
                outs[i] = _stream_all(handle, [req])[0]
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=drive, args=(i, 0.8 * i))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errs, errs
        assert all(o is not None and len(o[0]) == 96 for o in outs), \
            [(o and len(o[0])) for o in outs]
        # the third stream's import was queue-cap-rejected at least
        # once while the first held the pool and the second the queue
        st = serve.status()["llm-tiny-decode"]
        rejects = 0
        for tag in st["replicas"]:
            a = ray_tpu.get_actor(REPLICA_PREFIX + tag,
                                  namespace=SERVE_NAMESPACE)
            s = ray_tpu.get(a.handle_request.remote("stats", (), {}),
                            timeout=60)
            rejects += s["import_rejects"]
        assert rejects >= 1, "no import was ever queue-cap-rejected"
    finally:
        serve.shutdown()


def test_disagg_handoff_quantize_numerics_gate(tiny_engine_parts):
    """``serve_handoff_quantize`` ships the cross-host KV handoff as
    int8 wire blocks (util/collective/quant.Int8Codec, ~3.9x smaller)
    and dequantizes before import.  The gate: greedy tokens must STILL
    match lone generation EXACTLY — per-block scaling keeps the KV
    error ~0.4% of blockmax, far under what flips a tiny-model argmax —
    and the prefill pool must account the bytes it did NOT ship on
    ray_tpu_serve_handoff_saved_bytes."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.generate import Generator

    cfg, params = tiny_engine_parts
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [50, 60], [9] * 17]
    lone = Generator(cfg, params)
    expect = {
        tuple(p): [int(t) for t in lone.generate(
            jnp.asarray([p], jnp.int32), max_new_tokens=6,
            temperature=0.0)[0]]
        for p in prompts
    }

    # the knob rides system_config so replica processes inherit it
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024,
                 system_config={"serve_handoff_quantize": True})
    try:
        serve.start()
        serve.run(_disagg_app(prefill_replicas=1, num_replicas=1))
        handle = serve.llm.disagg_handle("tiny")
        reqs = [{"prompt": prompts[i % len(prompts)],
                 "max_new_tokens": 6, "temperature": 0.0}
                for i in range(8)]
        outs = _stream_all(handle, reqs)
        for req, (toks, summary, _) in zip(reqs, outs):
            assert toks == expect[tuple(req["prompt"])], (req, toks)
            assert summary["finish_reason"] == "length"
        # the quantized wire actually carried the handoffs: saved bytes
        # (raw - encoded) accumulate on the prefill replica and flush
        # to the cluster metric plane
        from ray_tpu.experimental.state.api import list_metrics
        deadline = time.monotonic() + 60
        saved = 0.0
        while time.monotonic() < deadline and saved <= 0:
            saved = sum(
                r.get("value", 0.0) for r in
                list_metrics("ray_tpu_serve_handoff_saved_bytes"))
            if saved <= 0:
                time.sleep(0.5)
        assert saved > 0, "no handoff bytes were saved (codec never ran)"
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


@pytest.mark.slow
def test_serve_disagg_load_harness_1k():
    """The full >= 1k-connection closed-loop A/B (benchmarks/
    serve_disagg.py) with the MICROBENCH acceptance bars: p99 TTFT
    >= 2x better disaggregated, aggregate tokens/s within 10%, handoff
    p50 under one decode block's wall time, zero stream errors.
    ~10 min; tier-1 runs the fast smoke above instead."""
    from benchmarks.serve_disagg import run_ab

    rows = run_ab(connections=1000, new_tokens=96, duration_s=90.0)
    ab = rows[-1]
    assert ab["errors"] == 0
    assert ab["connections"] >= 1000
    assert ab["ttft_p99_ratio"] >= 2.0, ab
    assert ab["tokens_per_s_ratio"] >= 0.9, ab
    assert ab["handoff_total_p50_ms"] < ab["decode_block_wall_p50_ms"], ab


def test_serve_llm_deployment(ray_start_regular):
    """End-to-end: a Serve replica owning an engine serves ≥8 concurrent
    requests through the handle with interleaved admission."""
    from ray_tpu import serve

    serve.start()
    app = serve.llm.build_app(preset="tiny", num_slots=4,
                              max_concurrent_queries=32)
    handle = serve.run(app, name="llm")
    try:
        refs = [handle.remote({"prompt": [i + 1, i + 2],
                               "max_new_tokens": 5 + (i % 4)})
                for i in range(10)]
        outs = ray_tpu.get(refs, timeout=300)
        for i, out in enumerate(outs):
            assert len(out["tokens"]) == 5 + (i % 4)
            assert out["prompt_len"] == 2
            assert out["latency_s"] > 0
    finally:
        serve.shutdown()
