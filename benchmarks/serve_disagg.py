"""Disaggregated prefill/decode serving load harness (docs/serve_disagg.md).

The MICROBENCH `serve_disagg` section: an interleaved same-box A/B of
colocated vs disaggregated LLM serving at EQUAL chip count under a
bimodal saturation mix, sustaining >= 1k concurrent streaming
connections through the real Serve stack (controller, replicas,
streaming generators, transfer-plane KV handoff).

  - colocated arm: 2 paged replicas, each prefilling AND decoding
    (the strongest single-pool baseline: slotless prefill-ahead, PR 4).
  - disaggregated arm: 1 prefill replica + 1 decode replica with 2x the
    per-replica slots (equal aggregate decode slots, equal replica
    count), KV handoffs shipped via ray_tpu.put / the PR 5 pull engine.

Why disaggregation wins p99 TTFT at saturation: a colocated engine's
prefill-ahead stalls the moment the KV pool fills — a queued prompt
cannot prefill until a RESIDENT request completes, so tail TTFT is
bound by decode turnover.  A prefill-only engine frees a request's
pages at export, so its prefill throughput never waits on decode; TTFT
is bound by prefill compute alone.  Aggregate tokens/s must stay within
10% (equal decode slots, the handoff riding idle host cycles).

Measured per stream (client side): TTFT (submit -> first token),
inter-token latency, per-stream decode block wall; plus the handoff
stage latencies from the replicas' telemetry.  One JSON line per row;
collect_microbench.py ingests these into MICROBENCH.json and
serve_disagg_deltas.

Run:  python benchmarks/serve_disagg.py [--connections 1000]
          [--rounds 1] [--new-tokens 16] [--slots 16]
"""

import argparse
import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

try:
    from benchmarks._bench_util import percentiles as _percentiles
except ImportError:          # run as a script from benchmarks/
    from _bench_util import percentiles as _percentiles

SHORT_LEN = 16               # bimodal prompt mix: 75% short ...
LONG_LEN = 192               # ... 25% long (the TTFT-tail driver)
PAGE_SIZE = 16
MAX_SEQ = 256


def _requests(n, new_tokens, vocab=250):
    reqs = []
    for i in range(n):
        plen = LONG_LEN if i % 4 == 0 else SHORT_LEN
        prompt = [(i * 37 + j) % (vocab - 1) + 1 for j in range(plen)]
        reqs.append({"prompt": prompt, "max_new_tokens": new_tokens,
                     "temperature": 0.8})
    return reqs


class _StreamStats:
    __slots__ = ("t0", "ttft", "token_ts", "error", "retries")

    def __init__(self):
        self.t0 = 0.0
        self.ttft = None
        self.token_ts = []
        self.error = None
        self.retries = 0


async def _drive_colocated(handle, worker, reqs, connections,
                           duration_s, ramp_s):
    from ray_tpu.serve.handle import _aget

    async def one(req, st):
        st.t0 = time.monotonic()
        gen = handle.remote_streaming(req)
        async for ref in gen:
            item = await _aget(worker, ref, timeout=600.0)
            if "token" in item:
                now = time.monotonic()
                if st.ttft is None:
                    st.ttft = now - st.t0
                st.token_ts.append(now)

    return await _drive(reqs, one, connections, duration_s, ramp_s)


async def _drive_disagg(handle, reqs, connections, duration_s, ramp_s):
    async def one(req, st):
        st.t0 = time.monotonic()
        async for item in handle.stream(req):
            if "token" in item:
                now = time.monotonic()
                if st.ttft is None:
                    st.ttft = now - st.t0
                st.token_ts.append(now)
            elif "retry" in item:
                st.retries = item["retry"]

    return await _drive(reqs, one, connections, duration_s, ramp_s)


async def _drive(reqs, one, connections, duration_s, ramp_s):
    """Closed loop at constant concurrency: each of ``connections``
    logical clients streams requests back-to-back until the window
    closes — steady state, where BOTH arms' prefill and decode work
    overlap (a one-shot burst lets the disaggregated prefill pool go
    idle after the drain, understating its throughput).  Streams in
    flight at the deadline run to completion but only in-window tokens
    count."""
    stats_all = []
    t_end = time.monotonic() + ramp_s + duration_s

    async def conn_loop(i):
        k = i
        while time.monotonic() < t_end:
            st = _StreamStats()
            stats_all.append(st)
            try:
                await asyncio.wait_for(one(reqs[k % len(reqs)], st),
                                       timeout=600.0)
            except Exception as e:  # noqa: BLE001 - counted, not fatal
                st.error = f"{type(e).__name__}: {e}"
                await asyncio.sleep(0.5)   # no hot error spin
            k += connections

    tasks = []
    for i in range(connections):
        tasks.append(asyncio.ensure_future(conn_loop(i)))
        await asyncio.sleep(0.002)         # ~2 s submission spread
    await asyncio.gather(*tasks)
    return stats_all, t_end


def _summarize(name, stats, connections, block_size, w0, w1):
    """Steady-state stats over the measurement window [w0, w1]:
    latency percentiles from streams STARTED in-window, tokens/s from
    token arrivals in-window (ramp and drain excluded)."""
    errors = [s for s in stats if s.error is not None]
    ok = [s for s in stats if s.error is None and s.t0 >= w0]
    # all lists in SECONDS; _percentiles converts to ms
    ttfts = [s.ttft for s in ok if s.ttft is not None]
    itls = []
    block_walls = []
    tokens = 0
    for s in stats:
        if s.error is not None:
            continue
        tokens += sum(1 for ts in s.token_ts if w0 <= ts <= w1)
    for s in ok:
        for a, b in zip(s.token_ts, s.token_ts[1:]):
            itls.append(b - a)
        if len(s.token_ts) > 1:
            # decode wall split over the stream's block dispatches:
            # tokens arrive in per-block bursts, so (last - first) /
            # nblocks is one block's wall time as the client feels it
            nblocks = max(1, -(-(len(s.token_ts) - 1) // block_size))
            block_walls.append(
                (s.token_ts[-1] - s.token_ts[0]) / nblocks)
    t50, t99 = _percentiles(ttfts) if ttfts else (0.0, 0.0)
    i50, i99 = _percentiles(itls) if itls else (0.0, 0.0)
    b50, _ = _percentiles(block_walls) if block_walls else (0.0, 0.0)
    row = {
        "metric": f"serve_disagg_{name}",
        "connections": connections,
        "streams": len(stats),
        "measured_streams": len(ok),
        "errors": len(errors),
        "retries": sum(s.retries for s in stats),
        "ttft_p50_ms": round(t50, 1),
        "ttft_p99_ms": round(t99, 1),
        "itl_p50_ms": round(i50, 2),
        "itl_p99_ms": round(i99, 1),
        "block_wall_p50_ms": round(b50, 1),
        "tokens_per_s": round(tokens / max(w1 - w0, 1e-9), 1),
        "window_s": round(w1 - w0, 1),
    }
    if errors:
        row["first_error"] = errors[0].error
    return row


def _engine_stats():
    """Per-replica engine snapshots (occupancy is the decode-waste
    telltale: junk-stepped slots past eos / between installs)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import REPLICA_PREFIX, SERVE_NAMESPACE

    out = {}
    try:
        for name, s in serve.status().items():
            rows = []
            for tag in s["replicas"]:
                try:
                    a = ray_tpu.get_actor(REPLICA_PREFIX + tag,
                                          namespace=SERVE_NAMESPACE)
                    st = ray_tpu.get(
                        a.handle_request.remote("stats", (), {}),
                        timeout=30)
                    rows.append({k: st[k] for k in
                                 ("batch_occupancy", "prefills",
                                  "requests_completed", "exports",
                                  "imports", "import_rejects")})
                except Exception:
                    pass
            out[name] = rows
    except Exception:
        pass
    return out


def _handoff_summary():
    """p50 handoff stage latencies/bytes from the replicas' telemetry
    (flushed to GCS; docs/observability.md)."""
    time.sleep(2.0)          # let the per-process flushers publish
    from ray_tpu.experimental.state.api import list_metrics
    out = {}
    for r in list_metrics():
        if r["name"] == "ray_tpu_serve_handoff_ms" and r.get("count"):
            out[r["tags"].get("stage", "?") + "_p50_ms"] = r.get("p50")
        if r["name"] == "ray_tpu_serve_handoff_bytes" and r.get("count"):
            out.setdefault("bytes_p50", r.get("p50"))
    stages = ("export_gather_p50_ms", "export_put_p50_ms",
              "import_pull_p50_ms", "import_admit_p50_ms")
    if any(k in out for k in stages):
        out["total_p50_ms"] = round(
            sum(out.get(k) or 0.0 for k in stages), 2)
    return out


def run_arm(mode, connections=1000, new_tokens=16, slots=16,
            block_size=8, duration_s=30.0, ramp_s=12.0, replicas=2):
    """One A/B arm in a fresh cluster; returns its summary row.

    Equal chip count both arms: ``replicas`` colocated replicas at
    ``slots`` each, vs ``replicas/2`` prefill + ``replicas/2`` decode
    replicas with the decode engines at ``2*slots`` (a decode-only
    chip hosts the whole chip's KV/compute — equal AGGREGATE decode
    slots, equal replica count)."""
    import ray_tpu
    from ray_tpu import serve

    reqs = _requests(connections, new_tokens)
    ray_tpu.init(num_cpus=2 * replicas,
                 object_store_memory=512 * 1024 * 1024,
                 system_config={"actor_creation_timeout_s": 900.0})
    try:
        serve.start()
        common = dict(preset="tiny", page_size=PAGE_SIZE,
                      max_seq_len=MAX_SEQ, max_prompt_len=LONG_LEN + 8,
                      block_size=block_size,
                      max_concurrent_queries=2 * connections,
                      warmup_prompt_lens=[SHORT_LEN, LONG_LEN])
        if mode == "colocated":
            app = serve.llm.build_app(num_replicas=replicas,
                                      num_slots=slots, **common)
            handle = serve.run(app)
            from ray_tpu.runtime.core_worker import get_global_worker
            worker = get_global_worker()

            def drive(batch, conns, dur, ramp):
                return asyncio.run(_drive_colocated(
                    handle.stream, worker, batch, conns, dur, ramp))
        else:
            app = serve.llm.build_app(
                disaggregated=True, prefill_replicas=max(replicas // 2, 1),
                num_replicas=max(replicas // 2, 1),
                num_slots=2 * slots,
                prefill_server_kwargs={"num_slots": 2,
                                       "kv_pool_pages": 1024},
                **common)
            serve.run(app)
            handle = serve.llm.disagg_handle("tiny")
            handle.pool_full_timeout_s = 300.0

            def drive(batch, conns, dur, ramp):
                return asyncio.run(_drive_disagg(
                    handle, batch, conns, dur, ramp))

        # warm pass: lazily-compiled jit shapes (export/import page
        # buckets, burst fetch concats) must not pollute the timed run
        drive(reqs[:32], 32, 1.0, 0.0)
        t0 = time.monotonic()
        stats, t_end = drive(reqs, connections, duration_s, ramp_s)
        row = _summarize(mode, stats, connections, block_size,
                         t0 + ramp_s, t_end)
        if mode == "disaggregated":
            row["handoff"] = _handoff_summary()
        row["engines"] = _engine_stats()
        return row
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def run_ab(connections=1000, new_tokens=16, slots=16, block_size=8,
           rounds=1, replicas=2, duration_s=30.0):
    """Interleaved A/B; emits one JSON row per arm-round plus the
    aggregated comparison row, and returns all rows."""
    rows = []
    per_mode = {"colocated": [], "disaggregated": []}
    for _ in range(rounds):
        for mode in ("colocated", "disaggregated"):
            row = run_arm(mode, connections, new_tokens, slots,
                          block_size, duration_s=duration_s,
                          replicas=replicas)
            rows.append(row)
            per_mode[mode].append(row)
            print(json.dumps(row))
            sys.stdout.flush()

    def best(mode, key, lo=True):
        vals = [r[key] for r in per_mode[mode]]
        return min(vals) if lo else max(vals)

    handoff = next((r["handoff"] for r in per_mode["disaggregated"]
                    if r.get("handoff")), {})
    ab = {
        "metric": "serve_disagg_ab",
        "connections": connections,
        "ttft_p99_colocated_ms": best("colocated", "ttft_p99_ms"),
        "ttft_p99_disagg_ms": best("disaggregated", "ttft_p99_ms"),
        "ttft_p99_ratio": round(
            best("colocated", "ttft_p99_ms")
            / max(best("disaggregated", "ttft_p99_ms"), 1e-9), 2),
        "tokens_per_s_colocated": best("colocated", "tokens_per_s",
                                       lo=False),
        "tokens_per_s_disagg": best("disaggregated", "tokens_per_s",
                                    lo=False),
        "tokens_per_s_ratio": round(
            best("disaggregated", "tokens_per_s", lo=False)
            / max(best("colocated", "tokens_per_s", lo=False), 1e-9), 3),
        "handoff_total_p50_ms": handoff.get("total_p50_ms"),
        "decode_block_wall_p50_ms": best("disaggregated",
                                         "block_wall_p50_ms"),
        "errors": sum(r["errors"] for r in rows),
        "bars": "ttft_p99_ratio >= 2; tokens_per_s_ratio >= 0.9; "
                "handoff_total_p50_ms < decode_block_wall_p50_ms; "
                "errors == 0",
    }
    rows.append(ab)
    print(json.dumps(ab))
    sys.stdout.flush()
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--connections", type=int, default=1000)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--duration", type=float, default=30.0)
    args = ap.parse_args()
    run_ab(args.connections, args.new_tokens, args.slots,
           args.block_size, args.rounds, args.replicas, args.duration)


if __name__ == "__main__":
    main()
