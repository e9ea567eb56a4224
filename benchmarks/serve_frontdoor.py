"""Serving front-door closed-loop harness (docs/serve_frontdoor.md).

The MICROBENCH `serve_frontdoor` section: a bimodal shared-prefix mix
sustained at constant concurrency against the full front-door stack —
SSE streaming ingress through the HTTP proxy, prefix-affinity routing
into a prefix-caching prefill pool, int8-quantized KV handoffs — with
the SLO plane doing the verdicts: every stream closes an ingress trace
root, and the row reports the per-pool (route) TTFT/TPOT good/violation
classification straight from ``trace_stats()``.

Connection split: real OS sockets cap the pure-HTTP arm (each SSE
stream holds a client fd AND a server fd against a 20k box limit), so
``http_conns`` of the ``connections`` logical clients stream over real
HTTP/SSE through the proxy and the rest drive the same DisaggHandle
router in-process (identical routing, prefix-affinity, retry and SLO
accounting paths — the HTTP arm adds only the aiohttp transport).  The
row carries both counts.

Prompt mix: 8 shared "system prompt" families of 2 pages each head
every prompt — 75% short (1 unique page) / 25% long (the TTFT-tail
driver) — so prefix-affinity has real sharing to exploit and the row's
``prefix_hit_rate`` must come out nonzero.

Quantized handoffs are ON for this harness (the `serve_handoff_quantize`
knob ships prefill->decode KV as int8 wire blocks): the row reports the
bytes the codec did NOT ship.

Run:  python benchmarks/serve_frontdoor.py [--connections 1000]
          [--duration 60] [--new-tokens 32] [--http-conns 256]
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

PAGE_SIZE = 16
SYS_PAGES = 2                # shared "system prompt" head: 2 full pages
SYS_LEN = SYS_PAGES * PAGE_SIZE
SHORT_LEN = SYS_LEN + PAGE_SIZE        # 75% of the mix
LONG_LEN = SYS_LEN + 10 * PAGE_SIZE    # 25%: the TTFT-tail driver
MAX_SEQ = 256
N_FAMILIES = 8


def _requests(n, new_tokens, vocab=250):
    """Bimodal mix with shared-prefix heads: every prompt opens with one
    of N_FAMILIES fixed 2-page families, then a per-request tail."""
    fams = [[(f * 131 + j) % (vocab - 1) + 1 for j in range(SYS_LEN)]
            for f in range(N_FAMILIES)]
    reqs = []
    for i in range(n):
        plen = LONG_LEN if i % 4 == 0 else SHORT_LEN
        tail = [(i * 37 + j) % (vocab - 1) + 1
                for j in range(plen - SYS_LEN)]
        reqs.append({"prompt": fams[i % N_FAMILIES] + tail,
                     "max_new_tokens": new_tokens, "temperature": 0.8})
    return reqs


class _StreamStats:
    __slots__ = ("t0", "ttft", "token_ts", "error", "retries", "via")

    def __init__(self, via="handle"):
        self.t0 = 0.0
        self.ttft = None
        self.token_ts = []
        self.error = None
        self.retries = 0
        self.via = via


async def _drive(reqs, handle, connections, http_conns, port,
                 duration_s, ramp_s):
    """Closed loop at constant concurrency (cf. serve_disagg._drive):
    the first ``http_conns`` clients stream SSE over real HTTP, the
    rest through the DisaggHandle router in-process."""
    import aiohttp

    url = f"http://127.0.0.1:{port}/-/disagg/tiny"
    stats_all = []
    t_end = time.monotonic() + ramp_s + duration_s

    async def one_handle(req, st):
        st.t0 = time.monotonic()
        async for item in handle.stream(req):
            if "token" in item:
                now = time.monotonic()
                if st.ttft is None:
                    st.ttft = now - st.t0
                st.token_ts.append(now)
            elif "retry" in item:
                st.retries = item["retry"]

    async def one_http(session, req, st):
        st.t0 = time.monotonic()
        async with session.post(url, json=req) as resp:
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}")
            async for raw in resp.content:
                line = raw.decode("utf-8").rstrip("\r\n")
                if not line.startswith("data:"):
                    continue
                d = json.loads(line[5:])
                if "token" in d:
                    now = time.monotonic()
                    if st.ttft is None:
                        st.ttft = now - st.t0
                    st.token_ts.append(now)
                elif "retry" in d:
                    st.retries = d["retry"]

    async def conn_loop(i, session):
        k = i
        while time.monotonic() < t_end:
            st = _StreamStats("http" if session is not None else "handle")
            stats_all.append(st)
            try:
                if session is not None:
                    await asyncio.wait_for(
                        one_http(session, reqs[k % len(reqs)], st),
                        timeout=900.0)
                else:
                    await asyncio.wait_for(
                        one_handle(reqs[k % len(reqs)], st),
                        timeout=900.0)
            except Exception as e:  # noqa: BLE001 - counted, not fatal
                st.error = f"{type(e).__name__}: {e}"
                await asyncio.sleep(0.5)   # no hot error spin
            k += connections

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        tasks = []
        for i in range(connections):
            tasks.append(asyncio.ensure_future(
                conn_loop(i, session if i < http_conns else None)))
            await asyncio.sleep(0.002)     # submission spread
        await asyncio.gather(*tasks)
    return stats_all, t_end


def _summarize(stats, connections, http_conns, w0, w1):
    errors = [s for s in stats if s.error is not None]
    ok = [s for s in stats if s.error is None and s.t0 >= w0]
    ttfts = [s.ttft for s in ok if s.ttft is not None]
    tpots = []
    tokens = 0
    for s in stats:
        if s.error is None:
            tokens += sum(1 for ts in s.token_ts if w0 <= ts <= w1)
    for s in ok:
        if len(s.token_ts) > 1:
            tpots.append((s.token_ts[-1] - s.token_ts[0])
                         / (len(s.token_ts) - 1))
    t50, t99 = _percentiles(ttfts) if ttfts else (0.0, 0.0)
    p50, p99 = _percentiles(tpots) if tpots else (0.0, 0.0)
    row = {
        "metric": "serve_frontdoor_closed_loop",
        "connections": connections,
        "http_connections": http_conns,
        "streams": len(stats),
        "measured_streams": len(ok),
        "errors": len(errors),
        "retries": sum(s.retries for s in stats),
        "ttft_p50_ms": round(t50, 1),
        "ttft_p99_ms": round(t99, 1),
        "tpot_p50_ms": round(p50, 2),
        "tpot_p99_ms": round(p99, 1),
        "tokens_per_s": round(tokens / max(w1 - w0, 1e-9), 1),
        "window_s": round(w1 - w0, 1),
    }
    if errors:
        row["first_error"] = errors[0].error
    return row


def _slo_by_route():
    """Per-pool TTFT/TPOT verdicts from the SLO plane: every stream
    above closed an ingress root (the proxy's for HTTP connections,
    the router's for in-process ones) on its pool's route."""
    from ray_tpu.experimental.state.api import trace_stats
    out = {}
    try:
        for route, slot in (trace_stats().get("slo_by_route")
                            or {}).items():
            out[route] = {k: slot.get(k, 0) for k in
                          ("good", "violation", "ttft_violation",
                           "tpot_violation")}
    except Exception:
        pass
    return out


def _prefix_counters():
    """Cluster-wide prefix-affinity outcomes, with the driver's own
    (unflushed) counters folded in — the DisaggHandle router lives in
    this process."""
    from ray_tpu._private import runtime_metrics as rtm
    from ray_tpu.experimental.state.api import list_metrics

    by_outcome = {"hit": 0.0, "miss": 0.0, "evicted": 0.0}
    try:
        for r in list_metrics("ray_tpu_serve_prefix_hit"):
            o = r.get("tags", {}).get("outcome")
            if o in by_outcome:
                by_outcome[o] += r.get("value", 0.0)
    except Exception:
        pass
    local = (rtm.snapshot().get("ray_tpu_serve_prefix_hit")
             or {}).get("values") or {}
    for tagjson, val in local.items():
        try:
            o = json.loads(tagjson).get("outcome")
        except (ValueError, AttributeError):
            continue
        if o in by_outcome:
            # the driver's flusher may have published already; take the
            # larger reading rather than double counting
            by_outcome[o] = max(by_outcome[o], val)
    return by_outcome


def _handoff_savings():
    """Bytes the int8 wire codec kept off the transfer plane."""
    from ray_tpu.experimental.state.api import list_metrics
    saved = wire = 0.0
    try:
        for r in list_metrics():
            if r["name"] == "ray_tpu_serve_handoff_saved_bytes":
                saved += r.get("value", 0.0)
            elif r["name"] == "ray_tpu_serve_handoff_bytes" \
                    and r.get("sum"):
                wire += r["sum"]
    except Exception:
        pass
    out = {"handoff_saved_bytes": int(saved)}
    if saved and wire:
        out["handoff_saved_frac"] = round(saved / (saved + wire), 3)
    return out


def run_frontdoor(connections=1000, new_tokens=32, duration_s=60.0,
                  ramp_s=15.0, http_conns=256, slots=32, port=18299,
                  quantize=True):
    """One closed-loop run; returns its rows ([summary])."""
    import ray_tpu
    from ray_tpu import serve

    http_conns = min(connections, http_conns)
    reqs = _requests(connections, new_tokens)
    ray_tpu.init(num_cpus=4, object_store_memory=512 * 1024 * 1024,
                 system_config={
                     "actor_creation_timeout_s": 900.0,
                     "serve_handoff_quantize": bool(quantize),
                 })
    try:
        serve.start(serve.HTTPOptions(port=port))
        serve.run(serve.llm.build_app(
            preset="tiny", disaggregated=True, prefill_replicas=1,
            num_replicas=1, num_slots=2 * slots,
            page_size=PAGE_SIZE, max_seq_len=MAX_SEQ,
            max_prompt_len=LONG_LEN + 8, block_size=8,
            max_concurrent_queries=2 * connections,
            warmup_prompt_lens=[SHORT_LEN, LONG_LEN],
            prefill_server_kwargs={
                "num_slots": 2, "kv_pool_pages": 1024,
                # room for all 8 families' heads plus churn
                "prefix_cache_pages": 8 * N_FAMILIES * SYS_PAGES,
            }))
        handle = serve.llm.disagg_handle("tiny")
        handle.pool_full_timeout_s = 600.0

        def drive(batch, conns, http_n, dur, ramp):
            return asyncio.run(_drive(batch, handle, conns, http_n,
                                      port, dur, ramp))

        # warm pass: jit shapes + the first advertisement round trip
        # (engine retain -> health-check advertise -> controller publish
        # -> router index) so the timed window measures steady state
        drive(reqs[:32], 32, 8, 4.0, 0.0)
        t0 = time.monotonic()
        stats, t_end = drive(reqs, connections, http_conns,
                             duration_s, ramp_s)
        row = _summarize(stats, connections, http_conns,
                         t0 + ramp_s, t_end)
        time.sleep(2.0)      # let the per-process flushers publish
        row["slo"] = _slo_by_route()
        pref = _prefix_counters()
        looked = sum(pref.values())
        row["prefix_hits"] = int(pref["hit"])
        row["prefix_misses"] = int(pref["miss"] + pref["evicted"])
        row["prefix_hit_rate"] = round(pref["hit"] / looked, 3) \
            if looked else 0.0
        row.update(_handoff_savings())
        row["bars"] = ("errors == 0; prefix_hit_rate > 0; "
                       "slo rows present for the decode route")
        print(json.dumps(row))
        sys.stdout.flush()
        return [row]
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--connections", type=int, default=1000)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--ramp", type=float, default=15.0)
    ap.add_argument("--http-conns", type=int, default=256)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--port", type=int, default=18299)
    ap.add_argument("--no-quantize", action="store_true")
    args = ap.parse_args()
    run_frontdoor(args.connections, args.new_tokens, args.duration,
                  args.ramp, args.http_conns, args.slots, args.port,
                  quantize=not args.no_quantize)


if __name__ == "__main__":
    main()
