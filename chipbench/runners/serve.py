"""Runner for mixes of kind ``serve``: ``serve.run`` of the paged LLM
deployment on one chip, streaming path, open loop.

One process, one event loop: each request is a task that sleeps until it
is due, calls ``handle.stream.remote_streaming`` and takes each token as
it arrives.  A request is timed from when it was DUE, so a stall charges
the requests behind it; how late the generator itself ran is reported.

Mix parameters beyond ``lib/traffic.py``'s::

    server            keyword arguments of LLMServer (num_slots, page_size,
                      max_seq_len, max_prompt_len, block_size, ...)
    config_overrides  TransformerConfig overrides (param_dtype, ...)
    max_concurrent_queries
    warm_waves        {"default": [waves], "<bucket>": [waves]}: the
                      prefill wave sizes warmed for each prompt bucket
    warm_concat       {"products": [[k, [waves]], ...], "exact": [[w, ...]]}:
                      the wave-size combinations whose first tokens the
                      engine joins in one fetch (all k-tuples of the given
                      waves, and the listed ones)
    warm_requests     requests sent one by one before the window
    trace_seconds     length of the traced part of the window
    reference         {"requests": n, "max_total_tokens": t,
                       "worst_deficit_sigma": x}
"""

import asyncio
import os
import time

from chipbench.lib import cluster, configs, serve_views, traffic


def _buckets(lo: int, hi: int, smallest: int = 16) -> list:
    """The engine's prefill buckets (powers of two from 16) that prompt
    lengths ``lo..hi`` fall into."""
    out, b = [], smallest
    while True:
        if b >= lo:
            out.append(b)
        if b >= hi:
            return out
        b *= 2


def _warm_pairs(mix: dict) -> list:
    spec = mix["prompt_len"]
    waves = mix["warm_waves"]
    return [(b, w) for b in _buckets(spec["min"], spec["max"])
            for w in waves.get(str(b), waves["default"])]


async def _one(handle, worker, req, rec, t0, aget):
    await asyncio.sleep(max(0.0, t0 + req["due_s"] - time.perf_counter()))
    rec["sent"] = time.perf_counter() - t0
    try:
        gen = handle.stream.remote_streaming(
            {"prompt": req["prompt"],
             "max_new_tokens": req["max_new_tokens"]})
        async for ref in gen:
            item = await aget(worker, ref, timeout=300.0)
            now = time.perf_counter() - t0
            if "token" in item:
                rec["token_t"].append(now)
                rec["tokens"].append(item["token"])
            else:
                rec["summary"] = item
        rec["done"] = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 -- counted as a failed request
        rec["error"] = f"{type(e).__name__}: {e}"[:300]


async def _drive(handle, worker, schedule, seconds, on_trace):
    """Offer the whole schedule, then wait until every request due in the
    window has finished (or 120 s have passed: then it has failed)."""
    from ray_tpu.serve.handle import _aget
    recs = [{"due": r["due_s"], "prompt_len": len(r["prompt"]),
             "asked": r["max_new_tokens"], "token_t": [], "tokens": []}
            for r in schedule]
    t0 = time.perf_counter()
    tasks = [asyncio.ensure_future(_one(handle, worker, r, rec, t0, _aget))
             for r, rec in zip(schedule, recs)]
    tracer = asyncio.ensure_future(on_trace(t0)) if on_trace else None
    _, pending = await asyncio.wait(tasks, timeout=seconds + 120.0)
    for task in pending:
        task.cancel()
    for rec in recs:
        if "done" not in rec and "error" not in rec:
            rec["error"] = "not finished 120 s after the window"
    if tracer is not None:
        await tracer
    return recs


def deploy(cell, config, mix, seed31, allow_cpu, say):
    """Start the cluster and the one replica, check where it runs, warm
    the cell's own shapes.  Returns ``(ray_tpu, handle, info)``; the
    caller shuts both down."""
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from chipbench.lib.replica import BenchLLMServer

    overrides = configs.model_overrides(config,
                                        mix.get("config_overrides", {}))
    server = dict(mix["server"], paged=True, seed=seed31,
                  config_overrides=overrides)
    ray_tpu = cluster.start_cluster(
        cell["chips"], int(mix.get("object_store_gb", 2) * 2**30),
        allow_cpu)
    try:
        # as serve.llm.build_app deploys LLMServer (serve/llm.py)
        num_tpus = 0 if allow_cpu and not ray_tpu.cluster_resources(
            ).get("TPU") else cell["chips"]
        app = deployment(
            BenchLLMServer, name=f"llm-{cell['config']}", num_replicas=1,
            max_concurrent_queries=mix["max_concurrent_queries"],
            ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
        ).bind(config["program"]["preset"], **server)
        handle = serve.run(app, name="chipbench")
        info = ray_tpu.get(handle.device_info.remote(), timeout=1100)
        pairs = _warm_pairs(mix)
        warm = ray_tpu.get(handle.bench_warm.remote(
            pairs, mix.get("warm_concat", {})), timeout=1100)
        say("replica", device=info["device"], paged_impl=info["paged_impl"],
            warm=warm, pairs=len(pairs))
        for n in range(mix["warm_requests"]):
            items = [ray_tpu.get(ref, timeout=300) for ref in
                     handle.stream.remote_streaming(
                         {"prompt": [1 + n] * 48, "max_new_tokens": 8})]
            if len(items) != 9:            # 8 tokens and the summary
                raise RuntimeError(f"warm request streamed {items}")
    except BaseException:
        serve.shutdown()
        ray_tpu.shutdown()
        raise
    return ray_tpu, handle, info


def run(ctx) -> dict:
    from ray_tpu import serve
    from ray_tpu.runtime.core_worker import get_global_worker

    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
    say, seconds = ctx["say"], ctx["seconds"]
    schedule = traffic.serve_schedule(mix, ctx["seed"], seconds,
                                      config["vocab_size"])
    say("schedule", **traffic.describe(schedule, seconds))
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(cluster.OUT_DIR, "trace", cell["name"])

    ray_tpu, handle, info = deploy(cell, config, mix, ctx["seed31"],
                                   ctx["allow_cpu"], say)
    try:
        vocab = config["vocab_size"]

        stats0 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts0 = ray_tpu.get(handle.bench_facts.remote(), timeout=60)
        traced = {}

        async def on_trace(t0):
            await asyncio.sleep(seconds * 0.25)
            loop = asyncio.get_running_loop()
            w0 = await loop.run_in_executor(None, lambda: ray_tpu.get(
                handle.bench_trace.remote("start", trace_dir), timeout=120))
            await asyncio.sleep(mix["trace_seconds"])
            w1 = await loop.run_in_executor(None, lambda: ray_tpu.get(
                handle.bench_trace.remote("stop"), timeout=300))
            traced["window_s"] = w1 - w0

        first_wall = time.time()
        recs = asyncio.run(_drive(
            handle, get_global_worker(), schedule, seconds,
            on_trace if trace_dir else None))
        stats1 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts1 = ray_tpu.get(handle.bench_facts.remote(facts0["compiles"]),
                             timeout=60)
        info1 = ray_tpu.get(handle.device_info.remote(), timeout=60)

        # the plain reference, outside the window, in the replica
        ref_spec = mix["reference"]
        good = [r for r in recs if "done" in r and
                r["prompt_len"] + len(r["tokens"])
                <= ref_spec["max_total_tokens"]][:ref_spec["requests"]]
        by_due = {r["due_s"]: r for r in schedule}
        samples = [{"prompt": by_due[r["due"]]["prompt"],
                    "tokens": r["tokens"]} for r in good]
        ref = ray_tpu.get(handle.bench_reference.remote(
            samples, float(config["rope_theta"]),
            float(config["rms_norm_eps"])), timeout=600) if samples else []
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    cluster.wait_gone(info["pid"])

    in_window = {k: facts1["compiles"][k] - facts0["compiles"][k]
                 for k in facts0["compiles"]}
    finished = [r for r in recs if "done" in r]
    failed = [r for r in recs if "error" in r]
    checks = {
        "platform_tpu": info["device"]["platform"] == "tpu",
        "device_count": info["device"]["count"] == cell["chips"],
        "paged_decode_is_pallas": info["paged_impl"] == "tpu",
        "no_failed_request": not failed,
        "token_counts_as_asked": all(
            len(r["tokens"]) == r["asked"] for r in finished),
        "tokens_in_vocabulary": all(
            isinstance(t, int) and 0 <= t < vocab
            for r in recs for t in r["tokens"]),
        "all_finished": len(finished) == len(recs),
        # a model program takes seconds to compile and half a second to
        # load from the cache: none may start inside the window.  The
        # engine joins several waves' first tokens with an eager
        # concatenate whose shape depends on the waves (PERF.md): the
        # common ones are warmed, a rare one costs ~0.09 s and is
        # tolerated up to 1% of the window
        "no_compile_in_window":
            facts1["longest_compile_s"] <= 0.5
            and in_window["compile_s"] <= 0.01 * seconds,
        "reference_checked": len(ref) >= min(1, ref_spec["requests"]),
        "reference_logits": all(
            m["worst_deficit_sigma"] <= ref_spec["worst_deficit_sigma"]
            for m in ref),
    }
    say("serve_done", requests=len(recs), finished=len(finished),
        failed=len(failed), errors=[r["error"] for r in failed][:3],
        compile_in_window=in_window,
        longest_compile_s=facts1["longest_compile_s"],
        compiled_names=facts1["compiled_names"],
        client=serve_views.client_summary(recs),
        stats0=stats0, stats1=stats1, reference=ref, checks=checks,
        load_end=facts1["load"], cache=[info1["compile_s"],
                                        info1["cache_hits"],
                                        info1["cache_misses"]])
    # each request as the client saw it, for whoever reads the log: due,
    # sent, first and last token (seconds from the window's start), tokens
    say("requests", rows=[
        [round(x, 4) for x in (r["due"], r.get("sent", -1.0),
                               r["token_t"][0], r["token_t"][-1])]
        + [len(r["token_t"])] for r in finished if r["token_t"]])
    return {
        "kind": "serve", "checks": checks,
        "attempted": sum("sent" in r for r in recs),
        "failed": len(failed),
        "device": {"platform": info["device"]["platform"],
                   "kind": info["device"]["kind"],
                   "count": info["device"]["count"],
                   "memory_peak_bytes": facts1["memory_peak_bytes"]},
        "first_measured_wall": first_wall,
        "chips": cell["chips"], "config": config, "mix": mix,
        "serve": {"requests": recs, "seconds": seconds,
                  "stats0": stats0, "stats1": stats1,
                  "num_slots": mix["server"]["num_slots"]},
        "trace_dir": trace_dir, "traced": traced or None,
    }
