"""Runner for mixes of kind ``serve_mtp``: a ``serve_latent`` cell (see
``runners/serve_latent.py``, ``runners/serve_arch.py`` and
``chipbench/README-arch.md``) whose model is served DRAFTING with its own
multi-token-prediction module: a decode step verifies two positions a
row and yields one or two tokens (``chipbench/README-mtp.md``).  The
served path, the schedule, the rehearsal of the prefill programs, the
two reference samples and the record (``kind: "serve"``: every reader of
a serve record reads it) are ``serve_arch``'s and ``serve_hybrid``'s,
imported from them, with ``serve_latent``'s cap on a wave's tokens and
its refusal of a program that lacks the preset; what differs is the
replica, ``lib/replica_mtp.py MtpBenchLLMServer`` (its check runs verify
steps of two positions and the module behind them, and it samples at the
mix's ``server.temperature``), three invariants (both branches of the
acceptance ran in the window, a step gave between one and two tokens a
row, the expert layers' step at two positions a row is still the Pallas
kernel) and one comparison that needs both sides: the window's own
``drafts_accepted / drafts_proposed`` against the share the reference
says has to stand over the samples' positions (``window.accept_err``,
beside what a program that lets every draft stand would read).  ``deploy`` and ``run`` are written out here with that
replica, until a ``benchmark`` PR may fold the five runners of this
family.

Mix parameters: ``runners/serve_hybrid.py``'s, and::

    server.temperature        what every request is sampled at
    reference.faults_in       "first": the short sample alone runs the
                              wrong-on-purpose references and the
                              low-precision controls (each is a forward
                              of the reference at the long one's length)
"""

import asyncio
import os
import time

from chipbench.lib import cluster, serve_views, traffic
from chipbench.runners.serve import _buckets, _drive
from chipbench.runners.serve_arch import (cell_schedule, reference_samples,
                                          server_args)
from chipbench.runners.serve_hybrid import warmed_pairs
from chipbench.runners.serve_latent import _fits, _refuse_unless_known


def deploy(cell, config, mix, seed31, allow_cpu, say, pairs=None):
    """``runners/serve_hybrid.py deploy`` with ``MtpBenchLLMServer`` for
    the replica.  ``pairs`` are the prefill programs to warm; without
    them, every bucket at waves 1 to 4 (the sweep); either way those the
    engine can form under ``server.prefill_wave_tokens``."""
    _refuse_unless_known(config, mix)
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from chipbench.lib.replica_mtp import MtpBenchLLMServer

    server = server_args(config, mix, seed31)
    if pairs is None:
        spec = mix["prompt_len"]
        pairs = [(b, w) for b in _buckets(spec["min"], spec["max"])
                 for w in (1, 2, 4)]
    pairs = _fits(mix, pairs)
    t0 = time.perf_counter()
    ray_tpu = cluster.start_cluster(
        cell["chips"], int(mix.get("object_store_gb", 2) * 2**30),
        allow_cpu)
    t1 = time.perf_counter()
    try:
        num_tpus = 0 if allow_cpu and not ray_tpu.cluster_resources(
            ).get("TPU") else cell["chips"]
        app = deployment(
            MtpBenchLLMServer, name=f"llm-{cell['config']}",
            num_replicas=1,
            max_concurrent_queries=mix["max_concurrent_queries"],
            ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
        ).bind(config["program"]["preset"], **server)
        handle = serve.run(app, name="chipbench")
        info = ray_tpu.get(handle.device_info.remote(), timeout=1100)
        t2 = time.perf_counter()
        warm = ray_tpu.get(handle.bench_warm.remote(
            pairs, mix.get("warm_concat", {})), timeout=1100)
        took = info["phases"] = {"cluster_s": round(t1 - t0, 2),
                                 "replica_s": round(t2 - t1, 2),
                                 "warm_s": round(warm["seconds"], 2)}
        say("replica", device=info["device"], paged_impl=info["paged_impl"],
            moe_impl=info["moe_impl"], weights_seed=server["seed"],
            cluster_s=took["cluster_s"], replica_s=took["replica_s"],
            warm=warm, pairs=pairs)
        short = mix["prompt_len"]["min"]
        for n in range(mix["warm_requests"]):
            items = [ray_tpu.get(ref, timeout=300) for ref in
                     handle.stream.remote_streaming(
                         {"prompt": [1 + n] * short, "max_new_tokens": 8})]
            if len(items) != 9:            # 8 tokens and the summary
                raise RuntimeError(f"warm request streamed {items}")
    except BaseException:
        serve.shutdown()
        ray_tpu.shutdown()
        raise
    return ray_tpu, handle, info


def run(ctx) -> dict:
    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
    _refuse_unless_known(config, mix)    # a parent commit: out, at once
    from ray_tpu import serve
    from ray_tpu.runtime.core_worker import get_global_worker

    say, seconds = ctx["say"], ctx["seconds"]
    vocab = config["vocab_size"]
    schedule = cell_schedule(mix, ctx["seed"], seconds, vocab)
    say("schedule", **traffic.describe(schedule, seconds))
    ref_spec = mix["reference"]
    wants_long = any(
        len(r["prompt"]) + r["max_new_tokens"]
        >= ref_spec["long_min_context"] for r in schedule)
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(cluster.OUT_DIR, "trace", cell["name"])

    ray_tpu, handle, info = deploy(cell, config, mix, ctx["seed31"],
                                   ctx["allow_cpu"], say,
                                   warmed_pairs(schedule, mix))
    try:
        stats0 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts0 = ray_tpu.get(handle.bench_facts.remote(), timeout=60)
        traced = {}

        async def on_trace(t0):
            await asyncio.sleep(seconds * 0.25)
            loop = asyncio.get_running_loop()
            call = lambda ref, t: loop.run_in_executor(  # noqa: E731
                None, lambda: ray_tpu.get(ref, timeout=t))
            w0 = await call(handle.bench_trace.remote("start", trace_dir),
                            120)
            # the engine's counters over the traced interval, for the
            # readers that set them against the trace's device time
            traced["stats0"] = await call(handle.stats.remote(), 60)
            await asyncio.sleep(mix["trace_seconds"])
            traced["stats1"] = await call(handle.stats.remote(), 60)
            w1 = await call(handle.bench_trace.remote("stop"), 300)
            traced["window_s"] = w1 - w0

        first_wall = time.time()
        recs = asyncio.run(_drive(
            handle, get_global_worker(), schedule, seconds,
            on_trace if trace_dir else None))
        drained = time.time()
        stats1 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts1 = ray_tpu.get(handle.bench_facts.remote(facts0["compiles"]),
                             timeout=60)
        info1 = ray_tpu.get(handle.device_info.remote(), timeout=60)

        # the plain reference, outside the window, in the replica
        samples = reference_samples(recs, schedule, ref_spec)
        if ref_spec.get("faults_in") == "first":
            for s in samples[1:]:
                s["faults"] = []
        ref = ray_tpu.get(handle.bench_reference.remote(
            [{k: v for k, v in s.items() if k != "which"} for s in samples],
            config), timeout=900) if samples else []
        for s, m in zip(samples, ref):
            m["which"] = s["which"]
        phases = dict(info["phases"], window_s=seconds,
                      drained_s=round(drained - first_wall, 2),
                      reference_s=round(time.time() - drained, 2))
        say("after_window", drained_s=phases["drained_s"],
            reference_s=phases["reference_s"])
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    cluster.wait_gone(info["pid"])

    limits = ref_spec["limits"]
    inside = lambda x, lo, hi: (lo is None or x >= lo) and (  # noqa: E731
        hi is None or x <= hi)
    in_window = {k: facts1["compiles"][k] - facts0["compiles"][k]
                 for k in facts0["compiles"]}
    before = {(b, w): n for b, w, n in facts0["prefill_calls"]}
    pairs_used = [[b, w, n - before.get((b, w), 0)]
                  for b, w, n in facts1["prefill_calls"]
                  if n > before.get((b, w), 0)]
    finished = [r for r in recs if "done" in r]
    failed = [r for r in recs if "error" in r]
    # each number the reference check compares, beside its limit
    compared = {f"{m['which']}.{key}": {"value": m[key],
                                        "limit": limits[key]}
                for m in ref for key in limits if key in m}
    # and each control beside the same limit turned round: what a
    # program with that fault reads has to lie OUTSIDE it
    controls = ref_spec.get("controls", {})
    for m in ref:
        for key, reading in controls.items():
            if key in m:
                lo, hi = limits[reading]
                compared[f"control.{m['which']}.{key}"] = {
                    "value": m[key],
                    "limit": [hi, None] if hi is not None else [None, lo]}
    grew = lambda key: stats1.get(key, 0) - stats0.get(key, 0)  # noqa: E731
    drafts, stood, tokens = (grew(k) for k in (
        "drafts_proposed", "drafts_accepted", "step_tokens"))
    # the WINDOW's own decisions, every request's, against the share of
    # drafts the reference says has to stand over the samples' served
    # positions (float32 sum_x min(P_1, Q)); and what a program that
    # lets every draft stand would read, beside the same limit
    window = {"drafts": drafts, "accepted": stood}
    seen = [m for m in ref if m.get("accept_positions")]
    if seen and drafts and "window_accept_err" in limits:
        over = sum(m["accept_positions"] for m in seen)
        want = sum(m["accept_expected_mean"] * m["accept_positions"]
                   for m in seen) / over
        _, hi = limits["window_accept_err"]
        window.update(accept_share=stood / drafts, accept_expected=want,
                      positions=over)
        compared["window.accept_err"] = {
            "value": abs(stood / drafts - want),
            "limit": limits["window_accept_err"]}
        compared["control.window.always_accept"] = {
            "value": abs(1.0 - want), "limit": [hi, None]}
    checks = {
        "platform_tpu": info["device"]["platform"] == "tpu",
        "device_count": info["device"]["count"] == cell["chips"],
        "paged_decode_is_pallas": info["paged_impl"] == "tpu",
        "experts_decode_is_pallas": info["moe_impl"] == "tpu",
        "no_failed_request": not failed,
        "token_counts_as_asked": all(
            len(r["tokens"]) == r["asked"] for r in finished),
        "tokens_in_vocabulary": all(
            isinstance(t, int) and 0 <= t < vocab
            for r in recs for t in r["tokens"]),
        "all_finished": len(finished) == len(recs),
        "no_compile_in_window":
            facts1["longest_compile_s"] <= 0.5
            and in_window["compile_s"] <= 0.01 * seconds,
        # drafts stood and drafts fell, and a delivered step gave its row
        # one token or two (a request may end at the first of a pair)
        "both_branches_ran": 0 < stood < drafts,
        "one_or_two_tokens_a_step":
            drafts + stood - len(recs) <= tokens <= drafts + stood,
        "reference_checked": len(ref) >= (2 if wants_long else 1) and all(
            key in m or key == "window_accept_err"
            for m in ref[:1] for key in (*limits, *controls))
            and ("window_accept_err" not in limits
                 or "window.accept_err" in compared),
        "reference_numbers": all(inside(c["value"], *c["limit"])
                                 for c in compared.values()),
    }
    say("serve_done", requests=len(recs), finished=len(finished),
        failed=len(failed), errors=[r["error"] for r in failed][:3],
        compile_in_window=in_window, prefill_pairs_used=pairs_used,
        longest_compile_s=facts1["longest_compile_s"],
        compiled_names=facts1["compiled_names"],
        client=serve_views.client_summary(recs), window=window,
        stats0=stats0, stats1=stats1, reference=ref, checks=checks,
        load_end=facts1["load"], cache=[info1["compile_s"],
                                        info1["cache_hits"],
                                        info1["cache_misses"]])
    say("requests", rows=[
        [round(x, 4) for x in (r["due"], r.get("sent", -1.0),
                               r["token_t"][0], r["token_t"][-1])]
        + [len(r["token_t"])] for r in finished if r["token_t"]])
    return {
        "kind": "serve", "checks": checks, "compared": compared,
        "attempted": sum("sent" in r for r in recs),
        "failed": len(failed),
        "device": {"platform": info["device"]["platform"],
                   "kind": info["device"]["kind"],
                   "count": info["device"]["count"],
                   "memory_peak_bytes": facts1["memory_peak_bytes"]},
        "first_measured_wall": first_wall, "phases": phases,
        "chips": cell["chips"], "config": config, "mix": mix,
        "serve": {"requests": recs, "seconds": seconds,
                  "stats0": stats0, "stats1": stats1,
                  "num_slots": mix["server"]["num_slots"]},
        "trace_dir": trace_dir, "traced": traced or None,
    }
