"""Runner for mixes of kind ``serve_kda``: a ``serve_hybrid`` cell (see
``runners/serve_hybrid.py`` and ``chipbench/README-hybrid.md``) whose
recurrent layers are Kimi Delta Attention (a gated delta rule whose decay
is a vector over a head's key channels), whose attention layers keep a
LATENT row in the pool and do not rotate, and whose layers have routed
experts of which this chip holds a share (``chipbench/README-kda.md``).
The served path, the schedule, the rehearsal of the prefill programs, the
controls and the record (``kind: "serve"``: every reader of a serve
record reads it) are ``serve_arch``'s, ``serve_hybrid``'s and
``serve_latent``'s, imported from them; what differs is the replica,
``lib/replica_kda.py KdaBenchLLMServer`` (every other replica's check
knows one of this model's three mechanisms and not the other two), the
two samples the reference checks (``reference_samples``: answers here
run to thousands of tokens, and the check's decode steps and its token by
token recurrence are paid for a position; a run has to end inside the
driver's 360 s), and the checks that name the
kernels (``kda_decode_is_pallas``, ``experts_decode_is_pallas``), so
``deploy`` and ``run`` are written out here with that replica, until a
``benchmark`` PR may fold the six runners of this family.

Mix parameters: ``runners/serve_hybrid.py``'s, with another
``reference``: ``{"short_max_total_tokens": t, "long_min_prompt": p,
"long_max_answer": a, "limits", "controls"}``: the finished request of
the SHORTEST PROMPT, cut at ``t`` tokens in all, is run through the
engine's model and held to the reference and to every fault of it; the
PROMPT of the one with the shortest prompt of at least ``p`` tokens goes
through the engine's own compiled prefill and decode block, and what
they leave in its state entry is held to the reference after the same
tokens (``a``: how many of its streamed tokens ride along, to be set
beside the block's for the log).  And ``trace_at_s``: the second of the
window at which a traced run turns the profiler on (a quarter of the
window without it): the schedule is the mix's for every ``--seed``, and
a trace has to hold prefill programs as well as decode blocks.
"""

import asyncio
import os
import time

from chipbench.lib import cluster, serve_views, traffic
from chipbench.runners.serve import _buckets, _drive
from chipbench.runners.serve_arch import (_WAVES, cell_schedule,
                                          server_args)
from chipbench.runners.serve_hybrid import warmed_pairs
from chipbench.runners.serve_latent import _fits, _refuse_unless_known


# a replica that builds 9.9 GB of weights and a 27-layer engine from an
# EMPTY compile cache is not up within the controller's default 120 s,
# and is then killed as unhealthy (my chip run, PR 55, call 1: at 131 s)
REPLICA_GRACE_S = 900.0


def reference_samples(recs: list, schedule: list, spec: dict) -> list:
    """The two requests the reference checks (module docstring), as
    ``{"prompt", "tokens", "which", "faults"}``; ``faults`` False: the
    right reference and the float8 one alone."""
    by_due = {r["due_s"]: r for r in schedule}
    done = [r for r in recs if "done" in r and r["tokens"]]
    out = []
    if done:
        r = min(done, key=lambda r: (r["prompt_len"], len(r["tokens"])))
        keep = max(1, spec["short_max_total_tokens"] - r["prompt_len"])
        out.append({"prompt": by_due[r["due"]]["prompt"],
                    "tokens": r["tokens"][:keep], "which": "short",
                    "faults": True})
    long = min((r for r in done
                if r["prompt_len"] >= spec["long_min_prompt"]),
               key=lambda r: r["prompt_len"], default=None)
    if long is not None:
        out.append({"prompt": by_due[long["due"]]["prompt"],
                    "tokens": long["tokens"][:spec["long_max_answer"]],
                    "which": "long", "faults": False})
    return out


def deploy(cell, config, mix, seed31, allow_cpu, say, pairs=None):
    """``runners/serve_hybrid.py deploy`` with ``KdaBenchLLMServer``
    for the replica.  ``pairs`` are the prefill programs to warm; without
    them (the sweep) every bucket at waves 1, 2 and 4; either way those
    the engine can form under ``server.prefill_wave_tokens``."""
    _refuse_unless_known(config, mix)
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from chipbench.lib.replica_kda import KdaBenchLLMServer

    server = server_args(config, mix, seed31)
    if pairs is None:
        spec = mix["prompt_len"]
        pairs = [(b, w) for b in _buckets(spec["min"], spec["max"])
                 for w in _WAVES[:3]]
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
            KdaBenchLLMServer, name=f"llm-{cell['config']}",
            num_replicas=1,
            max_concurrent_queries=mix["max_concurrent_queries"],
            ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
        ).options(health_check_grace_period_s=REPLICA_GRACE_S).bind(
            config["program"]["preset"], **server)
        handle = serve.run(app, name="chipbench")
        info = ray_tpu.get(handle.device_info.remote(), timeout=1100)
        t2 = time.perf_counter()
        warm = ray_tpu.get(handle.bench_warm.remote(
            pairs, mix.get("warm_concat", {})), timeout=1100)
        took = info["phases"] = {"cluster_s": round(t1 - t0, 2),
                                 "replica_s": round(t2 - t1, 2),
                                 "warm_s": round(warm["seconds"], 2)}
        say("replica", device=info["device"], paged_impl=info["paged_impl"],
            kda_impl=info["kda_impl"], moe_impl=info["moe_impl"],
            weights_seed=server["seed"], cluster_s=took["cluster_s"],
            replica_s=took["replica_s"], warm=warm, pairs=pairs)
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
        len(r["prompt"]) >= ref_spec["long_min_prompt"] for r in schedule)
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(cluster.OUT_DIR, "trace", cell["name"])

    ray_tpu, handle, info = deploy(cell, config, mix, ctx["seed31"],
                                   ctx["allow_cpu"], say,
                                   warmed_pairs(schedule, mix))
    try:
        # the high-water marks are the window's own, not the warm-up's
        ray_tpu.get(handle.bench_reset_peaks.remote(), timeout=60)
        stats0 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts0 = ray_tpu.get(handle.bench_facts.remote(), timeout=60)
        traced = {}

        async def on_trace(t0):
            # where the mix says: the schedule is the mix's own, and the
            # trace has to hold prefill waves as well as decode blocks
            await asyncio.sleep(mix.get("trace_at_s", seconds * 0.25))
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
        ref = ray_tpu.get(handle.bench_reference.remote(
            [{"prompt": s["prompt"], "tokens": s["tokens"],
              "faults": s["faults"]}
             for s in samples], config), timeout=900) if samples else []
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
    checks = {
        "platform_tpu": info["device"]["platform"] == "tpu",
        "device_count": info["device"]["count"] == cell["chips"],
        "paged_decode_is_pallas": info["paged_impl"] == "tpu",
        "kda_decode_is_pallas": info["kda_impl"] == "tpu",
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
        # the short sample carries the model's readings, the long one
        # the engine path's: between them every limit and control
        "reference_checked": len(ref) >= (2 if wants_long else 1) and (
            not wants_long or all(
                any(key in m for m in ref) for key in (*limits, *controls))),
        "reference_numbers": all(inside(c["value"], *c["limit"])
                                 for c in compared.values()),
    }
    say("serve_done", requests=len(recs), finished=len(finished),
        failed=len(failed), errors=[r["error"] for r in failed][:3],
        compile_in_window=in_window, prefill_pairs_used=pairs_used,
        longest_compile_s=facts1["longest_compile_s"],
        compiled_names=facts1["compiled_names"],
        client=serve_views.client_summary(recs),
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
