"""Runner for mixes of kind ``serve_latent``: a ``serve_arch`` cell (see
``runners/serve_arch.py`` and ``chipbench/README-arch.md``) whose cache
row is LATENT and whose router reads the feed-forward's input
(``chipbench/README-latent.md``).  The served path, the schedule, the
rehearsal of the prefill programs and the record (``kind: "serve"``:
every reader of a serve record reads it) are ``serve_arch``'s, imported
from it; what differs is the replica, ``lib/replica_latent.py
LatentBenchLLMServer`` (its check captures the router's OWN input and
reads a latent pool), which requests the check takes, one more
invariant (the expert layer's decode step is the Pallas kernel), and
that the refusal of a program that cannot express the configuration
comes first of all, before the schedule is even drawn.  ``deploy`` and
``run`` are ``serve_hybrid``'s with that replica (``serve_arch`` imports
its replica class by name), written out here until a ``benchmark`` PR
may fold the three.

Mix parameters: ``runners/serve_arch.py``'s, ``reference.controls`` as in
``runners/serve_hybrid.py``, and, in place of ``short_max_total_tokens``
/ ``long_min_context``::

    reference.max_new_tokens   how many of a checked request's answer
                               tokens the check runs (one decode step
                               each, in the engine's decode shape)
    reference.faults_in        which sample runs every wrong-on-purpose
                               reference and the low-precision control
                               ("first" | "all"): the others run the
                               readings of ``limits`` that need none
                               (each is a forward of the reference)

``server.prefill_wave_tokens`` (``LLMEngine``: the most tokens one
prefill wave carries) bounds the programs warmed: a rehearsed wave that
the engine would split is not compiled.

The check takes ONE finished request of each prefill bucket the schedule
has prompts in (``b<bucket>``: the one of the smallest context), so both
prefill programs' numbers are held, the blocked one's too.
"""

import asyncio
import os
import time

from chipbench.lib import cluster, serve_views, traffic
from chipbench.runners.serve import _buckets, _drive
from chipbench.runners.serve_arch import (_refuse_unknown, cell_schedule,
                                          rehearse, server_args)


def _refuse_unless_known(config: dict, mix: dict) -> None:
    """Exit AT ONCE where the program beside this benchmark lacks the
    preset or a field the adapter sets (a parent commit): before a
    schedule is drawn or a cluster started.  Nothing here starts a JAX
    backend."""
    _refuse_unknown(config["program"]["preset"],
                    server_args(config, mix, 0)["config_overrides"])


def _fits(mix: dict, pairs: list) -> list:
    """The ``(bucket, wave)`` programs the engine can form: it carries at
    most ``server.prefill_wave_tokens`` tokens in one wave, which
    ``serve_arch.rehearse`` does not know of."""
    cap = mix["server"].get("prefill_wave_tokens")
    return [(b, w) for b, w in pairs
            if cap is None or w == 1 or b * w <= cap]


def _bucket_of(mix: dict):
    spec = mix["prompt_len"]
    buckets = _buckets(spec["min"], spec["max"])
    return lambda n: next(b for b in buckets if b >= n)


def reference_samples(recs: list, schedule: list, mix: dict) -> list:
    """The requests the reference checks (module docstring), as
    ``{"prompt", "tokens", "which"[, "faults"]}``."""
    spec = mix["reference"]
    of = _bucket_of(mix)
    by_due = {r["due_s"]: r for r in schedule}
    done = [r for r in recs if "done" in r and r["tokens"]]
    out = []
    for bucket in sorted({of(r["prompt_len"]) for r in done}):
        r = min((r for r in done if of(r["prompt_len"]) == bucket),
                key=lambda r: r["prompt_len"] + len(r["tokens"]))
        s = {"prompt": by_due[r["due"]]["prompt"],
             "tokens": r["tokens"][:spec["max_new_tokens"]],
             "which": f"b{bucket}"}
        if out and spec.get("faults_in", "all") == "first":
            s["faults"] = []
        out.append(s)
    return out


def deploy(cell, config, mix, seed31, allow_cpu, say, pairs=None):
    """``runners/serve_hybrid.py deploy`` with ``LatentBenchLLMServer``
    for the replica.  ``pairs`` are the prefill programs to warm;
    without them, every bucket at waves 1 to 4 (the sweep)."""
    _refuse_unless_known(config, mix)
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from chipbench.lib.replica_latent import LatentBenchLLMServer

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
            LatentBenchLLMServer, name=f"llm-{cell['config']}",
            num_replicas=1,
            max_concurrent_queries=mix["max_concurrent_queries"],
            ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
        ).bind(config["program"]["preset"], **server)
        handle = serve.run(app, name="chipbench")
        info = ray_tpu.get(handle.device_info.remote(), timeout=1100)
        t2 = time.perf_counter()
        warm = ray_tpu.get(handle.bench_warm.remote(
            pairs, mix.get("warm_concat", {})), timeout=1100)
        # where the set-up's seconds go: a run has to end well inside
        # the driver's limit
        say("replica", device=info["device"], paged_impl=info["paged_impl"],
            moe_impl=info.get("moe_impl"), weights_seed=server["seed"],
            cluster_s=round(t1 - t0, 2), replica_s=round(t2 - t1, 2),
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
    of = _bucket_of(mix)
    wanted = {f"b{of(len(r['prompt']))}" for r in schedule}
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(cluster.OUT_DIR, "trace", cell["name"])

    ray_tpu, handle, info = deploy(cell, config, mix, ctx["seed31"],
                                   ctx["allow_cpu"], say,
                                   rehearse(schedule, mix))
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
        samples = reference_samples(recs, schedule, mix)
        ref = ray_tpu.get(handle.bench_reference.remote(
            [{k: v for k, v in s.items() if k != "which"}
             for s in samples], config), timeout=900) if samples else []
        for s, m in zip(samples, ref):
            m["which"] = s["which"]
        say("after_window", drained_s=round(drained - first_wall, 2),
            reference_s=round(time.time() - drained, 2))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    cluster.wait_gone(info["pid"])

    limits = ref_spec["limits"]
    inside = lambda x, lo, hi: (lo is None or x >= lo) and (  # noqa: E731
        hi is None or x <= hi)
    in_window = {k: facts1["compiles"][k] - facts0["compiles"][k]
                 for k in facts0["compiles"]}
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
    # the checks mean what runners/serve_arch.py's mean; the reference's
    # limits are the mix's (PERF.md says what each was set from)
    checks = {
        "platform_tpu": info["device"]["platform"] == "tpu",
        "device_count": info["device"]["count"] == cell["chips"],
        "paged_decode_is_pallas": info["paged_impl"] == "tpu",
        "moe_decode_is_pallas": info["moe_impl"] == "tpu",
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
        # a request of every bucket the schedule has, and the first of
        # them with every reading and every control
        "reference_checked": {m["which"] for m in ref} >= wanted and all(
            key in m for m in ref[:1] for key in (*limits, *controls)),
        "reference_numbers": all(inside(c["value"], *c["limit"])
                                 for c in compared.values()),
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
        "first_measured_wall": first_wall,
        "chips": cell["chips"], "config": config, "mix": mix,
        "serve": {"requests": recs, "seconds": seconds,
                  "stats0": stats0, "stats1": stats1,
                  "num_slots": mix["server"]["num_slots"]},
        "trace_dir": trace_dir, "traced": traced or None,
    }
