"""Runner for mixes of kind ``serve_arch``: a serve cell whose
configuration the dense adapter (``lib/configs.py model_overrides``,
``lib/reference.py``) cannot express.  The served path is the one
``runners/serve.py`` measures: ``serve.run`` of the paged LLM deployment
on one chip, streaming, open loop, each request timed from when it was
DUE.  What differs is found by name in the configuration's file (see
``chipbench/README-arch.md``)::

    program.preset     the preset of models/configs.py
    program.adapter    module with model_overrides(config, extra): the
                       published keys -> overrides of that preset
    program.reference  module with from_program_params(params) and the
                       comparisons ``lib/replica_arch.py`` calls

so the next architecture adds a configuration, an adapter and a
reference, not a runner.  The record is ``runners/serve.py``'s (``kind:
"serve"``): every reader of a serve record reads this one.

Mix parameters beyond ``runners/serve.py``'s (``warm_waves`` is not
read)::

    weights_seed     the seed the server makes its weights from, for
                     every ``--seed``: where a step costs what the live
                     rows' routing touches, the routers' skew is part of
                     the work and the mix fixes it, as it fixes the
                     schedule (``lib/traffic.py``'s steadiness rule)
    contents_seed    the same for the prompts' token ids.  Without
                     either key that input is drawn from ``--seed``, as
                     in ``runners/serve.py``
    warm_horizon_s   the prefill programs warmed are those a REHEARSAL of
                     the schedule finds: requests of one prompt bucket
                     due within this many seconds of each other may share
                     a prefill wave (``rehearse``).  It is the longest
                     pause of the machine the cell takes without a
                     compile in its window: while the engine stands
                     still, arrivals pile up into larger waves (a 10 s
                     pause met once in this cell's traced runs)
    reference        {"short_max_total_tokens": t, "long_min_context": c,
                      "limits": {reading: [lowest, highest], ...}}:
                     two finished requests are checked, the one of the
                     smallest context (its continuation cut at t tokens
                     in all) and the first whose context reached c.  The
                     replica runs their tokens through the engine's own
                     model on the chip and the reference module compares
                     NUMBERS (``lib/replica_arch.py``); each key of
                     ``limits`` names a reading of that report and the
                     range it has to lie in (null: no bound on that
                     side), for every request that has the reading, and
                     the long one has to have them all
"""

import asyncio
import importlib
import os
import time

from chipbench.lib import cluster, serve_views, traffic
from chipbench.runners.serve import _buckets, _drive

_WAVES = (1, 2, 4, 8, 16, 32)          # the engine's prefill wave sizes


def rehearse(schedule: list, mix: dict) -> list:
    """The ``(bucket, wave)`` prefill programs this schedule can form:
    the engine batches the prompts of one bucket that are waiting when
    its loop comes round (a decode block and a prefill wave later), so
    every run of ``n`` requests of one bucket due within
    ``warm_horizon_s`` may become a wave of the next size up, or of any
    smaller one.  The warm-up requests use the smallest bucket alone."""
    lo = mix["prompt_len"]["min"]
    buckets = _buckets(lo, mix["prompt_len"]["max"])
    of = lambda n: next(b for b in buckets if b >= n)        # noqa: E731
    most = {buckets[0]: 1}
    for i, r in enumerate(schedule):
        b = of(len(r["prompt"]))
        n = sum(1 for s in schedule[i:]
                if s["due_s"] < r["due_s"] + mix["warm_horizon_s"]
                and of(len(s["prompt"])) == b)
        most[b] = max(most.get(b, 0), n)
    return [(b, w) for b in sorted(most) for w in _WAVES
            if w < 2 * most[b]]


def _refuse_unknown(preset: str, overrides: dict) -> None:
    """A program that lacks the preset or a field the adapter sets cannot
    run this configuration: say so and exit before anything is started
    (a replica that cannot be built is otherwise retried for minutes).
    Importing the configs module starts no JAX backend."""
    import dataclasses

    from ray_tpu.models.configs import PRESETS, TransformerConfig
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    unknown = sorted(set(overrides) - fields)
    if preset not in PRESETS or unknown:
        raise SystemExit(
            f"this program cannot express the configuration: preset "
            f"{preset!r} {'missing' if preset not in PRESETS else 'known'}"
            f", TransformerConfig lacks {unknown}")


def server_args(config: dict, mix: dict, seed31: int) -> dict:
    """What ``LLMServer`` is built with.  The weights come from the mix's
    ``weights_seed`` where it names one (module docstring) and from the
    run's ``--seed`` where it does not."""
    adapter = importlib.import_module(config["program"]["adapter"])
    overrides = adapter.model_overrides(config,
                                        mix.get("config_overrides", {}))
    return dict(mix["server"], paged=True,
                seed=mix.get("weights_seed", seed31),
                config_overrides=overrides)


def cell_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The generator's schedule, its token ids drawn from the mix's
    ``contents_seed`` where it names one and from ``--seed`` where not."""
    return traffic.serve_schedule(mix, mix.get("contents_seed", seed),
                                  seconds, vocab)


def deploy(cell, config, mix, seed31, allow_cpu, say, pairs=None):
    """As ``runners/serve.py deploy``, with the overrides and the replica
    class of a configuration that names its adapter and reference.
    ``pairs`` are the prefill programs to warm; without them, every
    bucket at waves 1 to 4 (the sweep)."""
    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from chipbench.lib.replica_arch import ArchBenchLLMServer

    server = server_args(config, mix, seed31)
    _refuse_unknown(config["program"]["preset"], server["config_overrides"])
    if pairs is None:
        spec = mix["prompt_len"]
        pairs = [(b, w) for b in _buckets(spec["min"], spec["max"])
                 for w in (1, 2, 4)]
    ray_tpu = cluster.start_cluster(
        cell["chips"], int(mix.get("object_store_gb", 2) * 2**30),
        allow_cpu)
    try:
        num_tpus = 0 if allow_cpu and not ray_tpu.cluster_resources(
            ).get("TPU") else cell["chips"]
        app = deployment(
            ArchBenchLLMServer, name=f"llm-{cell['config']}",
            num_replicas=1,
            max_concurrent_queries=mix["max_concurrent_queries"],
            ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
        ).bind(config["program"]["preset"], **server)
        handle = serve.run(app, name="chipbench")
        info = ray_tpu.get(handle.device_info.remote(), timeout=1100)
        warm = ray_tpu.get(handle.bench_warm.remote(
            pairs, mix.get("warm_concat", {})), timeout=1100)
        say("replica", device=info["device"], paged_impl=info["paged_impl"],
            weights_seed=server["seed"], warm=warm, pairs=pairs)
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


def reference_samples(recs: list, schedule: list, spec: dict) -> list:
    """The two requests the reference checks (module docstring), as
    ``{"prompt", "tokens", "which"}``."""
    by_due = {r["due_s"]: r for r in schedule}
    done = [r for r in recs if "done" in r and r["tokens"]]
    context = lambda r: r["prompt_len"] + len(r["tokens"])   # noqa: E731
    out = []
    if done:
        r = min(done, key=context)
        keep = max(1, spec["short_max_total_tokens"] - r["prompt_len"])
        out.append({"prompt": by_due[r["due"]]["prompt"],
                    "tokens": r["tokens"][:keep], "which": "short"})
    long = next((r for r in done
                 if context(r) >= spec["long_min_context"]), None)
    if long is not None:
        out.append({"prompt": by_due[long["due"]]["prompt"],
                    "tokens": long["tokens"], "which": "long"})
    return out


def run(ctx) -> dict:
    from ray_tpu import serve
    from ray_tpu.runtime.core_worker import get_global_worker

    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
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
        stats1 = ray_tpu.get(handle.stats.remote(), timeout=60)
        facts1 = ray_tpu.get(handle.bench_facts.remote(facts0["compiles"]),
                             timeout=60)
        info1 = ray_tpu.get(handle.device_info.remote(), timeout=60)

        # the plain reference, outside the window, in the replica
        samples = reference_samples(recs, schedule, ref_spec)
        ref = ray_tpu.get(handle.bench_reference.remote(
            [{"prompt": s["prompt"], "tokens": s["tokens"]}
             for s in samples], config), timeout=900) if samples else []
        for s, m in zip(samples, ref):
            m["which"] = s["which"]
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
    # the checks mean what runners/serve.py's mean; the reference's
    # limits are the mix's (PERF.md says what each was set from)
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
        "no_compile_in_window":
            facts1["longest_compile_s"] <= 0.5
            and in_window["compile_s"] <= 0.01 * seconds,
        "reference_checked": len(ref) >= (2 if wants_long else 1) and (
            not wants_long or all(
                key in m for m in ref if m["which"] == "long"
                for key in limits)),
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
