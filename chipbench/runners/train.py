"""Runner for mixes of kind ``train``: the program's own gang loop
(``sharded_train_loop``) inside the worker ``ShardedTrainer`` would start,
for a number of steps that fills ``--seconds``.

The driver side builds the trainer exactly as ``ShardedTrainer.__init__``
and ``fit`` do (``tpu_lease_per_worker``, ``JaxConfig(init_distributed=
False)``), but hands it ``bench_train_loop`` below, which calls the
program's loop three times in the one worker that holds the chips:

1. a few steps: compile (or load from the persistent cache), warm up,
   learn the step time;
2. ``ceil(seconds / step) + 2`` steps: the measured window;
3. with ``--trace 1``, a few steps under ``jax.profiler``.

The benchmark's only probe is a time stamp taken each time the loop
reports a step (``session.report`` wrapped for the duration): the loop
itself is untouched.  The window runs from the report of its first step
to the report of its last step but one, so it holds whole steps only —
the first step's load from the cache and the last step's checkpoint and
summary (which the loop always makes) lie outside it.  At each report the
step's loss has been fetched, which fences that step's forward and
backward pass and the optimizer update before it.
"""

import math
import os
import time

from chipbench.lib import cluster, configs


# ------------------------------------------------------------ in the worker

def bench_train_loop(config):
    import dataclasses
    import statistics

    import jax

    from chipbench.lib import compile_watch, reference, trace
    from ray_tpu.air import session
    from ray_tpu.train.sharded import layout
    from ray_tpu.train.sharded.executor import (_synth_batch, build_step,
                                                sharded_train_loop)

    compile_watch.snapshot()                 # listeners on before any jit
    run, plan = config["run"], config["bench"]
    stamps, hook = [], {"fn": None}
    real_report = session.report

    def stamping_report(metrics, checkpoint=None):
        stamps.append({"t": time.perf_counter(), "wall": time.time(),
                       "compiles": compile_watch.snapshot()})
        if hook["fn"] is not None:
            hook["fn"](metrics)
        return real_report(metrics, checkpoint=checkpoint)

    def call(tag, steps):
        """One run of the program's loop; each gets its own tag and a
        checkpoint interval of its whole length, so none resumes from
        another's checkpoint."""
        del stamps[:]
        cfg = dataclasses.replace(run, steps=steps,
                                  checkpoint_interval=steps)
        out = sharded_train_loop({"run": cfg, "tag": tag})
        return out["summary"], list(stamps)

    session.report = stamping_report
    try:
        _, warm = call("chipbench-warm", plan["warm_steps"])
        gaps = [b["t"] - a["t"] for a, b in zip(warm, warm[1:])]
        step_s = statistics.median(gaps[:-1] or gaps)
        steps = math.ceil(plan["seconds"] / step_s) + 2
        summary, window = call("chipbench-window", steps)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        traced = None
        if plan["trace_dir"]:
            marks = {}
            last = plan["trace_steps"]

            def on_step(metrics):
                if metrics["step"] == 0:
                    trace.start_trace(plan["trace_dir"])
                    marks["t0"] = time.perf_counter()
                elif metrics["step"] == last:
                    marks["t1"] = time.perf_counter()
                    jax.profiler.stop_trace()

            hook["fn"] = on_step
            call("chipbench-trace", last + 2)
            hook["fn"] = None
            traced = {"window_s": marks["t1"] - marks["t0"], "steps": last}
    finally:
        session.report = real_report

    # the plain reference, outside every window: the loss of the step-0
    # batch under the initial parameters (same seed, same init program)
    ref = None
    if plan["reference"]:
        from ray_tpu.models import get_config
        model_cfg = get_config(run.model, **run.model_overrides)
        vocab = model_cfg.vocab_size
        mesh = layout.plan(run.sharding,
                           n_devices=jax.device_count()).build_mesh()
        batch = _synth_batch(run, vocab, 0, 0)
        init_fn = build_step(run, mesh, batch)[0]
        params = init_fn(jax.random.PRNGKey(run.seed), batch).params
        t0 = time.perf_counter()
        ref_loss = reference.lm_loss(
            reference.from_program_params(params),
            jax.device_get(batch["tokens"]), z_loss=model_cfg.z_loss,
            **plan["reference"])
        ref = {"loss": ref_loss, "seconds": time.perf_counter() - t0}

    real_report({
        "step": steps, "summary": summary, "step_s_warm": step_s,
        "window_stamps": window, "memory_peak_bytes": peak,
        "traced": traced, "reference": ref})


# ------------------------------------------------------------ in the driver

def run(ctx) -> dict:
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.base_trainer import DataParallelTrainer
    from ray_tpu.train.jax_trainer import JaxConfig
    from ray_tpu.train.sharded import ShardedRunConfig, ShardingConfig
    from ray_tpu.train.sharded.executor import tpu_lease_per_worker

    cell, config, mix = ctx["cell"], ctx["config"], ctx["mix"]
    chips = cell["chips"]
    overrides = configs.model_overrides(
        config, dict(mix.get("model_overrides", {}),
                     max_seq_len=mix["seq_len"]))
    shard = ShardingConfig(**mix.get("sharding", {}))
    run_cfg = ShardedRunConfig(
        sharding=shard, model=config["program"]["preset"],
        model_overrides=overrides, num_workers=1, seed=ctx["seed31"],
        batch_per_worker=mix["batch_per_worker"], seq_len=mix["seq_len"],
        **mix.get("run", {}))
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(cluster.OUT_DIR, "trace", cell["name"])
    plan = {"seconds": ctx["seconds"], "warm_steps": mix["warm_steps"],
            "trace_dir": trace_dir, "trace_steps": mix["trace_steps"],
            "reference": {"rope_theta": config["rope_theta"],
                          "rms_norm_eps": config["rms_norm_eps"]}}

    ray_tpu = cluster.start_cluster(
        chips, int(mix["object_store_gb"] * 2**30), ctx["allow_cpu"])
    try:
        lease = tpu_lease_per_worker(1)
        if lease is not None and lease != {"TPU": float(chips)}:
            raise SystemExit(f"one worker would lease {lease}, the cell "
                             f"asks for {chips} chip(s)")
        ctx["say"]("train", lease=lease, steps_warm=plan["warm_steps"])
        result = DataParallelTrainer(
            bench_train_loop,
            train_loop_config={"run": run_cfg, "bench": plan},
            backend_config=JaxConfig(init_distributed=False),
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=lease),
            run_config=RunConfig(
                name=f"chipbench-{cell['name']}",
                storage_path=os.path.join(cluster.OUT_DIR, "results")),
        ).fit()
        if result.error is not None:
            raise RuntimeError(f"training failed: {result.error}")
        out = result.metrics
    finally:
        ray_tpu.shutdown()
    cluster.wait_gone(out["summary"]["pid"])

    s, stamps = out["summary"], out["window_stamps"]
    first, last = stamps[0], stamps[-2]
    steps = len(stamps) - 2
    window_s = last["t"] - first["t"]
    tokens = steps * mix["batch_per_worker"] * mix["seq_len"]
    losses = s["losses"]
    in_window = {k: last["compiles"][k] - first["compiles"][k]
                 for k in first["compiles"]}
    ref = out["reference"]
    ln_v = math.log(config["vocab_size"])
    checks = {
        "platform_tpu": s["device"]["platform"] == "tpu",
        "device_count": s["device"]["count"] == chips,
        "steps_as_asked": len(losses) == len(stamps),
        "losses_finite": all(math.isfinite(x) for x in losses),
        "first_loss_near_ln_vocab": ln_v - 0.5 < losses[0] < ln_v + 1.5,
        "pallas_custom_call":
            s["pallas_custom_call"] or not mix.get("expect_pallas", True),
        "no_compile_in_window": in_window["backend_compiles"] == 0,
        "mesh": all(s["mesh"].get(k, 1) == v
                    for k, v in mix.get("expect_mesh", {}).items()),
        "reference_loss":
            abs(losses[0] - ref["loss"]) <= mix["reference_loss_atol"],
    }
    gaps = [b["t"] - a["t"] for a, b in zip(stamps, stamps[1:-1])]
    ctx["say"]("train_done", steps=steps, window_s=window_s,
               step_ms=1e3 * window_s / steps,
               slowest_step_ms=[1e3 * max(gaps), gaps.index(max(gaps))],
               losses=losses[:3],
               loss_last=losses[-1], reference=ref, checks=checks,
               compile_in_window=in_window, mesh=s["mesh"],
               warm_step_s=out["step_s_warm"],
               cache=[s["compile_s"], s["cache_hits"], s["cache_misses"]])
    return {
        "kind": "train", "checks": checks,
        "attempted": steps, "failed": sum(not math.isfinite(x)
                                          for x in losses),
        "device": {"platform": s["device"]["platform"],
                   "kind": s["device"]["kind"],
                   "count": s["device"]["count"],
                   "memory_peak_bytes": out["memory_peak_bytes"]},
        "first_measured_wall": first["wall"],
        "chips": chips, "config": config, "mix": mix,
        "train": {"steps": steps, "window_s": window_s, "tokens": tokens,
                  "seq_len": mix["seq_len"]},
        "trace_dir": trace_dir, "traced": out["traced"],
    }
