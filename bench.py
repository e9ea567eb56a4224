"""Headline benchmark: flagship GPT train-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": "tokens/s",
   "vs_baseline": achieved_MFU / 0.35}

The reference commits no number for its Train north-star metric
(BASELINE.json "published" is empty), so ``vs_baseline`` is measured against
the north-star target itself: BASELINE.md's "GPT-J FSDP->GSPMD >= 35% MFU".
vs_baseline >= 1.0 means we meet/beat the target MFU on this chip.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS = {
    # bf16 peak per chip
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float:
    """bf16 peak of ``device`` from the table above.  A device that is
    not a TPU, or a TPU kind the table does not know, is an error: a
    utilization against an assumed peak is not a measurement."""
    kind = getattr(device, "device_kind", "")
    if device.platform != "tpu":
        raise SystemExit(
            f"this benchmark measures a TPU; JAX found platform "
            f"{device.platform!r} ({kind!r}).  Nothing is measured on "
            "another device under the chip metric's name.")
    for name, peak in PEAK_FLOPS.items():
        if name in kind:
            return peak
    raise SystemExit(f"no bf16 peak known for device kind {kind!r}; "
                     "add it to bench.PEAK_FLOPS with its source")


def _bench_one(cfg, batch, seq, steps, warmup, peak, *,
               optimizer=None, chunked=False):
    from ray_tpu._private import step_stats as sst
    from ray_tpu.models import GPT
    from ray_tpu.train.step import (OptimizerConfig, lm_loss_chunked_fn,
                                    make_sharded_train)
    from ray_tpu.parallel import build_mesh, MeshConfig

    n_params = cfg.num_params()
    # PaLM-style: 6N per token fwd+bwd + attention 12*L*d*S
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.d_model * seq

    mesh = build_mesh(MeshConfig(data=-1))
    n_chips = mesh.size
    # goodput ledger (docs/observability.md training performance
    # plane): the same per-step clock the trainers drive, standalone
    # (no cluster, local-only ledger).  peak_flops covers the whole
    # mesh so the ledger MFU is per-chip-comparable with the hand
    # computation below.
    run = sst.start_run(
        f"bench-{getattr(cfg, 'name', 'gpt')}",
        flops_per_token=flops_per_token, peak_flops=peak * n_chips,
        tokens_per_step=batch * seq)
    clock = sst.step_clock()
    model = GPT(cfg, mesh=mesh)
    rng = np.random.default_rng(0)
    batch_data = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)}
    kwargs = {"loss_fn": lm_loss_chunked_fn} if chunked else {}
    init_fn, step_fn, _, _ = make_sharded_train(
        model, mesh,
        optimizer or OptimizerConfig(warmup_steps=10, decay_steps=1000),
        example_batch=batch_data, **kwargs)
    state = init_fn(jax.random.PRNGKey(0), batch_data)

    if run is not None:
        run.ledger.note_init_done()
    t_compile = time.perf_counter()
    for _ in range(warmup):
        state, metrics = step_fn(state, batch_data)
    jax.block_until_ready((state, metrics))
    if run is not None:
        run.ledger.note_compile_ms((time.perf_counter() - t_compile) * 1e3)
    t0 = time.perf_counter()
    for i in range(steps):
        clock.begin()
        with clock.phase("host_dispatch"):
            state, metrics = step_fn(state, batch_data)
        if i == steps - 1:
            # the drain fence belongs to the LAST step's device_compute
            # so the ledger's productive window equals the timed window
            # (per-step fencing would serialize the device pipeline and
            # change the headline number)
            with clock.phase("device_compute"):
                final_loss = float(metrics["loss"])
        clock.end()
    dt = (time.perf_counter() - t0) / steps
    ledger = sst.end_run(run) or {}

    tokens_per_sec = batch * seq / dt / n_chips  # per chip
    mfu = flops_per_token * tokens_per_sec / peak
    out = {"tokens_s": round(tokens_per_sec, 1), "mfu": round(mfu, 4),
           "step_ms": round(dt * 1e3, 2), "params": n_params,
           "n_chips": n_chips, "final_loss": round(final_loss, 4)}
    if ledger:
        out.update({
            "goodput": ledger.get("goodput"),
            "ledger_mfu": ledger.get("mfu"),
            "init_ms": round(ledger.get("init_ms", 0.0), 1),
            "compile_ms": round(ledger.get("compile_ms", 0.0), 1),
            "phase_ms": ledger.get("phase_ms"),
        })
    return out


def main():
    from ray_tpu._private.compile_cache import ensure_compile_cache
    from ray_tpu.models import get_config
    from ray_tpu.train.step import OptimizerConfig

    ensure_compile_cache()
    dev = jax.devices()[0]
    peak = peak_flops(dev)
    kind = dev.device_kind
    n_dev = len(jax.devices())

    # measured sweep on v5e (16 GiB): batch 16 + remat beats batch 8
    # no-remat (47.7% vs 45.1% MFU); batch 32 needs the chunked head
    # and lands lower (44.3%) — the fp32 logits path at 16 wins.
    # Round-3 kernel sweep: flash block_q/block_k 1024/1024 beats the
    # old 256/256 by ~25% on attention fwd+bwd at these shapes
    # (gpt-small 49.1% -> 54.4% MFU, gpt-large 44.3% -> 48.6%).
    small = _bench_one(
        get_config("gpt-small", max_seq_len=1024, remat=True,
                   attention_impl="flash"),
        16 * n_dev, 1024, steps=20, warmup=3, peak=peak)
    # memory-lean path at 1B scale (north-star stepping stone): full
    # per-block remat + chunked CE head + adafactor + the hoisted
    # f32->bf16 param cast (train/step.py cast_params_once: one cast
    # per step instead of one per backward recompute) fits 1.07B
    # params on one 16 GiB chip at batch 10.  Round-4 sweep
    # (benchmarks/mfu_sweep.py): batch {4,6,8,12,16} x policy
    # {nothing, block_outs, dots, partial remat_layers} x CE chunk
    # {256,512,1024} all land 45.1-48.6% without the cast; with it,
    # nothing/b8 49.6%, nothing/b10 50.4% (b12 regresses: the bf16
    # copy eats the headroom).  Round-3 results still hold: xla
    # attention 37.5%, splash 23.6%, seq-2048@b4 worse; the in-tree
    # flash kernel with 1024-blocks wins.  Both models measure ~59%
    # raw hardware efficiency on their fwd pass — further MFU comes
    # from kernel work, not schedule knobs.
    import functools

    from ray_tpu.train.step import lm_loss_chunked_fn as _chunked
    import ray_tpu.train.step as _step_mod
    _orig_chunked = _step_mod.lm_loss_chunked_fn
    _step_mod.lm_loss_chunked_fn = functools.partial(
        _chunked, param_cast=jnp.bfloat16)
    try:
        large = _bench_one(
            get_config("gpt-large", max_seq_len=1024, remat=True,
                       remat_policy="nothing", attention_impl="flash"),
            10 * n_dev, 1024, steps=10, warmup=3, peak=peak,
            optimizer=OptimizerConfig(warmup_steps=10, decay_steps=1000,
                                      optimizer="adafactor"),
            chunked=True)
    finally:
        _step_mod.lm_loss_chunked_fn = _orig_chunked
    large.update({"config": "gpt-large", "optimizer": "adafactor",
                  "remat_policy": "nothing", "loss_head": "chunked_ce",
                  "param_cast": "bf16_once"})

    out = {
        "metric": "gpt_small_train_tokens_per_sec_per_chip",
        "value": small["tokens_s"],
        "unit": "tokens/s",
        "vs_baseline": round(small["mfu"] / 0.35, 4),
        "mfu": small["mfu"],
        "step_ms": small["step_ms"],
        "device": kind,
        "n_chips": small["n_chips"],
        "params": small["params"],
        "final_loss": small["final_loss"],
        # goodput ledger (docs/observability.md): the step-stats plane's
        # accounting of the same run — ledger_mfu must match `mfu`
        # (same flops arithmetic, clock-measured productive time)
        "goodput": small.get("goodput"),
        "ledger_mfu": small.get("ledger_mfu"),
        "init_ms": small.get("init_ms"),
        "compile_ms": small.get("compile_ms"),
        "phase_ms": small.get("phase_ms"),
        "large_model": large,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
