"""Smoke test on the chip: does the system still start and run there?

    python chip_smoke.py             # one chip: native, train, serve
    python chip_smoke.py --chips 4   # four chips: sharded train vs one device

Drives the two user entry points the way a user would — ``ShardedTrainer``
for training, ``serve.run(serve.llm.build_app(...))`` for serving — at
gpt-small's full widths with seeded random weights, and checks what comes
out.  One JSON object per phase goes to stdout as it finishes; the last
line is ``{"ok": true, "device": {...}}`` with the device as the worker
that held the chip reported it.  Anything else — a failed check, a
timeout, a platform other than ``tpu`` — raises: the script exits
non-zero and prints no ``"ok": true``.

One process per chip: this driver never starts a JAX backend (asserted
at the end), or its workers could not have the chip.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# What runs, at the widths and shape of the only chip rows the repo has
# (ROADMAP.md's table): module constants, so a CPU rehearsal can shrink
# them from outside without the script growing options.
TRAIN = dict(model="gpt-small", steps=12, batch_per_worker=16, seq_len=1024,
             model_overrides={"attention_impl": "flash", "remat": True,
                              "max_seq_len": 1024})
SERVE_PRESET = "gpt-small"
FOUR = dict(model="gpt-medium", steps=4, batch_per_worker=4, seq_len=1024,
            model_overrides={"remat": True, "max_seq_len": 1024})
PROMPT_LEN = 64
NEW_TOKENS = 64


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class phase_limit:
    """A phase that hangs must fail the script, not outlive its caller."""

    def __init__(self, name: str, seconds: int):
        self.name, self.seconds = name, seconds

    def _expired(self, *_):
        raise TimeoutError(f"phase {self.name!r} exceeded {self.seconds}s")

    def __enter__(self):
        signal.signal(signal.SIGALRM, self._expired)
        signal.alarm(self.seconds)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        signal.alarm(0)

    @property
    def elapsed(self) -> float:
        return round(time.monotonic() - self.t0, 2)


def wait_gone(pid: int, what: str, timeout: float = 60.0) -> None:
    """The chip is free only once its holder has exited."""
    deadline = time.monotonic() + timeout
    while os.path.exists(f"/proc/{pid}"):
        check(time.monotonic() < deadline,
              f"{what} (pid {pid}) still alive {timeout:.0f}s after shutdown")
        time.sleep(0.05)


def libtpu_holders() -> list:
    """Pids (other than ours) with libtpu mapped: processes that hold, or
    could hold, the chip."""
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" in f.read():
                    out.append(int(pid))
        except OSError:
            continue
    return out


def check_tpu(device: dict, count: int) -> None:
    check(device["platform"] == "tpu",
          f"worker ran on platform {device['platform']!r}, not 'tpu'")
    check(device["count"] == count,
          f"worker saw {device['count']} device(s), expected {count}")


# --------------------------------------------------------------------- native

def phase_native() -> None:
    """Every native artefact rebuilt HERE from the tracked sources, then
    loaded: the chip run never uses an ELF built on another box."""
    with phase_limit("native", 300) as ph:
        proc = subprocess.run(["make", "-B", "-j4", "-C",
                               os.path.join(ROOT, "csrc")],
                              capture_output=True, text=True)
        check(proc.returncode == 0,
              f"make -B -C csrc failed:\n{proc.stderr[-3000:]}")
        from ray_tpu._core import scheduler
        from ray_tpu.runtime import object_store
        object_store.get_lib()
        check(scheduler.native_available(),
              "libscheduler.so did not load")
        check(isinstance(scheduler.make_scheduler(),
                         scheduler.NativeClusterScheduler),
              "the Python fallback scheduler was selected")
        emit("native", seconds=ph.elapsed)


# ---------------------------------------------------------------------- train

def start_cluster(chips: int, object_store_memory: int):
    """``ray_tpu.init()`` with no ``num_tpus=``: detection must find the
    chips.  Only the store size (checkpoints of real-width state ride
    it) and the actor-start patience (replicas compile at start) are
    set."""
    import ray_tpu
    ray_tpu.init(object_store_memory=object_store_memory,
                 system_config={"actor_creation_timeout_s": 900.0})
    try:
        found = ray_tpu.cluster_resources().get("TPU", 0)
        check(found == chips,
              f"ray_tpu.init() advertised TPU={found}, expected {chips}: "
              "no chip on this host, or detection missed it")
    except BaseException:
        ray_tpu.shutdown()
        raise
    return ray_tpu


def phase_train(seed: int) -> dict:
    from ray_tpu.air.config import RunConfig
    from ray_tpu.train.sharded import ShardedRunConfig, ShardedTrainer

    with phase_limit("train", 500) as ph:
        run = ShardedRunConfig(num_workers=1, seed=seed,
                               checkpoint_interval=TRAIN["steps"], **TRAIN)
        result = ShardedTrainer(
            run, run_config=RunConfig(
                name="chip-smoke-train",
                storage_path=os.path.join(ROOT, "chiprun_out", "results")),
            tag="chip-smoke").fit()
        check(result.error is None, f"training failed: {result.error}")
        s = result.metrics["summary"]
        losses = s["losses"]
        check_tpu(s["device"], 1)
        check(len(losses) == run.steps, f"{len(losses)} losses reported")
        check(all(x == x and abs(x) != float("inf") for x in losses),
              f"non-finite loss in {losses}")
        check(losses[-1] < losses[0],
              f"loss did not fall: {losses[0]} -> {losses[-1]}")
        check(s["pallas_custom_call"],
              "the lowered train step holds no Pallas custom call "
              "(interpreter or xla attention ran instead of the kernel)")
        wait_gone(s["pid"], "the trainer's worker")
        emit("train", seconds=ph.elapsed, compile_seconds=s["compile_s"],
             cache_hits=s["cache_hits"], cache_misses=s["cache_misses"],
             losses=losses, step_seconds=s["step_s"],
             device_kind=s["device"]["kind"], mesh=s["mesh"],
             pallas_custom_call=s["pallas_custom_call"])
        return s["device"]


# ---------------------------------------------------------------------- serve

def phase_serve(ray_tpu, seed: int) -> dict:
    import random

    from ray_tpu import serve

    with phase_limit("serve", 500) as ph:
        app = serve.llm.build_app(
            SERVE_PRESET, num_tpus=1, page_size=64, num_slots=8,
            max_seq_len=2 * (PROMPT_LEN + NEW_TOKENS), seed=seed,
            max_concurrent_queries=32, warmup_prompt_lens=[PROMPT_LEN])
        handle = serve.run(app, name="chip-smoke")
        info = ray_tpu.get(handle.device_info.remote(), timeout=120)
        check_tpu(info["device"], 1)
        check(info["paged_impl"] == "tpu",
              f"paged decode resolved to {info['paged_impl']!r}, "
              "not the Pallas kernel")

        from ray_tpu.models.configs import get_config
        vocab = get_config(SERVE_PRESET).vocab_size
        rng = random.Random(seed)
        prompts = [[rng.randrange(1, vocab) for _ in range(PROMPT_LEN)]
                   for _ in range(4)]
        prompts.append(prompts[0])      # the same greedy prompt twice

        def in_vocab(tokens) -> bool:
            return all(isinstance(t, int) and 0 <= t < vocab
                       for t in tokens)

        t_req = time.monotonic()
        outs = ray_tpu.get(
            [handle.remote({"prompt": p, "max_new_tokens": NEW_TOKENS})
             for p in prompts], timeout=300)
        request_s = round(time.monotonic() - t_req, 2)
        for out in outs:
            check(len(out["tokens"]) == NEW_TOKENS,
                  f"asked {NEW_TOKENS} tokens, got {len(out['tokens'])}")
            check(in_vocab(out["tokens"]), "token outside the vocabulary")
        check(outs[0]["tokens"] == outs[-1]["tokens"],
              "the same greedy prompt gave different tokens")

        streamed, summary = [], None
        for ref in handle.stream.remote_streaming(
                {"prompt": prompts[1], "max_new_tokens": NEW_TOKENS}):
            item = ray_tpu.get(ref, timeout=120)
            if "token" in item:
                streamed.append(item["token"])
            else:
                summary = item
        check(summary is not None and summary["num_tokens"] == NEW_TOKENS,
              f"stream summary: {summary}")
        check(len(streamed) == NEW_TOKENS and in_vocab(streamed),
              f"stream yielded {len(streamed)} tokens")
        check(streamed == outs[1]["tokens"],
              "streaming and handle paths disagree on a greedy prompt")

        info = ray_tpu.get(handle.device_info.remote(), timeout=120)
        serve.shutdown()
        wait_gone(info["pid"], "the serve replica")
        emit("serve", seconds=ph.elapsed, compile_seconds=info["compile_s"],
             cache_hits=info["cache_hits"],
             cache_misses=info["cache_misses"], requests=len(outs) + 1,
             request_seconds=request_s, tokens=outs[0]["tokens"][:8],
             ttft_seconds=[round(o["time_to_first_token_s"], 4)
                           for o in outs],
             device_kind=info["device"]["kind"],
             paged_impl=info["paged_impl"])
        return info["device"]


# ----------------------------------------------------------------- four chips

LOSS_RTOL = 1e-3        # bf16 activations, different reduction orders
MEMORY_FACTOR = 1.5     # largest / smallest bytes_in_use across the mesh


def four_chip_loop(config):
    """Runs in the ONE worker that leases all four chips: the gang loop
    itself under fsdp=2 x tp=2, then the same model, seed and batches on
    a one-device mesh in the same process."""
    import jax

    from ray_tpu.air import session
    from ray_tpu.train.sharded import layout
    from ray_tpu.train.sharded.executor import (ShardingConfig, _synth_batch,
                                                build_step,
                                                sharded_train_loop)

    run = config["run"]
    sharded = sharded_train_loop(config)["summary"]

    mesh = layout.plan(ShardingConfig(), n_devices=1).build_mesh(
        jax.devices()[:1])
    vocab = config["vocab"]
    batch = _synth_batch(run, vocab, 0, 0)
    init_fn, grad_fn, apply_fn = build_step(run, mesh, batch)
    state = init_fn(jax.random.PRNGKey(run.seed), batch)
    reference = []
    for step in range(run.steps):
        grads, metrics = grad_fn(state, _synth_batch(run, vocab, 0, step))
        state = apply_fn(state, grads)
        reference.append(float(metrics["loss"]))
    session.report({"step": run.steps, "sharded": sharded,
                    "reference_losses": reference})


def phase_four_chips(seed: int) -> dict:
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.models.configs import get_config
    from ray_tpu.train.base_trainer import DataParallelTrainer
    from ray_tpu.train.jax_trainer import JaxConfig
    from ray_tpu.train.sharded import ShardedRunConfig, ShardingConfig
    from ray_tpu.train.sharded.executor import tpu_lease_per_worker

    with phase_limit("four_chips", 900) as ph:
        run = ShardedRunConfig(
            sharding=ShardingConfig(fsdp=2, tp=2), num_workers=1, seed=seed,
            checkpoint_interval=FOUR["steps"], **FOUR)
        lease = tpu_lease_per_worker(1)
        check(lease == {"TPU": 4.0}, f"lease for one worker: {lease}")
        # ShardedTrainer's own construction (executor.py), with the loop
        # wrapped so the one-device comparison shares its process
        result = DataParallelTrainer(
            four_chip_loop,
            train_loop_config={
                "run": run, "tag": "chip-smoke-4",
                "vocab": get_config(FOUR["model"]).vocab_size},
            backend_config=JaxConfig(init_distributed=False),
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=lease),
            run_config=RunConfig(
                name="chip-smoke-4",
                storage_path=os.path.join(ROOT, "chiprun_out", "results")),
        ).fit()
        check(result.error is None, f"training failed: {result.error}")
        s = result.metrics["sharded"]
        ref = result.metrics["reference_losses"]
        check_tpu(s["device"], 4)
        check(s["mesh"]["fsdp"] == 2 and s["mesh"]["tensor"] == 2,
              f"mesh {s['mesh']}")
        worst = max(abs(a - b) / abs(b) for a, b in zip(s["losses"], ref))
        check(len(ref) == len(s["losses"]) and worst <= LOSS_RTOL,
              f"sharded {s['losses']} vs one device {ref}: relative "
              f"difference {worst:.4f} > {LOSS_RTOL}")
        check(s["min_devices_per_param"] == 4,
              f"a parameter sits on {s['min_devices_per_param']} device(s)")
        check(s["partitioned_params"] > 0, "no parameter is partitioned")
        mem = s["bytes_in_use"]
        check(len(mem) == 4 and all(mem)
              and max(mem) / min(mem) <= MEMORY_FACTOR,
              f"per-device bytes_in_use {mem}: spread above "
              f"{MEMORY_FACTOR}x")
        wait_gone(s["pid"], "the trainer's worker")
        emit("four_chips", seconds=ph.elapsed,
             compile_seconds=s["compile_s"], losses=s["losses"],
             reference_losses=ref, loss_rel_diff=round(worst, 5),
             loss_rtol=LOSS_RTOL, mesh=s["mesh"],
             partitioned_params=s["partitioned_params"],
             n_params=s["n_params"], bytes_in_use=mem,
             memory_factor=MEMORY_FACTOR, step_seconds=s["step_s"],
             device_kind=s["device"]["kind"])
        return s["device"]


# ----------------------------------------------------------------------- main

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from ray_tpu._private.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()      # inherited by every worker
    emit("start", chips=args.chips, seed=args.seed,
         compile_cache_dir=cache_dir)

    if args.chips == 4:
        ray_tpu = start_cluster(4, 16 << 30)
        try:
            device = phase_four_chips(args.seed)
        finally:
            ray_tpu.shutdown()
    else:
        phase_native()
        ray_tpu = start_cluster(1, 6 << 30)
        try:
            device = phase_train(args.seed)
            served_on = phase_serve(ray_tpu, args.seed)
            check(served_on == device,
                  f"train ran on {device}, serve on {served_on}")
        finally:
            from ray_tpu import serve
            serve.shutdown()
            ray_tpu.shutdown()

    holders = libtpu_holders()
    check(not holders, f"processes still hold libtpu: {holders}")
    xb = sys.modules.get("jax._src.xla_bridge")
    check(xb is None or not xb.backends_are_initialized(),
          "the driver process initialised a JAX backend")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
