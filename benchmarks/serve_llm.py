"""LLM serving benchmark: continuous batching on the real chip.

The north-star serving row (BASELINE.md: "Serve llama-3-8b, TPU
replicas ... qps, p50/p99").  The reference's serve numbers are no-op
handlers (doc/source/serve/performance.md); this drives REAL token
generation through one TPU-resident engine replica and reports
tokens/s/chip, request qps, latency percentiles, and batch occupancy —
the numbers a model-serving user actually plans capacity with.

Run directly (defaults to gpt-small shapes, random weights):
  python benchmarks/serve_llm.py [--preset gpt-small] [--slots 8]
        [--requests 64] [--prompt-len 64] [--new-tokens 64] [--engine-only]

One row per process (one process per chip): ``--engine-only`` drives the
engine in this process, without it the row goes through a Serve replica
that leases the chip.  ``--suite`` runs each scenario as a fresh child.

Prints one JSON line per scenario (collect_microbench.py ingests these).
"""

import argparse
import json
import os
import sys
import time

# repo-root import without PYTHONPATH
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


try:
    from benchmarks._bench_util import percentiles as _percentiles
except ImportError:          # run as a script from benchmarks/
    from _bench_util import percentiles as _percentiles


def build_engine(preset: str, slots: int, seed: int = 0,
                 max_seq_len=None, block_size=16, page_size=64,
                 kv_pool_pages=None):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = get_config(preset)
    model = GPT(cfg, decode=True)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return LLMEngine(cfg, params, num_slots=slots,
                     max_seq_len=max_seq_len,
                     block_size=block_size, page_size=page_size,
                     kv_pool_pages=kv_pool_pages), cfg


def _pool_pages(slots, requests, prompt_len, new_tokens, page_size):
    """A pool in which every request can prefill ahead of slot turnover
    (the TTFT path), with slack."""
    per_req = -(-(prompt_len + new_tokens) // page_size)
    return 1 + (requests + slots) * per_req


def bench_engine(preset="gpt-small", slots=8, requests=64, prompt_len=64,
                 new_tokens=64, stagger_s=0.0, page_size=64):
    """Drive the engine directly (no serve actor hop): the chip-side
    ceiling for one replica."""
    eng, cfg = build_engine(preset, slots,
                            max_seq_len=2 * (prompt_len + new_tokens),
                            page_size=page_size,
                            kv_pool_pages=_pool_pages(
                                slots, requests, prompt_len, new_tokens,
                                page_size))
    try:
        return _drive_engine(eng, cfg, preset, slots, requests, prompt_len,
                             new_tokens, stagger_s)
    finally:
        # a mid-bench failure must not leak the loop thread + device
        # buffers into the next suite scenario
        eng.close()


def _drive_engine(eng, cfg, preset, slots, requests, prompt_len,
                  new_tokens, stagger_s):
    import asyncio

    vocab = cfg.vocab_size

    # compile every jit path at the bench shapes before timing, incl.
    # the saturation-burst decomposition
    eng.warmup(prompt_lens=[prompt_len], burst=requests)
    eng.submit([7] * prompt_len, max_new_tokens=4, temperature=0.8)

    # single-threaded async submission: all requests enqueue at t~0 from
    # one event loop (a thread per request on this 1-core box measures
    # Python thread scheduling, not the engine)
    async def drive():
        futs = []
        for i in range(requests):
            prompt = [(i * 37 + j) % (vocab - 1) + 1
                      for j in range(prompt_len)]
            futs.append(eng.submit(prompt, max_new_tokens=new_tokens,
                                   temperature=0.8))
            if stagger_s:
                await asyncio.sleep(stagger_s)
        return await asyncio.gather(*futs)

    t0 = time.monotonic()
    results = asyncio.run(drive())
    wall = time.monotonic() - t0
    lats = [r.latency_s for r in results]
    ttfts = [r.time_to_first_token_s for r in results]

    tokens = sum(len(r.tokens) for r in results if r is not None)
    st = eng.stats.snapshot(eng.num_slots)
    p50, p99 = _percentiles(lats)
    t50, t99 = _percentiles(ttfts)
    return {
        "metric": "serve_llm_engine",
        "kv": "paged",
        "preset": preset,
        "num_slots": slots,
        "requests": requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "tokens_per_s": round(tokens / wall, 1),
        "qps": round(requests / wall, 2),
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
        "ttft_p50_ms": round(t50, 1),
        "ttft_p99_ms": round(t99, 1),
        "batch_occupancy": st["batch_occupancy"],
        "wall_s": round(wall, 2),
    }


def bench_serve(preset="gpt-small", slots=8, requests=64, prompt_len=64,
                new_tokens=64, page_size=64):
    """Same load through a Serve replica handle: measures what a client
    of the deployment sees (adds router + actor-call overhead).

    Latency accounting matches bench_engine: every request is submitted
    up front and measured from its own submission instant (the round-4
    engine/handle rows used a concurrency window that hid queue wait —
    VERDICT round 4, "what's weak" #3)."""
    import ray_tpu
    from ray_tpu import serve

    pool = _pool_pages(slots, requests, prompt_len, new_tokens, page_size)
    # replica __init__ compiles every engine specialization (warmup):
    # give actor creation room beyond the 60 s default.  num_tpus=1 on
    # both the cluster and the deployment: a replica without a TPU
    # lease is pinned to the CPU backend (see build_app docstring).
    # Setup sits INSIDE the try: a failed serve.run must still tear the
    # cluster down, or its daemons poison the rest of the --suite run.
    ray_tpu.init(num_cpus=4, num_tpus=1,
                 system_config={"actor_creation_timeout_s": 900.0})
    try:
        serve.start()
        app = serve.llm.build_app(preset=preset, num_slots=slots,
                                  max_concurrent_queries=2 * requests,
                                  max_seq_len=2 * (prompt_len + new_tokens),
                                  num_tpus=1, page_size=page_size,
                                  kv_pool_pages=pool,
                                  warmup_prompt_lens=[prompt_len],
                                  warmup_burst=requests)
        handle = serve.run(app, name="llm-bench")
        # warm the replica's jit paths
        ray_tpu.get(handle.remote({"prompt": [7] * prompt_len,
                                   "max_new_tokens": 4}), timeout=600)
        t0 = time.monotonic()
        pending = {}
        for i in range(requests):
            prompt = [(i * 37 + j) % 1000 + 1 for j in range(prompt_len)]
            ref = handle.remote({"prompt": prompt,
                                 "max_new_tokens": new_tokens,
                                 "temperature": 0.8})
            pending[ref] = time.monotonic()
        lats = []
        ttfts = []
        while pending:
            ready, _ = ray_tpu.wait(list(pending), num_returns=1,
                                    timeout=600)
            for r in ready:
                out = ray_tpu.get(r)
                assert len(out["tokens"]) == new_tokens
                lats.append(time.monotonic() - pending.pop(r))
                ttfts.append(out["time_to_first_token_s"])
        wall = time.monotonic() - t0
        p50, p99 = _percentiles(lats)
        t50, t99 = _percentiles(ttfts)
        return {
            "metric": "serve_llm_handle",
            "kv": "paged",
            "preset": preset,
            "num_slots": slots,
            "requests": requests,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "tokens_per_s": round(requests * new_tokens / wall, 1),
            "qps": round(requests / wall, 2),
            "p50_ms": round(p50, 1),
            "p99_ms": round(p99, 1),
            "ttft_p50_ms": round(t50, 1),
            "ttft_p99_ms": round(t99, 1),
            "wall_s": round(wall, 2),
        }
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass          # serve may not have started; cluster must die
        ray_tpu.shutdown()


def run_suite(slots: int, requests: int):
    """The MICROBENCH serve_llm matrix (one JSON line each):
      - gpt-small engine     (the pool + prefill-ahead TTFT)
      - gpt-small handle     (client view through Serve)
      - gpt-large engine     (1B: the north-star scale row)
    """
    scenarios = [
        ("gpt-small", True),
        ("gpt-small", False),                # handle row
        ("gpt-large", True),
    ]
    # One process per chip: an engine row holds the chip in the process
    # that built the engine, the handle row needs it for the replica's
    # worker.  So every scenario is a fresh child of this process, which
    # itself never touches JAX, and each child has exited (chip
    # released) before the next starts.
    import subprocess
    failed = 0
    for preset, engine_only in scenarios:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--preset", preset, "--slots", str(slots),
               "--requests", str(requests)]
        cmd += ["--engine-only"] if engine_only else []
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        if proc.returncode != 0:        # one scenario must not kill the rest
            failed += 1
            print(f"[serve_llm] {preset} "
                  f"engine_only={engine_only} FAILED: exit "
                  f"{proc.returncode}", file=sys.stderr, flush=True)
    if "jax" in sys.modules:
        raise RuntimeError("run_suite's parent must stay off JAX: its "
                           "children need the chip")
    if failed:
        # non-zero exit so the collector keeps the previous COMPLETE row
        # set instead of replacing it with this truncated one
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt-small")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--engine-only", action="store_true")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--suite", action="store_true",
                    help="emit the full MICROBENCH scenario matrix")
    args = ap.parse_args()

    if args.suite:
        run_suite(args.slots, args.requests)
        return
    # one row per process: the engine row holds the chip in THIS
    # process, the handle row needs it free for the replica's worker
    bench = bench_engine if args.engine_only else bench_serve
    row = bench(args.preset, args.slots, args.requests,
                args.prompt_len, args.new_tokens,
                page_size=args.page_size)
    print(json.dumps(row))


if __name__ == "__main__":
    main()
