"""One-variant MFU measurement for the gpt-large remat/chunk sweep.

Run one configuration per process (fresh HBM + compile cache):
  python benchmarks/mfu_sweep.py --policy block_outs --batch 8 --chunk 256
Prints one JSON line; the sweep results are recorded in bench.py's
comments and BENCH notes.
"""

import argparse
import functools
import json
import os
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt-large")
    ap.add_argument("--policy", default="nothing")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--attn", default="flash")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--remat-layers", type=int, default=None)
    args = ap.parse_args()

    import bench
    from ray_tpu.models import get_config
    from ray_tpu.train.step import OptimizerConfig, lm_loss_chunked_fn

    peak = bench.peak_flops(jax.devices()[0])

    # _bench_one re-imports lm_loss_chunked_fn at call time, so patching
    # the module attribute injects our chunk size
    loss_fn = functools.partial(lm_loss_chunked_fn, chunk_size=args.chunk)
    try:
        cfg = get_config(args.config, max_seq_len=args.seq, remat=True,
                         remat_policy=args.policy,
                         remat_layers=args.remat_layers,
                         attention_impl=args.attn)
        import ray_tpu.train.step as step_mod
        orig = step_mod.lm_loss_chunked_fn
        step_mod.lm_loss_chunked_fn = loss_fn
        try:
            res = bench._bench_one(
                cfg, args.batch, args.seq, steps=args.steps, warmup=3,
                peak=peak,
                optimizer=OptimizerConfig(warmup_steps=10, decay_steps=1000,
                                          optimizer="adafactor"),
                chunked=True)
        finally:
            step_mod.lm_loss_chunked_fn = orig
        res.update({"policy": args.policy, "batch": args.batch,
                    "chunk": args.chunk, "attn": args.attn,
                    "seq": args.seq, "ok": True})
    except Exception as e:
        res = {"policy": args.policy, "batch": args.batch,
               "chunk": args.chunk, "attn": args.attn, "seq": args.seq,
               "ok": False,
               "error": f"{type(e).__name__}: {str(e)[:200]}"}
    print(json.dumps(res))


if __name__ == "__main__":
    main()
