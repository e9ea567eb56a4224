"""Find a serve cell's knee once, on the chip: one process, the server
started once, each rate offered for ``--seconds`` and then drained.

    python3 chipbench/sweep.py --workload <cell> --rates 3,5,7,9,12 --seconds 20

Prints one JSON line per rate.  "Keeps up" is read from the second half
of each window: tokens delivered over tokens whose requests fell due in
it (at least 0.97), and TTFT p95 of the requests due in the second half
no more than 1.5 times the first half's.  The rate a cell then offers is
a number in its traffic file; this script judges nothing by itself.
"""

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from chipbench.lib import cluster, configs, traffic
    from chipbench.lib.stats import percentile
    from chipbench.run import say
    from chipbench.runners import serve as runner
    from ray_tpu import serve
    from ray_tpu._private.compile_cache import ensure_compile_cache
    from ray_tpu.runtime.core_worker import get_global_worker

    bench = configs.load_benchmark()
    cell, _, config, mix = configs.find_cell(bench, args.workload)
    ensure_compile_cache()
    allow_cpu = os.environ.get("CHIPBENCH_REHEARSAL") == "1"
    ray_tpu, handle, info = runner.deploy(
        cell, config, mix, args.seed, allow_cpu, say)
    try:
        w = args.seconds
        for rate in (float(r) for r in args.rates.split(",")):
            sched = traffic.serve_schedule(
                dict(mix, rate_per_s=rate), args.seed, w,
                config["vocab_size"])
            s0 = ray_tpu.get(handle.stats.remote(), timeout=60)
            recs = asyncio.run(runner._drive(
                handle, get_global_worker(), sched, w, None))
            s1 = ray_tpu.get(handle.stats.remote(), timeout=60)
            half = lambda r, lo, hi: lo <= r["due"] < hi  # noqa: E731
            ttft = lambda lo, hi: percentile(  # noqa: E731
                [r["token_t"][0] - r["due"] for r in recs
                 if r["token_t"] and half(r, lo, hi)], 95)
            delivered = sum(1 for r in recs for t in r["token_t"]
                            if w / 2 <= t < w)
            offered = sum(r["asked"] for r in recs if half(r, w / 2, w))
            tpot = percentile(
                [(r["token_t"][-1] - r["token_t"][0])
                 / (len(r["token_t"]) - 1)
                 for r in recs if len(r["token_t"]) > 1], 95)
            steps = s1["steps"] - s0["steps"]
            print(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "failed": sum("error" in r for r in recs),
                "offered_tokens_per_s": sum(r["asked"] for r in recs) / w,
                "second_half_delivered_over_offered":
                    delivered / max(1, offered),
                "ttft_p95_first_half_ms": 1e3 * (ttft(0, w / 2) or 0),
                "ttft_p95_second_half_ms": 1e3 * (ttft(w / 2, w) or 0),
                "tpot_p95_ms": 1e3 * (tpot or 0),
                "in_flight_at_end": sum(
                    1 for r in recs if r.get("done", 1e9) > w),
                "last_done_s": max((r.get("done", 0) for r in recs),
                                   default=0),
                "engine_steps": steps,
                "device": info["device"]}), flush=True)
            time.sleep(1.0)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    cluster.wait_gone(info["pid"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
