"""``chipbench/sweep.py`` for a serve cell of any runner kind: one
process, the server started once by the cell's own runner
(``runners/<kind>.py deploy``), each rate offered for ``--seconds`` and
then drained.

    python3 chipbench/sweep_arch.py --workload <cell> --rates 0.5,1,1.5 --seconds 45

Prints one JSON line per rate.  ``sustained`` is the reading this mix
can be held to: answers last 26 s on average and up to a minute, so a
server that starts empty fills for about a minute whatever the rate, and
the tokens of the requests due in a window are mostly delivered after
it (``second_half_delivered_over_offered`` stays under 1 at every
rate).  A rate is sustained where, over the SECOND HALF of a window of
90 s or more, no request waits for a slot: the engine's own
``queue_wait_s + slot_wait_s`` of the requests due there stays under
``SUSTAINED_QUEUE_WAIT_S`` at the 95th percentile (a request always
waits for the block in flight, some 0.5 s), at
most ``num_slots`` requests are in flight at the window's end, and
every request finishes in the drain.  Above capacity the slots are all
taken, requests queue for them and that wait grows through the window.
The rate a cell then offers is a number in its traffic file.  Every
prompt bucket is warmed at waves 1, 2 and 4; above the knee a larger
wave can still compile inside a window, which that row then says
(``compiled``).

``sweep.py`` may not be edited by the PR that added this file; once it
may, this file supersedes it (``--workload`` of either kind).
"""

import argparse
import asyncio
import importlib
import json
import os
import sys
import time

SUSTAINED_QUEUE_WAIT_S = 1.5

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from chipbench.lib import cluster, configs, traffic
    from chipbench.lib.stats import percentile
    from chipbench.run import say
    from chipbench.runners.serve import _drive
    from ray_tpu import serve
    from ray_tpu._private.compile_cache import ensure_compile_cache
    from ray_tpu.runtime.core_worker import get_global_worker

    bench = configs.load_benchmark()
    cell, _, config, mix = configs.find_cell(bench, args.workload)
    runner = importlib.import_module(f"chipbench.runners.{mix['kind']}")
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
            f0 = ray_tpu.get(handle.bench_facts.remote(), timeout=60)
            recs = asyncio.run(_drive(
                handle, get_global_worker(), sched, w, None))
            s1 = ray_tpu.get(handle.stats.remote(), timeout=60)
            f1 = ray_tpu.get(handle.bench_facts.remote(f0["compiles"]),
                             timeout=60)
            late = [r for r in recs if w / 2 <= r["due"] < w]
            delivered = sum(1 for r in recs for t in r["token_t"]
                            if w / 2 <= t < w)
            tpots = [(r["token_t"][-1] - r["token_t"][0])
                     / (len(r["token_t"]) - 1)
                     for r in recs if len(r["token_t"]) > 1]
            ttft = [r["token_t"][0] - r["due"] for r in late
                    if r["token_t"]]
            steps = s1["steps"] - s0["steps"]
            waits = [r["summary"]["queue_wait_s"]
                     + r["summary"].get("slot_wait_s", 0.0) for r in late
                     if "queue_wait_s" in r.get("summary", {})]
            in_flight = sum(1 for r in recs if r.get("done", 1e9) > w)
            failed = sum("error" in r for r in recs)
            slots = mix["server"]["num_slots"]
            print(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "failed": failed,
                "sustained": bool(
                    not failed and waits and in_flight <= slots
                    and percentile(waits, 95) < SUSTAINED_QUEUE_WAIT_S),
                "queue_wait_p95_second_half_s": percentile(waits, 95),
                "queue_wait_max_second_half_s": max(waits, default=None),
                "offered_tokens_per_s": sum(r["asked"] for r in recs) / w,
                "second_half_delivered_over_offered":
                    delivered / max(1, sum(r["asked"] for r in late)),
                "second_half_delivered_tokens_per_s": delivered / (w / 2),
                "ttft_p95_second_half_ms": 1e3 * (percentile(ttft, 95)
                                                  or 0),
                "tpot_mean_ms": 1e3 * sum(tpots) / max(1, len(tpots)),
                "tpot_p95_ms": 1e3 * (percentile(tpots, 95) or 0),
                "in_flight_at_end": in_flight, "num_slots": slots,
                "live_slots_mean": (
                    (s1["tokens_generated"] - s0["tokens_generated"])
                    / max(1, steps)),
                "last_done_s": max((r.get("done", 0) for r in recs),
                                   default=0),
                "engine_steps": steps,
                "compiled": f1["compiled_names"],
                "device": info["device"]}), flush=True)
            time.sleep(1.0)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    cluster.wait_gone(info["pid"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
