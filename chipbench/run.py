"""One process, one cell, once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration file and its
traffic mix by name, picks the runner by the mix's ``kind``
(``chipbench/runners/<kind>.py``), and reads each metric the cell reports
with the metric's own file (``chipbench/metrics/<name>.py``).  Progress
goes to earlier lines; the last line of standard output is the one JSON
object the contract asks for; its last key, ``compared``, holds each
number that decided ``correct`` beside its limit, and the same are the
last lines of standard error.  This process never starts a JAX backend:
the worker or the replica must have the chip.  Any platform but ``tpu``
ends in a non-zero exit and no result line (``CHIPBENCH_REHEARSAL=1``
lets a CPU rehearsal print a line marked ``"rehearsal": true`` and still
exit non-zero: see README.md).
"""

import argparse
import importlib
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def process_start_wall() -> float:
    """When this process was started, on the wall clock (Linux)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(ln.split()[1]) for ln in f if ln.startswith("btime"))
    return btime + ticks / os.sysconf("SC_CLK_TCK")


def say(what: str, **facts) -> None:
    print(json.dumps({"chipbench": what, **facts}, default=str), flush=True)


def metric_names(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def compared(record: dict) -> dict:
    """What decided ``correct``, each number beside its limit ``[lowest,
    highest]``: every check as 1 or 0, and the numbers a runner compared
    with a reference where its record names them (``compared``)."""
    out = {name: {"value": int(bool(ok)), "limit": [1, None]}
           for name, ok in record["checks"].items()}
    out.update(record.get("compared") or {})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    started = process_start_wall()
    rehearsal = os.environ.get("CHIPBENCH_REHEARSAL") == "1"

    from chipbench.lib import cluster, configs
    try:
        import ray_tpu  # noqa: F401 -- the system under test
    except ImportError:
        print("chipbench: no ray_tpu beside chipbench/: nothing to "
              "measure", file=sys.stderr)
        return 1
    from ray_tpu._private.compile_cache import ensure_compile_cache

    bench = configs.load_benchmark()
    cell, entry, config, mix = configs.find_cell(bench, args.workload)
    os.makedirs(cluster.OUT_DIR, exist_ok=True)
    cache_dir = ensure_compile_cache()       # inherited by every worker
    say("start", workload=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, compile_cache_dir=cache_dir,
        config_file=entry["file"], kind=mix["kind"])

    def expired(*_):
        raise TimeoutError("chipbench run exceeded its own limit")
    signal.signal(signal.SIGALRM, expired)
    signal.alarm(1150)

    runner = importlib.import_module(f"chipbench.runners.{mix['kind']}")
    record = runner.run({
        "cell": cell, "config": config, "mix": mix, "seed": args.seed,
        "seed31": args.seed % (2**31 - 1), "seconds": args.seconds,
        "trace": bool(args.trace), "allow_cpu": rehearsal, "say": say})
    signal.alarm(0)
    record["setup_s"] = record["first_measured_wall"] - started

    if record.get("trace_dir"):
        from chipbench.lib import trace
        path = trace.find_xplane(record["trace_dir"])
        record["trace"] = trace.reduce_trace(path) if path else {}
        say("trace", file=path, planes=record["trace"].get("planes"),
            devices=record["trace"].get("devices"),
            busy_s=record["trace"].get("busy_s"))

    wanted = metric_names(bench, cell.get("like", cell["name"]),
                          "per_layer" if args.trace else "end_to_end")
    metrics = {}
    for m in wanted:
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = reader.read(record)
        if value is not None:       # nothing to read: left out of the line
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = dict(record["device"])
    line = {"correct": all(record["checks"].values()),
            "attempted": record["attempted"], "failed": record["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        red = record.get("trace", {})
        device["busy_s"] = red.get("busy_s")
        # the host's clock between tracing on and off; operations still
        # running when tracing was turned off end a little after it, so
        # the window is never shorter than the trace's own device span
        device["window_s"] = max(
            (record.get("traced") or {}).get("window_s") or 0.0,
            red.get("span_s") or 0.0) or None
        line["breakdown"] = trace.breakdown(red)
    line["compared"] = compared(record)            # last, and on stderr
    if cluster.driver_touched_backend():
        print("chipbench: the driver process initialised a JAX backend",
              file=sys.stderr)
        return 1
    if not line["correct"]:
        say("failed_checks", checks=record["checks"])
    # the last line but one: where the run's seconds went (the driver
    # stops a run at 360 s); the runner's phases where it names them
    say("phases", **record.get("phases", {}),
        setup_s=round(record["setup_s"], 2),
        total_s=round(time.time() - started, 2))
    if device["platform"] != "tpu":
        if rehearsal:
            print(json.dumps({"rehearsal": True, "not_a_measurement":
                              f"ran on {device['platform']}", **line}))
        print(f"chipbench: ran on {device['platform']!r}, not on a TPU: "
              "no result", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
