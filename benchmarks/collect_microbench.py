"""Assemble MICROBENCH.json from the individual benchmark programs.

Counterpart of the reference's release/benchmarks result collection:
each registered section (core ops/s, serve qps, data ingest, LLM
serving, RL, vision) runs in its own process so daemons can't leak
between sections, and their JSON outputs are merged into one file.
Sections that a run does NOT regenerate — because `--only` skipped
them, their script produced no rows, or they were written by another
program (the scale envelope from tests/test_scale_envelope.py) — are
preserved verbatim from the existing output file.

Usage:  python benchmarks/collect_microbench.py [-o MICROBENCH.json]
                                                [--only SECTION ...]
"""

import argparse
import json
import os
import platform
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_json_lines(cmd, timeout=900):
    # one process per chip: this collector never imports jax, and every
    # section is a fresh child that has exited (and released the chip)
    # before the next starts.  Every benchmark script self-inserts the
    # repo root into sys.path, so no PYTHONPATH is passed.
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=dict(os.environ), cwd=REPO)
    rows = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{") or line.startswith("["):
            try:
                rows.append(json.loads(line))
            except ValueError:
                pass
    if proc.returncode != 0 and not rows:
        raise RuntimeError(f"{cmd}: rc={proc.returncode}\n{proc.stderr[-2000:]}")
    return rows, proc.returncode


# Every benchmark program the collector owns, in run order.  Adding a
# section here is the ONLY step needed for it to survive refreshes: any
# section present in the existing output file that the current run does
# not regenerate is carried over verbatim (merge-preserve), so a partial
# `--only` refresh can never silently drop another program's numbers.
SECTIONS = {
    "core": dict(cmd=[sys.executable, "-m", "ray_tpu._private.ray_perf"],
                 timeout=900, last_list=True),
    "serve": dict(cmd=[sys.executable,
                       os.path.join(REPO, "benchmarks", "serve_qps.py")],
                  timeout=900),
    "data": dict(cmd=[sys.executable,
                      os.path.join(REPO, "benchmarks", "data_ingest.py")],
                 timeout=900),
    "streaming": dict(cmd=[sys.executable,
                           os.path.join(REPO, "benchmarks",
                                        "streaming_perf.py")],
                      timeout=600),
    # compiled static graphs (docs/compiled_dag.md): interleaved A/B of
    # a 3-stage actor chain, compiled (shm channels, zero per-call task
    # submission) vs classic dag.execute(); the speedup row is the >=5x
    # bar and the shm-growth row the ==0 slot-reuse bar
    "compiled_dag": dict(cmd=[sys.executable,
                              os.path.join(REPO, "benchmarks",
                                           "compiled_dag_perf.py")],
                         timeout=600),
    # bulk data plane (docs/object_transfer.md): interleaved same-box A/B
    # of a 64 MiB cross-node pull — pipelined zero-copy engine vs the
    # legacy serial algorithm (>=3x bar), striped 2-source vs 1 (>1x
    # bar), shm growth == object size (zero-copy bar), and the
    # prefetch-overlap task-e2e saving
    "object_transfer": dict(cmd=[sys.executable,
                                 os.path.join(REPO, "benchmarks",
                                              "object_transfer_perf.py")],
                            timeout=900),
    # collective data plane (docs/collective.md): interleaved same-box
    # A/B of the rebuilt DCN group (pipelined shm/TCP ring,
    # hierarchical) vs the legacy blocking ring at 1KiB/1MiB/64MiB and
    # world sizes 2/4/8 (>=3x bar at 64MiB ws4), the zero-TCP-bytes
    # same-node bar, and the multi-source 64MiB broadcast (>=2 sources)
    "collective": dict(cmd=[sys.executable,
                            os.path.join(REPO, "benchmarks",
                                         "collective_perf.py")],
                       timeout=2400),
    # quantized collective + backward overlap (docs/collective.md): ws4
    # group with the shm transport disabled so every segment rides
    # loopback TCP (the DCN regime the int8 codec targets) — fp32 vs
    # quantize="int8" interleaved A/B at 1/64 MiB (>=2x bar at 64 MiB)
    # and the allreduce_async overlap probe (>=50% of ring time hidden
    # behind a calibrated synthetic backward)
    "collective_quant": dict(cmd=[sys.executable,
                                  os.path.join(REPO, "benchmarks",
                                               "collective_perf.py"),
                                  "--quant"],
                             timeout=1200),
    # always-on runtime telemetry cost guard (docs/observability.md):
    # interleaved same-box A/B of task throughput with
    # RAY_TPU_TELEMETRY=0 vs 1; the overhead_pct row is the <=3% bar
    "telemetry": dict(cmd=[sys.executable,
                           os.path.join(REPO, "benchmarks",
                                        "telemetry_overhead.py")],
                      timeout=900),
    # cluster event plane cost guard (docs/observability.md):
    # interleaved same-box A/B of task throughput with RAY_TPU_EVENTS=0
    # vs 1 (telemetry pinned on in both arms); the events_overhead row
    # carries the same <=3% bar as the telemetry plane
    "events": dict(cmd=[sys.executable,
                        os.path.join(REPO, "benchmarks",
                                     "telemetry_overhead.py"),
                        "--events"],
                   timeout=900),
    # training performance plane cost guard (docs/observability.md):
    # interleaved same-box A/B of a fully-clocked ms-scale step loop
    # with RAY_TPU_STEP_STATS=0 vs 1 (telemetry + events pinned on);
    # the step_stats_overhead row carries the same <=3% bar.  4 rounds:
    # the ~5ms-step loop resolves a ~1% plane cost only if best-of gets
    # enough draws against this box's minute-scale throttle drift
    "step_stats": dict(cmd=[sys.executable,
                            os.path.join(REPO, "benchmarks",
                                         "telemetry_overhead.py"),
                            "--step-stats", "--rounds", "4"],
                       timeout=1200),
    # request tracing plane cost guard (docs/observability.md): paired
    # interleaved OFF/ON segments of the small-task loop at the DEFAULT
    # trace_sample_rate (telemetry + events pinned on); the
    # tracing_overhead row carries the same <=3% bar.  4 rounds -> 64
    # pairs: the task loop schedules a worker process per call, so
    # per-pair ratios spread +-15% on this box and the median needs
    # that many draws to resolve a ~1% plane cost
    "tracing": dict(cmd=[sys.executable,
                         os.path.join(REPO, "benchmarks",
                                      "telemetry_overhead.py"),
                         "--tracing", "--rounds", "4"],
                    timeout=1200),
    # metrics-history plane cost guard (docs/observability.md): paired
    # interleaved OFF/ON segments of metrics-shaped kv_put RPCs against
    # an in-process GcsServer at the default retention geometry
    # (telemetry + events pinned on); the history_overhead row carries
    # the same <=3% bar.  4 rounds -> 64 pairs, same reasoning as the
    # tracing arm: per-pair ratios on this box spread several percent
    # and the median needs the draws to resolve a ~1% plane cost
    "history": dict(cmd=[sys.executable,
                         os.path.join(REPO, "benchmarks",
                                      "telemetry_overhead.py"),
                         "--history", "--rounds", "4"],
                    timeout=1200),
    "serve_llm": dict(cmd=[sys.executable,
                           os.path.join(REPO, "benchmarks", "serve_llm.py"),
                           "--suite", "--slots", "32", "--requests", "128"],
                      timeout=5400),
    # disaggregated prefill/decode serving (docs/serve_disagg.md):
    # closed-loop interleaved A/B at 1k concurrent streaming
    # connections, colocated vs split pools at equal chip count —
    # the ab row carries the bars (ttft_p99_ratio >= 2,
    # tokens_per_s_ratio >= 0.9, handoff p50 < one decode block,
    # errors == 0)
    "serve_disagg": dict(cmd=[sys.executable,
                              os.path.join(REPO, "benchmarks",
                                           "serve_disagg.py"),
                              "--connections", "1000",
                              "--duration", "90",
                              "--new-tokens", "96"],
                         timeout=3600),
    # serving front door (docs/serve_frontdoor.md): closed-loop SSE
    # ingress + prefix-affinity routing + quantized handoffs under the
    # bimodal shared-prefix mix — the row carries the per-pool
    # TTFT/TPOT SLO classification from the trace plane, the prefix
    # hit rate (must be nonzero on this mix) and the bytes the int8
    # handoff codec kept off the wire
    "serve_frontdoor": dict(cmd=[sys.executable,
                                 os.path.join(REPO, "benchmarks",
                                              "serve_frontdoor.py"),
                                 "--connections", "1000",
                                 "--duration", "60",
                                 "--new-tokens", "32"],
                            timeout=3600),
    "rl": dict(cmd=[sys.executable,
                    os.path.join(REPO, "benchmarks", "rl_perf.py")],
               timeout=3600),   # PPO-to-150 + 2 IMPALA rows on 1 core
    # podracer RL data plane (docs/rl_podracer.md): IMPALA + PPO
    # env-frames/s A/B vs the blocking executor (same fleet, same
    # budget, mid-run actor-kill probe in the podracer arm) and the
    # fleet-floor weight-adoption latency at 2/4/8 actors — the
    # sub-linear growth bar for the store-routed multi-source broadcast
    "rl_podracer": dict(cmd=[sys.executable,
                             os.path.join(REPO, "benchmarks",
                                          "rl_podracer.py")],
                        timeout=2400),
    "vision": dict(cmd=[sys.executable,
                        os.path.join(REPO, "benchmarks", "vision_perf.py")],
                   timeout=1800),
}


# Control-plane rows whose regressions the RPC fast path must keep
# visible (docs/rpc_fastpath.md): fresh core numbers are compared against
# the COMMITTED MICROBENCH.json (git HEAD), not the working copy, so a
# refresh that regressed the task path can't silently rebase its own
# baseline before the diff is reviewed.
_CONTROL_PLANE_ROWS = {
    "single client tasks sync": "tasks_sync_ops_s",
    "1:1 actor calls sync": "actor_sync_ops_s",
}

# Streaming-generator rows (docs/streaming_generators.md): the per-item
# report path's throughput must stay visible the same way.
_STREAMING_ROWS = {
    "streaming 100-yield": "streaming_items_s",
}

# Compiled-DAG rows (docs/compiled_dag.md): the channel hot loop's
# per-execute rate must stay visible the same way.
_COMPILED_DAG_ROWS = {
    "compiled_dag 3-stage": "compiled_dag_execs_s",
}

# Object-transfer rows (docs/object_transfer.md): the data plane's pull
# bandwidth must stay visible the same way (mb_per_s rows).
_OBJECT_TRANSFER_ROWS = {
    "pull 64MiB pipelined": "pull_pipelined_mb_s",
    "pull 64MiB striped 2-source busy hosts": "pull_striped_mb_s",
}

# Collective rows (docs/collective.md): the DCN data plane's allreduce /
# broadcast bandwidth must stay visible the same way.
_COLLECTIVE_ROWS = {
    "allreduce 64MiB ws4 new": "collective_allreduce_ws4_mb_s",
    "allreduce 64MiB ws2 new": "collective_allreduce_ws2_mb_s",
    "broadcast 64MiB ws4 new": "collective_broadcast_ws4_mb_s",
}

# Quantized-collective rows (docs/collective.md): the int8 wire-codec
# bandwidth and the async-overlap hidden fraction must stay visible the
# same way — the tracked field differs per row.
_COLLECTIVE_QUANT_ROWS = {
    "allreduce 64MiB ws4 sim-dcn int8": ("mb_per_s",
                                         "collective_quant_int8_mb_s"),
    "allreduce 64MiB ws4 sim-dcn fp32": ("mb_per_s",
                                         "collective_quant_fp32_mb_s"),
    "allreduce 8MiB ws4 overlap hidden-frac": (
        "hidden_frac", "collective_overlap_hidden_frac"),
}

# Disaggregated-serving rows (docs/serve_disagg.md): the A/B bars must
# stay visible the same way — rows are keyed by "metric" and the
# tracked value differs per row.
_SERVE_DISAGG_ROWS = {
    "serve_disagg_ab": ("ttft_p99_ratio", "disagg_ttft_p99_ratio"),
    "serve_disagg_disaggregated": ("tokens_per_s",
                                   "disagg_tokens_per_s"),
}


# Front-door rows (docs/serve_frontdoor.md): the closed-loop ingress
# row's throughput and prefix-affinity effectiveness must stay visible
# the same way.
_SERVE_FRONTDOOR_ROWS = {
    "serve_frontdoor_closed_loop": [
        ("tokens_per_s", "frontdoor_tokens_per_s"),
        ("prefix_hit_rate", "frontdoor_prefix_hit_rate"),
    ],
}


def serve_frontdoor_deltas(rows, committed):
    """Same contract as the other delta families for the front-door
    closed-loop row (two tracked fields per row)."""
    if not committed:
        return {}
    base = {}
    for r in committed.get("serve_frontdoor", []):
        if isinstance(r, dict) and r.get("metric") in _SERVE_FRONTDOOR_ROWS:
            for field, key in _SERVE_FRONTDOOR_ROWS[r["metric"]]:
                if r.get(field):
                    base[key] = r[field]
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        for field, key in _SERVE_FRONTDOOR_ROWS.get(row.get("metric"),
                                                    ()):
            if key not in base or not row.get(field):
                continue
            prev, cur = base[key], row[field]
            out[key] = {"committed": prev, "current": cur,
                        "ratio": round(cur / prev, 3)}
    return out


def serve_disagg_deltas(rows, committed):
    """Same contract as the other delta families for the serve_disagg
    section's bar rows."""
    if not committed:
        return {}
    base = {}
    for r in committed.get("serve_disagg", []):
        if isinstance(r, dict) and r.get("metric") in _SERVE_DISAGG_ROWS:
            field, key = _SERVE_DISAGG_ROWS[r["metric"]]
            if r.get(field):
                base[key] = (field, r[field])
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        spec = _SERVE_DISAGG_ROWS.get(row.get("metric"))
        if spec is None:
            continue
        field, key = spec
        if key not in base or not row.get(field):
            continue
        prev, cur = base[key][1], row[field]
        out[key] = {"committed": prev, "current": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def _committed_baseline(path):
    """Core rows of the committed MICROBENCH.json (None outside git)."""
    try:
        rel = os.path.relpath(path, REPO)
        blob = subprocess.run(
            ["git", "-C", REPO, "show", f"HEAD:{rel}"],
            capture_output=True, text=True, timeout=30)
        if blob.returncode != 0:
            return None
        return json.loads(blob.stdout)
    except (OSError, ValueError, subprocess.TimeoutExpired):
        return None


def control_plane_deltas(core_rows, committed):
    """{metric: {committed, current, ratio}} for the RPC-path rows."""
    if not committed:
        return {}
    base = {r["name"]: r.get("ops_per_s")
            for r in committed.get("core", []) if isinstance(r, dict)}
    out = {}
    for row in core_rows:
        key = _CONTROL_PLANE_ROWS.get(row.get("name"))
        if key is None or not base.get(row["name"]):
            continue
        prev, cur = base[row["name"]], row["ops_per_s"]
        out[key] = {"committed_ops_s": prev, "current_ops_s": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def streaming_deltas(stream_rows, committed):
    """Same contract for the streaming section's items/s rows."""
    if not committed:
        return {}
    base = {r["name"]: r.get("items_per_s")
            for r in committed.get("streaming", []) if isinstance(r, dict)}
    out = {}
    for row in stream_rows:
        if not isinstance(row, dict):
            continue
        key = _STREAMING_ROWS.get(row.get("name"))
        if key is None or not base.get(row["name"]) \
                or not row.get("items_per_s"):
            continue
        prev, cur = base[row["name"]], row["items_per_s"]
        out[key] = {"committed_items_s": prev, "current_items_s": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def compiled_dag_deltas(rows, committed):
    """Same contract for the compiled-DAG section's executes/s row."""
    if not committed:
        return {}
    base = {r["name"]: r.get("ops_per_s")
            for r in committed.get("compiled_dag", []) if isinstance(r, dict)}
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        key = _COMPILED_DAG_ROWS.get(row.get("name"))
        if key is None or not base.get(row["name"]) \
                or not row.get("ops_per_s"):
            continue
        prev, cur = base[row["name"]], row["ops_per_s"]
        out[key] = {"committed_execs_s": prev, "current_execs_s": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def object_transfer_deltas(rows, committed):
    """Same contract for the object-transfer section's bandwidth rows."""
    if not committed:
        return {}
    base = {r["name"]: r.get("mb_per_s")
            for r in committed.get("object_transfer", [])
            if isinstance(r, dict)}
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        key = _OBJECT_TRANSFER_ROWS.get(row.get("name"))
        if key is None or not base.get(row["name"]) \
                or not row.get("mb_per_s"):
            continue
        prev, cur = base[row["name"]], row["mb_per_s"]
        out[key] = {"committed_mb_s": prev, "current_mb_s": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def collective_deltas(rows, committed):
    """Same contract for the collective section's bandwidth rows."""
    if not committed:
        return {}
    base = {r["name"]: r.get("mb_per_s")
            for r in committed.get("collective", [])
            if isinstance(r, dict)}
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        key = _COLLECTIVE_ROWS.get(row.get("name"))
        if key is None or not base.get(row["name"]) \
                or not row.get("mb_per_s"):
            continue
        prev, cur = base[row["name"]], row["mb_per_s"]
        out[key] = {"committed_mb_s": prev, "current_mb_s": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def collective_quant_deltas(rows, committed):
    """Same contract for the collective_quant section; the tracked field
    differs per row (mb_per_s for the codec arms, hidden_frac for the
    overlap probe)."""
    if not committed:
        return {}
    base = {}
    for r in committed.get("collective_quant", []):
        if isinstance(r, dict) and r.get("name") in _COLLECTIVE_QUANT_ROWS:
            field, key = _COLLECTIVE_QUANT_ROWS[r["name"]]
            if r.get(field):
                base[key] = r[field]
    out = {}
    for row in rows:
        if not isinstance(row, dict):
            continue
        spec = _COLLECTIVE_QUANT_ROWS.get(row.get("name"))
        if spec is None:
            continue
        field, key = spec
        if key not in base or not row.get(field):
            continue
        prev, cur = base[key], row[field]
        out[key] = {"committed": prev, "current": cur,
                    "ratio": round(cur / prev, 3)}
    return out


def merge_preserve(out, prev, regenerated):
    """Carry over every section of `prev` that this run didn't regenerate.

    This is the fix for the round-4 data loss where a refresh that only
    ran {core,serve,data,serve_llm} rewrote the whole file and dropped
    the `rl` section: unknown or un-regenerated keys now survive.
    """
    meta = {"generated", "host", "note"}
    for key, val in prev.items():
        if key in meta or key in regenerated:
            continue
        out[key] = val
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-o", "--output",
                    default=os.path.join(REPO, "MICROBENCH.json"))
    ap.add_argument("--only", nargs="*", default=None,
                    help="run only these sections (others are preserved "
                         "from the existing output file)")
    args = ap.parse_args()
    selected = list(SECTIONS) if args.only is None else args.only
    unknown = [s for s in selected if s not in SECTIONS]
    if unknown:
        ap.error(f"unknown sections {unknown}; known: {list(SECTIONS)}")

    try:
        import psutil
        mem_gb = round(psutil.virtual_memory().total / 1024**3, 1)
        cpus = psutil.cpu_count(logical=False) or os.cpu_count()
    except ImportError:
        mem_gb = None
        cpus = os.cpu_count()

    out = {
        "generated": time.strftime("%Y-%m-%d %H:%M:%S"),
        "host": {"cpus": os.cpu_count(), "physical_cpus": cpus,
                 "memory_gb": mem_gb, "platform": platform.platform()},
        "note": "reference microbenchmark runs on 16+ core machines; this "
                "box is a 1-2 core heavily throttled VM whose absolute "
                "throughput drifts hour to hour — per-core comparisons "
                "only, and control-plane code comparisons should use "
                "interleaved same-box A/B ratios "
                "(control_plane_same_box_vs_seed), not cross-refresh "
                "absolute deltas",
    }

    regenerated = set()
    for name in selected:
        spec = SECTIONS[name]
        script = next((a for a in spec["cmd"] if a.endswith(".py")), None)
        if script and not os.path.exists(script):
            # tolerable on a default all-sections sweep (a section can be
            # registered ahead of its script landing), but an explicit
            # --only request for it is a user error
            if args.only is not None:
                ap.error(f"--only {name}: {script} does not exist")
            print(f"[collect] {name}: {script} missing, skipping "
                  "(existing numbers preserved)", flush=True)
            continue
        print(f"[collect] {name}: {' '.join(spec['cmd'][1:])}", flush=True)
        try:
            rows, rc = _run_json_lines(spec["cmd"], timeout=spec["timeout"])
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            # one failing section must not abort the sweep or discard the
            # sections that already completed
            print(f"[collect] {name}: FAILED ({e}); "
                  "keeping previous numbers", flush=True)
            continue
        if spec.get("last_list") and rows and isinstance(rows[-1], list):
            rows = rows[-1]
        if not rows or rc != 0:
            # no JSON output, or a crash after partial output: either way
            # the previous good numbers survive — a truncated row set
            # must never replace a complete one
            print(f"[collect] {name}: "
                  f"{'no JSON rows' if not rows else f'rc={rc} (partial)'}"
                  ", keeping previous numbers", flush=True)
            continue
        out[name] = rows
        regenerated.add(name)

    # merge-preserve: sections this run didn't regenerate (including the
    # envelope written by tests/test_scale_envelope.py, and any section a
    # future program adds) survive the refresh
    try:
        with open(args.output) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = {}
    merge_preserve(out, prev, regenerated)

    committed = None
    if regenerated & {"core", "streaming", "compiled_dag",
                      "object_transfer", "collective",
                      "collective_quant", "serve_disagg",
                      "serve_frontdoor"}:
        committed = _committed_baseline(args.output)
    if "core" in regenerated:
        deltas = control_plane_deltas(out["core"], committed)
        if deltas:
            out["control_plane_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed_ops_s']:,.0f} -> "
                      f"{d['current_ops_s']:,.0f} ops/s "
                      f"(x{d['ratio']}) [{tag}]", flush=True)
    if "streaming" in regenerated:
        deltas = streaming_deltas(out["streaming"], committed)
        if deltas:
            out["streaming_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed_items_s']:,.0f} -> "
                      f"{d['current_items_s']:,.0f} items/s "
                      f"(x{d['ratio']}) [{tag}]", flush=True)
    if "compiled_dag" in regenerated:
        deltas = compiled_dag_deltas(out["compiled_dag"], committed)
        if deltas:
            out["compiled_dag_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed_execs_s']:,.0f} -> "
                      f"{d['current_execs_s']:,.0f} execs/s "
                      f"(x{d['ratio']}) [{tag}]", flush=True)
    if "object_transfer" in regenerated:
        deltas = object_transfer_deltas(out["object_transfer"], committed)
        if deltas:
            out["object_transfer_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed_mb_s']:,.0f} -> "
                      f"{d['current_mb_s']:,.0f} MB/s "
                      f"(x{d['ratio']}) [{tag}]", flush=True)
    if "collective" in regenerated:
        deltas = collective_deltas(out["collective"], committed)
        if deltas:
            out["collective_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed_mb_s']:,.0f} -> "
                      f"{d['current_mb_s']:,.0f} MB/s "
                      f"(x{d['ratio']}) [{tag}]", flush=True)
    if "collective_quant" in regenerated:
        deltas = collective_quant_deltas(out["collective_quant"], committed)
        if deltas:
            out["collective_quant_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed']:,.2f} -> "
                      f"{d['current']:,.2f} (x{d['ratio']}) [{tag}]",
                      flush=True)
    if "serve_disagg" in regenerated:
        deltas = serve_disagg_deltas(out["serve_disagg"], committed)
        if deltas:
            out["serve_disagg_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed']:,.2f} -> "
                      f"{d['current']:,.2f} (x{d['ratio']}) [{tag}]",
                      flush=True)
    if "serve_frontdoor" in regenerated:
        deltas = serve_frontdoor_deltas(out["serve_frontdoor"],
                                        committed)
        if deltas:
            out["serve_frontdoor_deltas"] = deltas
            for key, d in deltas.items():
                tag = "REGRESSION" if d["ratio"] < 0.9 else "ok"
                print(f"[collect] {key}: {d['committed']:,.2f} -> "
                      f"{d['current']:,.2f} (x{d['ratio']}) [{tag}]",
                      flush=True)

    with open(args.output, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[collect] wrote {args.output}")


if __name__ == "__main__":
    main()
    if "jax" in sys.modules:
        raise RuntimeError(
            "collect_microbench must stay off JAX: its children need the chip")
