"""ray_tpu CLI.

Cite: /root/reference/python/ray/scripts/scripts.py — `ray start` (:529),
`ray stop`, `ray status`, `ray memory`, `ray timeline`, plus the job CLI
(/root/reference/python/ray/dashboard/modules/job/cli.py) and the state
CLI (`ray list ...`, experimental/state/state_cli.py) folded in as
subcommands.

Usage:
  python -m ray_tpu.scripts start --head [--num-cpus N] [--dashboard] [--block]
  python -m ray_tpu.scripts start --address HOST:PORT       # join as worker node
  python -m ray_tpu.scripts stop
  python -m ray_tpu.scripts status [--address ...]
  python -m ray_tpu.scripts list tasks|actors|nodes|jobs|objects|workers|placement-groups
  python -m ray_tpu.scripts summary tasks|actors|objects|metrics|stacks
  python -m ray_tpu.scripts events [--type T] [--node N] [--dossier ID]
  python -m ray_tpu.scripts memory
  python -m ray_tpu.scripts timeline [-o trace.json]
  python -m ray_tpu.scripts job submit|status|logs|stop|list ...
  python -m ray_tpu.scripts debug
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
from typing import Optional


def _resolve_address(args) -> str:
    addr = getattr(args, "address", None) or \
        os.environ.get("RAY_TPU_ADDRESS")
    if addr:
        return addr
    from ray_tpu.job_submission.job_manager import latest_session_address
    return latest_session_address()


def _connect(args) -> None:
    import ray_tpu
    if not ray_tpu.is_initialized():
        ray_tpu.init(address=_resolve_address(args))


# ------------------------------------------------------------------ start
def cmd_start(args) -> None:
    from ray_tpu.runtime.node import NodeProcesses, new_session_dir
    import atexit

    session_dir = new_session_dir()
    node = NodeProcesses(session_dir)
    # the daemons must outlive this CLI process unless --block
    if not args.block:
        atexit.unregister(node.stop)

    resources = json.loads(args.resources) if args.resources else {}
    if args.num_cpus is not None:
        resources["CPU"] = float(args.num_cpus)
    if args.num_tpus is not None:
        resources["TPU"] = float(args.num_tpus)

    if args.head:
        gcs_addr = node.start_gcs(port=args.port)
        print(f"GCS listening at {gcs_addr[0]}:{gcs_addr[1]}")
    else:
        if not args.address:
            sys.exit("--address required to join an existing cluster "
                     "(or pass --head)")
        host, port = args.address.rsplit(":", 1)
        gcs_addr = (host, int(port))
    node.start_raylet(gcs_addr, resources=resources or None,
                      object_store_memory=args.object_store_memory or None)
    print(f"node {node.node_id[:12]} started (session: {session_dir})")

    dashboard = None
    if args.head and args.dashboard:
        if args.block:
            from ray_tpu.dashboard import start_dashboard
            dashboard = start_dashboard(gcs_addr, port=args.dashboard_port)
            print(f"dashboard at http://{dashboard.host}:{dashboard.port}")
        else:
            # must outlive this CLI process -> own daemon
            from ray_tpu.runtime.node import _spawn
            proc = _spawn(
                [sys.executable, "-m", "ray_tpu.dashboard",
                 "--gcs-host", gcs_addr[0],
                 "--gcs-port", str(gcs_addr[1]),
                 "--port", str(args.dashboard_port)],
                session_dir, "dashboard")
            node.dashboard_proc = proc
            print(f"dashboard at http://127.0.0.1:{args.dashboard_port}")
    _write_pids(session_dir, node)

    if args.head:
        from ray_tpu._private.usage.usage_lib import record_usage_report
        from ray_tpu.runtime.gcs import GcsClient
        probe = GcsClient(gcs_addr)
        try:
            record_usage_report(session_dir, probe)
        finally:
            probe.close()
        print(f"connect with: ray_tpu.init(address="
              f"\"{gcs_addr[0]}:{gcs_addr[1]}\")")

    if args.block:
        print("--block: press Ctrl-C to stop this node")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            if dashboard is not None:
                dashboard.stop()
            node.stop()


def _write_pids(session_dir: str, node) -> None:
    pids = [p.pid for p in (node.gcs_proc, node.raylet_proc,
                            getattr(node, "dashboard_proc", None))
            if p is not None]
    with open(os.path.join(session_dir, "pids.json"), "w") as f:
        json.dump(pids, f)


def _latest_session_dir() -> Optional[str]:
    """Session dir advertised by the most recent local `init`/`start`."""
    try:
        with open(os.path.join("/tmp", "ray_tpu_sessions",
                               "latest.json")) as f:
            return json.load(f)["session_dir"]
    except (OSError, ValueError, KeyError):
        return None


def cmd_stop(args) -> None:
    """Kill daemons of the latest session (plus their workers).
    ``--session-dir`` stops exactly one session — the cluster launcher's
    teardown path on hosts shared by several nodes/clusters."""
    import subprocess
    killed = 0
    base = "/tmp/ray_tpu_sessions"
    sessions = []
    one_session = getattr(args, "session_dir", None)
    if one_session:
        sessions = [one_session]
    elif args.all and os.path.isdir(base):
        sessions = [os.path.join(base, d) for d in os.listdir(base)
                    if d.startswith("session_")]
    else:
        latest = _latest_session_dir()
        if latest:
            sessions = [latest]
    all_pids = []
    for sess in sessions:
        pid_file = os.path.join(sess, "pids.json")
        try:
            with open(pid_file) as f:
                pids = json.load(f)
        except (OSError, ValueError):
            continue
        for pid in pids:
            try:
                os.kill(pid, signal.SIGTERM)
                killed += 1
                all_pids.append(pid)
            except ProcessLookupError:
                pass
        # a session's daemons/workers carry its dir on their command line
        # (match only runtime processes, not this CLI invocation itself)
        subprocess.run(["pkill", "-f", f"ray_tpu.runtime.*{sess}"],
                       check=False)
    # grace period, then SIGKILL stragglers (reference `ray stop` waits for
    # procs to exit and force-kills what remains)
    def _alive(pid: int) -> bool:
        try:
            os.kill(pid, 0)
            return True
        except ProcessLookupError:
            return False
        except PermissionError:
            return True

    deadline = time.monotonic() + 5.0
    while all_pids and time.monotonic() < deadline:
        all_pids = [p for p in all_pids if _alive(p)]
        if all_pids:
            time.sleep(0.2)
    for pid in all_pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    if not one_session:
        # workers/daemons not tracked by pid files (started via init())
        subprocess.run(
            ["pkill", "-f",
             "ray_tpu.(runtime.(gcs|raylet|worker_main)|dashboard)"],
            check=False)
    print(f"stopped {killed} tracked daemon(s)")


# -------------------------------------------------- cluster launcher verbs
# (reference scripts.py:1161 `ray up` + down/attach/exec/submit)
def cmd_up(args) -> None:
    from ray_tpu.autoscaler.cluster_launcher import create_or_update_cluster
    create_or_update_cluster(args.config, dry_run=args.dry_run,
                             no_start_workers=args.no_workers)


def cmd_down(args) -> None:
    from ray_tpu.autoscaler.cluster_launcher import teardown_cluster
    teardown_cluster(args.config)


def cmd_attach(args) -> None:
    from ray_tpu.autoscaler.cluster_launcher import attach_cluster
    attach_cluster(args.config)


def cmd_exec(args) -> None:
    import shlex
    from ray_tpu.autoscaler.cluster_launcher import exec_cluster
    # shlex.join preserves the user's quoting through the remote re-parse
    rc, _ = exec_cluster(args.config, shlex.join(args.command))
    sys.exit(rc)


def cmd_submit(args) -> None:
    from ray_tpu.autoscaler.cluster_launcher import submit_job
    rc, _ = submit_job(args.config, args.script, args.script_args)
    sys.exit(rc)


# ----------------------------------------------------------------- status
def cmd_status(args) -> None:
    _connect(args)
    import ray_tpu
    nodes = ray_tpu.nodes()
    alive = [n for n in nodes if n["alive"]]
    print(f"Nodes: {len(alive)} alive / {len(nodes)} total")
    total = ray_tpu.cluster_resources()
    avail = ray_tpu.available_resources()
    print("Resources:")
    for r in sorted(total):
        print(f"  {r}: {avail.get(r, 0):g} / {total[r]:g} available")
    for n in alive:
        print(f"  node {n['node_id'][:12]} @ "
              f"{n['address'][0]}:{n['address'][1]} {n['resources']}")
    # cluster health table off the heartbeat-piggybacked snapshots
    # (docs/observability.md node health plane)
    from ray_tpu.experimental.state.api import node_health_table
    health_lines = node_health_table(nodes)
    if health_lines:
        print("Health:")
        for line in health_lines:
            print("  " + line)


def cmd_list(args) -> None:
    _connect(args)
    from ray_tpu.experimental import state
    fn = {
        "tasks": state.list_tasks,
        "actors": state.list_actors,
        "nodes": state.list_nodes,
        "jobs": state.list_jobs,
        "objects": state.list_objects,
        "workers": state.list_workers,
        "placement-groups": state.list_placement_groups,
    }[args.resource]
    rows = fn(limit=args.limit)
    for row in rows:
        row = {k: v for k, v in row.items() if k != "events"}
        print(json.dumps(row, default=str))
    print(f"({len(rows)} {args.resource})", file=sys.stderr)


def cmd_summary(args) -> None:
    _connect(args)
    from ray_tpu.experimental import state
    if args.resource == "metrics":
        # runtime telemetry as a sorted operator table (top RPC methods
        # by p50/p95, stream stalls, pin counts) — docs/observability.md
        print(state.metrics_summary())
        return
    if args.resource == "training":
        # the goodput ledger: init/compile/productive/checkpoint/idle
        # buckets, MFU and goodput per rank (docs/observability.md
        # training performance plane)
        print(state.training_summary_text(getattr(args, "run", None)))
        return
    if args.resource == "stacks":
        _summary_stacks(args)
        return
    fn = {"tasks": state.summarize_tasks,
          "actors": state.summarize_actors,
          "objects": state.summarize_objects}[args.resource]
    print(json.dumps(fn(), indent=1, default=str))


def _summary_stacks(args) -> None:
    """`ray-tpu summary stacks [--pid P | --actor A]`: per-thread stack
    dumps + a short flame sample of live cluster processes, without
    gdb (docs/observability.md).  Default: the GCS and every raylet;
    --pid targets the worker process with that pid, --actor the worker
    hosting that actor (id prefix or name)."""
    from ray_tpu._private import rpc
    from ray_tpu._private.profiler import stacks_text, top_summary
    from ray_tpu.experimental import state
    from ray_tpu.runtime.core_worker import get_global_worker

    gcs = get_global_worker().gcs

    def show(title, reply):
        print(f"===== {title} =====")
        print(stacks_text(reply.get("threads", {})))
        folded = reply.get("folded")
        if folded:
            print("-- hot leaves (sampled) --")
            print(top_summary(folded, limit=8))
        print()

    pid = getattr(args, "pid", None)
    actor = getattr(args, "actor", None)
    if actor:
        cand = next(
            (a for a in state.list_actors()
             if a["actor_id"].startswith(actor)
             or (a.get("name") or "") == actor), None)
        if cand is None or not cand.get("address"):
            sys.exit(f"no live actor matching {actor!r}")
        conn = rpc.connect(tuple(cand["address"]), timeout=5.0)
        try:
            show(f"actor {cand['actor_id'][:12]}",
                 conn.call("dump_stacks", {}, timeout=30))
        finally:
            conn.close()
        return
    if pid:
        for w in state.list_workers():
            if w.get("pid") == int(pid) and w.get("alive"):
                node = next((n for n in state.list_nodes()
                             if n["node_id"] == w["node_id"]), None)
                if node is None:
                    sys.exit(f"worker pid {pid}'s node "
                             f"{w['node_id'][:12]} is gone")
                conn = rpc.connect(tuple(node["address"]), timeout=5.0)
                try:
                    show(f"worker pid {pid}",
                         conn.call("dump_stacks", {"pid": int(pid)},
                                   timeout=30))
                finally:
                    conn.close()
                return
        sys.exit(f"no live worker with pid {pid}")
    show("gcs", gcs.call("dump_stacks", {}, timeout=30))
    for node in state.list_nodes():
        if not node.get("alive"):
            continue
        try:
            conn = rpc.connect(tuple(node["address"]), timeout=5.0)
        except OSError:
            continue
        try:
            show(f"raylet {node['node_id'][:12]}",
                 conn.call("dump_stacks", {}, timeout=30))
        except (rpc.RpcError, ConnectionError, TimeoutError):
            pass
        finally:
            conn.close()


def cmd_drain(args) -> None:
    """`ray-tpu drain <node-id-prefix>`: graceful-preemption drain of
    one node (docs/fault_tolerance.md): emits NODE_PREEMPTING with the
    grace deadline, the raylet stops granting leases, lets short tasks
    finish and evacuates primary object copies to surviving nodes."""
    _connect(args)
    from ray_tpu.runtime.core_worker import get_global_worker
    worker = get_global_worker()
    matches = [n for n in worker.gcs.call("list_nodes")
               if n["alive"] and n["node_id"].startswith(args.node_id)]
    if not matches:
        sys.exit(f"no alive node matching {args.node_id!r}")
    if len(matches) > 1:
        sys.exit(f"ambiguous node prefix {args.node_id!r}: "
                 + ", ".join(n["node_id"][:12] for n in matches))
    node = matches[0]
    # omit grace_s when unset so the server-side CONFIG.drain_grace_s
    # default applies (an explicit --grace 0 still means "die ASAP")
    payload = {
        "node_id": node["node_id"],
        "reason": args.reason or "operator drain (ray-tpu drain)",
    }
    if args.grace is not None:
        payload["grace_s"] = args.grace
    reply = worker.gcs.call("drain_node", payload)
    if not reply.get("ok"):
        sys.exit(f"drain refused: {reply.get('reason')}")
    grace = "default" if args.grace is None else f"{args.grace:g}s"
    print(f"node {node['node_id'][:12]} draining "
          f"(grace {grace}, forwarded={reply.get('forwarded')})")


def cmd_events(args) -> None:
    """`ray-tpu events`: the cluster event table as an operator table;
    `--dossier <id>` dumps a crash dossier instead."""
    _connect(args)
    from ray_tpu.experimental import state
    if args.dossier:
        from ray_tpu._private.cluster_events import format_dossier
        d = state.get_dossier(args.dossier)
        if d is None:
            sys.exit(f"no dossier matching {args.dossier!r} "
                     "(rotated out, or the process died cleanly)")
        print(format_dossier(d))
        return
    rows = state.list_cluster_events(
        node_id=args.node, job_id=args.job, actor_id=args.actor,
        worker_id=args.worker, severity=args.severity,
        min_severity=args.min_severity, type=args.type,
        limit=args.limit)
    print("%-8s %-7s %-22s %-8s %-12s %s" % (
        "TIME", "SEV", "TYPE", "SOURCE", "NODE", "MESSAGE"))
    for e in rows:
        print("%-8s %-7s %-22s %-8s %-12s %s" % (
            time.strftime("%H:%M:%S", time.localtime(e.get("ts", 0))),
            e.get("severity", "?")[:7], e.get("type", "?")[:22],
            e.get("source", "")[:8],
            str(e.get("node_id") or "")[:12],
            e.get("message", "")))
    print(f"({len(rows)} events)", file=sys.stderr)


def cmd_traces(args) -> None:
    """`ray-tpu traces`: the trace directory as an operator table —
    newest first, with the SLO verdict per request root;
    `--slo-violations` narrows to requests that missed a target
    (docs/observability.md request tracing plane)."""
    _connect(args)
    from ray_tpu.experimental import state
    rows = state.list_traces(slo_violations=args.slo_violations,
                             route=args.route, limit=args.limit)
    print("%-18s %-8s %-22s %6s %9s %9s %-9s %s" % (
        "TRACE", "TIME", "ROUTE", "SPANS", "TTFT(ms)", "TPOT(ms)",
        "SLO", "STATUS"))
    for r in rows:
        slo = ("-" if r.get("slo_ok") is None else
               ("ok" if r["slo_ok"] else
                "VIOL:" + ",".join(r.get("slo_violated") or [])))
        print("%-18s %-8s %-22s %6d %9s %9s %-9s %s" % (
            r["trace_id"][:16] + "..",
            time.strftime("%H:%M:%S", time.localtime(r.get("start") or 0)),
            (r.get("route") or r.get("name") or "")[:22],
            r.get("nspans", 0),
            r.get("ttft_ms") if r.get("ttft_ms") is not None else "-",
            r.get("tpot_ms") if r.get("tpot_ms") is not None else "-",
            slo, r.get("status") or ""))
    print(f"({len(rows)} traces)", file=sys.stderr)


def cmd_trace(args) -> None:
    """`ray-tpu trace <trace_id>`: one request's span tree — which hop
    (queue wait, prefill, handoff pull, import wait, decode) ate the
    budget.  `--perfetto FILE` exports the trace merged with the
    cluster timeline's same-trace slices for ui.perfetto.dev."""
    _connect(args)
    from ray_tpu.experimental import state
    trace = state.get_trace(args.trace_id)
    if trace is None:
        sys.exit(f"no trace matching {args.trace_id!r} "
                 "(rotated out, unsampled, or not flushed yet)")
    print(state.trace_tree_text(trace))
    if args.perfetto:
        events = state.trace_timeline(trace["trace_id"], args.perfetto)
        print(f"wrote {len(events)} merged trace events to "
              f"{args.perfetto} (open in ui.perfetto.dev)")


def cmd_memory(args) -> None:
    _connect(args)
    from ray_tpu.experimental.state import memory_summary
    print(memory_summary())


def cmd_timeline(args) -> None:
    _connect(args)
    from ray_tpu.experimental.state import timeline
    out = args.output or f"timeline-{int(time.time())}.json"
    events = timeline(out)
    print(f"wrote {len(events)} trace events to {out} "
          "(open in chrome://tracing or ui.perfetto.dev)")


def cmd_doctor(args) -> None:
    """`ray-tpu doctor`: the cross-plane correlation report — node
    health, recovery episodes + SLO verdicts, recent WARNING+ events,
    straggler flags, worst-trace exemplars and open dossiers ranked
    into findings with evidence lines (docs/observability.md)."""
    import json as _json
    _connect(args)
    from ray_tpu.experimental import state
    if args.json:
        print(_json.dumps(state.doctor_report(), indent=1,
                          default=str))
        return
    print(state.doctor_report_text())


def cmd_debug_bundle(args) -> None:
    """`ray-tpu debug-bundle`: export every observability plane —
    events, dossiers, traces, metrics snapshot + history, step stats,
    recovery episodes, doctor report, merged Perfetto timeline — as
    one tarball for offline forensics."""
    _connect(args)
    from ray_tpu.experimental import state
    out = args.output or f"debug-bundle-{int(time.time())}.tar.gz"
    manifest = state.collect_debug_bundle(out)
    total = sum(manifest["members"].values())
    print(f"wrote {out}: {len(manifest['members'])} members, "
          f"{total:,} bytes")
    for name, size in sorted(manifest["members"].items()):
        print(f"  {name:32s} {size:>10,} B")


def cmd_debug(args) -> None:
    _connect(args)
    from ray_tpu.util.rpdb import list_breakpoints
    sessions = list_breakpoints()
    if not sessions:
        print("no active breakpoints")
        return
    for bid, addr in sessions:
        print(f"{bid}  {addr}   (attach: nc {addr.replace(':', ' ')})")


def cmd_profile(args) -> None:
    """Flame-sample a live cluster process (reference `ray stack`/py-spy
    reporter path): GCS by default, a raylet with --node, one of its
    workers with --worker (with --device that worker, e.g. a serve
    replica, also captures a jax.profiler trace of the window: the
    engine's spans and named programs on the device's clock).
    `--group <name>` gang-fans-out instead:
    every rank of the named training run captures the SAME time window
    (folded host stacks always; a jax.profiler device trace with
    --device, TPU only — on a CPU-only box each rank reports the
    caveat and ships host stacks) and the captures merge into one
    Perfetto trace keyed by rank, correlated with the run's STEP
    timeline slices (docs/observability.md).  Prints folded stacks
    (-o writes .folded, or the merged .json for --group) or a top-N
    leaf summary."""
    from ray_tpu._private import rpc
    from ray_tpu._private.profiler import (folded_text, split_leaf_detail,
                                           top_summary)
    from ray_tpu.runtime.gcs import GcsClient

    if args.worker and not args.node:
        sys.exit("--worker requires --node (the worker's raylet)")
    if args.device and not (args.group or args.worker):
        sys.exit("--device needs the process that holds the chip: "
                 "--group (a training gang) or --node with --worker "
                 "(e.g. a serve replica)")
    addr = _resolve_address(args)
    host, port = addr.rsplit(":", 1)
    gcs = GcsClient((host, int(port)))
    try:
        if args.group:
            _profile_group(args, gcs)
            return
        if args.node:
            node = next((n for n in gcs.call("list_nodes")
                         if n["node_id"].startswith(args.node)
                         and n.get("alive")), None)
            if node is None:
                sys.exit(f"no alive node matching {args.node!r}")
            conn = rpc.connect(tuple(node["address"]), timeout=5.0)
            req = {"duration": args.duration, "worker_id": args.worker}
            if args.device:
                req["device"] = True
            try:
                counts = conn.call(
                    "profile", req,
                    timeout=args.duration + (100 if args.device else 40))
            finally:
                conn.close()
            if args.device:
                # the capture dict: host stacks plus a jax.profiler
                # trace of the same window, which holds the program's
                # own spans (engine.*, train.*) and named programs
                if counts.get("device_trace"):
                    print(f"device trace at {counts['device_trace']} "
                          "(on the worker's host)")
                else:
                    print(counts.get("device_error"), file=sys.stderr)
                counts = counts["folded"]
        else:
            counts = gcs.call("profile", {"duration": args.duration},
                              timeout=args.duration + 40)
    finally:
        gcs.close()
    if args.output:
        clean, _ = split_leaf_detail(counts)
        with open(args.output, "w") as f:
            f.write(folded_text(counts) + "\n")
        print(f"wrote {sum(clean.values())} samples to {args.output}")
    else:
        print(top_summary(counts))


def _profile_group(args, gcs) -> None:
    """Gang-coordinated capture: one profile window on every rank of a
    training run, merged into a single Perfetto trace keyed by rank."""
    import threading
    from ray_tpu._private import rpc
    from ray_tpu._private import step_stats
    from ray_tpu._private.profiler import merge_folded, top_summary

    info = gcs.call("list_step_stats", {"run": args.group})
    runs = info.get("runs") or []
    if not runs:
        sys.exit(f"no training run matching {args.group!r} has reported "
                 "step stats (is the gang running with "
                 "RAY_TPU_STEP_STATS on?)")
    run = runs[-1]   # latest matching
    ranks = {int(r): m for r, m in (run.get("ranks") or {}).items()
             if m.get("address")}
    if not ranks:
        sys.exit(f"run {run['run']}: no rank has reported its RPC "
                 "address yet")
    results: dict = {}
    errors: dict = {}

    def capture(rank: int, meta: dict) -> None:
        try:
            conn = rpc.connect(tuple(meta["address"]), timeout=5.0)
            try:
                results[rank] = conn.call(
                    "profile", {"duration": args.duration,
                                "device": bool(args.device)},
                    timeout=args.duration + 40)
            finally:
                conn.close()
        except Exception as e:
            errors[rank] = repr(e)

    t_start = time.time()
    threads = [threading.Thread(target=capture, args=(r, m), daemon=True)
               for r, m in sorted(ranks.items())]
    for t in threads:
        t.start()   # all ranks sample the same wall-clock window
    for t in threads:
        t.join(args.duration + 60)
    t_end = time.time()
    for rank, err in sorted(errors.items()):
        print(f"rank {rank}: capture failed: {err}", file=sys.stderr)
    if not results:
        sys.exit("no rank produced a capture")

    per_rank = {}
    merged: dict = {}
    for rank, reply in sorted(results.items()):
        folded = reply.get("folded", reply) if isinstance(reply, dict) \
            and "folded" in reply else reply
        per_rank[rank] = folded
        merge_folded(merged, folded)
        if isinstance(reply, dict):
            if reply.get("device_trace"):
                print(f"rank {rank}: device trace at "
                      f"{reply['device_trace']} (on the rank's host)")
            elif reply.get("device_error"):
                print(f"rank {rank}: {reply['device_error']}",
                      file=sys.stderr)
    # correlate with the run's STEP slices from the GCS task table
    try:
        rows = gcs.call("list_task_events",
                        {"name": f"train_step:{run['run']}",
                         "limit": 4096})
    except Exception:
        rows = []
    step_events = step_stats.step_trace_events(
        rows, window=(t_start - 300.0, t_end))
    trace = step_stats.merged_profile_trace(
        per_rank, interval_s=0.01, t_start=t_start,
        step_events=step_events)
    out = args.output or f"profile-{run['run']}.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    print(f"wrote {len(trace)} trace events for {len(per_rank)} ranks "
          f"to {out} (open in ui.perfetto.dev)")
    print(top_summary(merged))


def cmd_stack(args) -> None:
    """Dump every session process's Python thread stacks (py-spy /
    `ray stack` analog): SIGUSR1 each process whose cmdline references the
    session dir, then print the faulthandler dumps they wrote."""
    import glob

    session_dir = getattr(args, "session_dir", None) or \
        _latest_session_dir()
    if not session_dir:
        print("no session found; pass --session-dir")
        return
    session_dir = os.path.abspath(session_dir).rstrip("/")
    # faulthandler APPENDS to each per-pid file: remember current sizes so
    # only this run's dumps are printed (older runs' output and files of
    # dead/recycled pids would otherwise masquerade as live stacks)
    offsets = {}
    for path in glob.glob(os.path.join(session_dir, "logs",
                                       "stack_*.txt")):
        try:
            offsets[path] = os.path.getsize(path)
        except OSError:
            pass
    signalled = []
    for proc_dir in glob.glob("/proc/[0-9]*"):
        try:
            with open(os.path.join(proc_dir, "cmdline"), "rb") as f:
                cmdline = f.read().decode(errors="replace")
        except OSError:
            continue
        if session_dir in cmdline and "ray_tpu" in cmdline:
            pid = int(os.path.basename(proc_dir))
            if pid == os.getpid():
                continue
            try:
                os.kill(pid, signal.SIGUSR1)
                signalled.append(pid)
            except OSError:
                pass
    if not signalled:
        print(f"no ray_tpu processes found for session {session_dir}")
        return
    time.sleep(0.4)  # let faulthandler flush
    print(f"signalled {len(signalled)} processes: {signalled}")
    for pid in signalled:
        path = os.path.join(session_dir, "logs", f"stack_{pid}.txt")
        try:
            with open(path) as f:
                f.seek(offsets.get(path, 0))
                content = f.read().strip()
        except OSError:
            continue
        if content:
            print(f"\n===== pid {pid} =====")
            print(content)


def cmd_microbenchmark(args) -> None:
    from ray_tpu._private.ray_perf import main as perf_main
    perf_main(min_time=args.min_time)


# ------------------------------------------------------------------- jobs
def cmd_job(args) -> None:
    from ray_tpu.job_submission import JobSubmissionClient
    client = JobSubmissionClient(getattr(args, "address", None))
    if args.job_cmd == "submit":
        import shlex
        entrypoint = list(args.entrypoint)
        if entrypoint and entrypoint[0] == "--":
            entrypoint = entrypoint[1:]
        sid = client.submit_job(
            entrypoint=shlex.join(entrypoint),
            runtime_env=json.loads(args.runtime_env)
            if args.runtime_env else None)
        print(f"submitted: {sid}")
        if args.wait:
            status = client.wait_until_finished(sid, timeout=args.timeout)
            print(f"{sid}: {status}")
            print(client.get_job_logs(sid), end="")
            sys.exit(0 if status == "SUCCEEDED" else 1)
    elif args.job_cmd == "status":
        print(client.get_job_status(args.submission_id))
    elif args.job_cmd == "logs":
        print(client.get_job_logs(args.submission_id), end="")
    elif args.job_cmd == "stop":
        print("stopping" if client.stop_job(args.submission_id)
              else "not running")
    elif args.job_cmd == "list":
        for info in client.list_jobs():
            print(f"{info.submission_id}  {info.status:10s}  "
                  f"{info.entrypoint}")


def cmd_lint(args) -> None:
    """`ray-tpu lint`: the raylint static analyzer over the package
    (docs/static_analysis.md).  Exits nonzero on any unallowlisted
    violation — the same entry the tier-1 gate runs."""
    from ray_tpu._private.analysis import cli as lint_cli
    argv = []
    if args.root:
        argv += ["--root", args.root]
    for r in args.rules or ():
        argv += ["--rule", r]
    if args.no_baseline:
        argv.append("--no-baseline")
    if args.list_rules:
        argv.append("--list-rules")
    sys.exit(lint_cli.run(argv))


def cmd_serve(args) -> None:
    """serve status / run / deploy / shutdown (reference `serve` CLI)."""
    _connect(args)
    from ray_tpu import serve as serve_api
    from ray_tpu.serve.schema import ServeApplicationSchema

    if args.serve_cmd == "status":
        for name, st in sorted(serve_api.status().items()):
            print(f"{name:24s} {st['status']:10s} "
                  f"{st['running_replicas']}/{st['target_replicas']} replicas "
                  f"v{st['version']}")
    elif args.serve_cmd == "run":
        schema = ServeApplicationSchema(import_path=args.import_path)
        schema.apply()
        print(f"deployed {args.import_path}")
        if args.blocking:
            import time as _time
            try:
                while True:
                    _time.sleep(3600)
            except KeyboardInterrupt:
                serve_api.shutdown()
                print("serve shut down")
    elif args.serve_cmd == "deploy":
        import yaml
        with open(args.config_file) as f:
            cfg = yaml.safe_load(f)
        apps = cfg.get("applications", [cfg])
        for app in apps:
            ServeApplicationSchema.from_dict(app).apply()
            print(f"deployed {app.get('name', 'default')}")
    elif args.serve_cmd == "shutdown":
        serve_api.shutdown()
        print("serve shut down")


# ------------------------------------------------------------------ parser
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="ray_tpu",
                                description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head or worker node")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", help="GCS host:port to join")
    sp.add_argument("--port", type=int, default=0, help="GCS port (head)")
    sp.add_argument("--num-cpus", type=float)
    sp.add_argument("--num-tpus", type=float)
    sp.add_argument("--resources", help="extra resources as JSON")
    sp.add_argument("--object-store-memory", type=int)
    sp.add_argument("--dashboard", action="store_true")
    sp.add_argument("--dashboard-port", type=int, default=8265)
    sp.add_argument("--block", action="store_true")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="stop local daemons")
    sp.add_argument("--all", action="store_true",
                    help="stop every session, not just the latest")
    sp.add_argument("--session-dir",
                    help="stop exactly this session (launcher teardown)")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("up", help="launch a cluster from a YAML config")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("--dry-run", action="store_true",
                    help="print the gcloud/SSH plan without executing")
    sp.add_argument("--no-workers", action="store_true",
                    help="bring up only the head node")
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="tear down a launched cluster")
    sp.add_argument("config", help="cluster YAML path (or cluster name)")
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("attach", help="interactive shell on the head node")
    sp.add_argument("config", help="cluster YAML path")
    sp.set_defaults(fn=cmd_attach)

    sp = sub.add_parser("exec", help="run a shell command on the head node")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("command", nargs=argparse.REMAINDER,
                    help="command to run")
    sp.set_defaults(fn=cmd_exec)

    sp = sub.add_parser("submit",
                        help="run a driver script against the cluster")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("script", help="local python script to run on the head")
    sp.add_argument("script_args", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_submit)

    for name, fn in (("status", cmd_status), ("memory", cmd_memory),
                     ("debug", cmd_debug)):
        sp = sub.add_parser(name)
        sp.add_argument("--address")
        sp.set_defaults(fn=fn)

    sp = sub.add_parser("list", help="list cluster state")
    sp.add_argument("resource", choices=[
        "tasks", "actors", "nodes", "jobs", "objects", "workers",
        "placement-groups"])
    sp.add_argument("--address")
    sp.add_argument("--limit", type=int, default=100)
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser("summary", help="summarize cluster state")
    sp.add_argument("resource",
                    choices=["tasks", "actors", "objects", "metrics",
                             "stacks", "training"])
    sp.add_argument("--address")
    sp.add_argument("--pid", help="(stacks) worker pid to sample")
    sp.add_argument("--actor",
                    help="(stacks) actor id prefix or name to sample")
    sp.add_argument("--run",
                    help="(training) run id or group prefix "
                         "(default: latest run)")
    sp.set_defaults(fn=cmd_summary)

    sp = sub.add_parser("drain",
                        help="gracefully drain a node before preemption "
                             "(stop leases, evacuate objects)")
    sp.add_argument("node_id", help="node id hex (prefix ok)")
    sp.add_argument("--grace", type=float, default=None,
                    help="grace window in seconds before the node is "
                         "expected to die (default: the cluster's "
                         "drain_grace_s)")
    sp.add_argument("--reason", default="")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_drain)

    sp = sub.add_parser("events",
                        help="cluster lifecycle events / crash dossiers")
    sp.add_argument("--address")
    sp.add_argument("--severity", help="exact severity filter")
    sp.add_argument("--min-severity", dest="min_severity",
                    help="severity floor (DEBUG|INFO|WARNING|ERROR)")
    sp.add_argument("--type", help="event type (e.g. WORKER_EXIT)")
    sp.add_argument("--node", help="node id prefix")
    sp.add_argument("--job", help="job id")
    sp.add_argument("--actor", help="actor id prefix")
    sp.add_argument("--worker", help="worker id prefix")
    sp.add_argument("--limit", type=int, default=100)
    sp.add_argument("--dossier",
                    help="dump the crash dossier with this id "
                         "(worker/node id hex) instead of listing events")
    sp.set_defaults(fn=cmd_events)

    sp = sub.add_parser("timeline", help="export Chrome trace")
    sp.add_argument("-o", "--output")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser("doctor",
                        help="cross-plane health report: ranked "
                             "findings with evidence lines")
    sp.add_argument("--address")
    sp.add_argument("--json", action="store_true",
                    help="emit the raw report as JSON")
    sp.set_defaults(fn=cmd_doctor)

    sp = sub.add_parser("debug-bundle",
                        help="export all observability planes as one "
                             "tarball for offline forensics")
    sp.add_argument("-o", "--output",
                    help="tarball path (default debug-bundle-"
                         "<ts>.tar.gz)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_debug_bundle)

    sp = sub.add_parser("traces",
                        help="list request traces (span table)")
    sp.add_argument("--address")
    sp.add_argument("--slo-violations", dest="slo_violations",
                    action="store_true",
                    help="only requests that missed a TTFT/TPOT target")
    sp.add_argument("--route", help="route/deployment prefix filter")
    sp.add_argument("--limit", type=int, default=50)
    sp.set_defaults(fn=cmd_traces)

    sp = sub.add_parser("trace",
                        help="show one request trace's span tree")
    sp.add_argument("trace_id", help="trace id (prefix ok)")
    sp.add_argument("--address")
    sp.add_argument("--perfetto", metavar="FILE",
                    help="also export the trace merged with the "
                         "timeline's same-trace slices")
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("stack",
                        help="dump all session processes' thread stacks")
    sp.add_argument("--session-dir")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("profile",
                        help="flame-sample a live cluster process, or a "
                             "whole training gang with --group")
    sp.add_argument("--address")
    sp.add_argument("--node", help="node id prefix (default: the GCS)")
    sp.add_argument("--worker", help="worker id prefix on that node")
    sp.add_argument("--group",
                    help="training run id or group prefix: capture the "
                         "same window on EVERY rank and merge into one "
                         "Perfetto trace keyed by rank")
    sp.add_argument("--device", action="store_true",
                    help="(--group, or --node with --worker) also "
                         "capture a jax.profiler device trace in each "
                         "process (TPU only; CPU-only boxes report the "
                         "caveat and ship host stacks)")
    sp.add_argument("--duration", type=float, default=2.0)
    sp.add_argument("-o", "--output",
                    help="write folded stacks (.folded) or the merged "
                         "gang trace (.json) here")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser("lint",
                        help="raylint: framework-invariant static "
                             "analyzer (docs/static_analysis.md)")
    sp.add_argument("--root", help="package dir to lint (default: the "
                                   "installed ray_tpu package)")
    sp.add_argument("--rule", action="append", dest="rules",
                    help="run only this rule (repeatable)")
    sp.add_argument("--no-baseline", action="store_true",
                    help="ignore the allowlist baseline")
    sp.add_argument("--list-rules", action="store_true",
                    help="print the checker catalog and exit")
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser("microbenchmark",
                        help="core-runtime ops/s suite (ray_perf analog)")
    sp.add_argument("--min-time", type=float, default=2.0)
    sp.set_defaults(fn=cmd_microbenchmark)

    sp = sub.add_parser("serve", help="serve deployments")
    ssub = sp.add_subparsers(dest="serve_cmd", required=True)
    s = ssub.add_parser("status")
    s.add_argument("--address")
    s = ssub.add_parser("run")
    s.add_argument("import_path", help="module:app bound Application")
    s.add_argument("--address")
    s.add_argument("--blocking", action="store_true")
    s = ssub.add_parser("deploy")
    s.add_argument("config_file", help="YAML app config")
    s.add_argument("--address")
    s = ssub.add_parser("shutdown")
    s.add_argument("--address")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("job", help="job submission")
    jsub = sp.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--address")
    j.add_argument("--runtime-env", help="runtime env as JSON")
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=3600.0)
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        j = jsub.add_parser(name)
        j.add_argument("--address")
        j.add_argument("submission_id")
    j = jsub.add_parser("list")
    j.add_argument("--address")
    sp.set_defaults(fn=cmd_job)

    return p


def main(argv: Optional[list] = None) -> None:
    args = build_parser().parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
