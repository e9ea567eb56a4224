"""What the PROGRAM wrote into the run's ``jax.profiler`` trace, reduced
to numbers: its named programs on the device and its own host spans, on
the one clock both share.  ``lib/trace.py`` reads what the device calls
things; this reads what the program calls them (ISSUE 24):

- ``modules[name]``: device time by program, from the events of the line
  ``XLA Modules`` of each ``/device:TPU:<n>`` plane (one event per
  execution of a jitted function), averaged over the device planes.
  ``jit_engine_decode_block(1234)`` is keyed ``engine_decode_block``.
  An execution under way when tracing starts or stops is cut at the
  trace's edge and cannot be told from a whole one (the trace clamps
  both to the same instant as the operations inside), so ``total_s`` is
  the time the program ran INSIDE the trace, and ``count`` includes the
  cut ones: divide by work counted inside the same trace
  (``kernel_runs``, the harness's traced steps), not by ``count``;
- ``kernel_runs[name]``: executions of each Pallas kernel the program
  names (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``,
  ``paged_attention_decode``) on the ``XLA Ops`` line: the paged decode
  kernel runs once a layer a decode step, so its count over the layers
  is the decode steps the trace holds, fractions of a cut block
  included;
- ``host[name]``: count and total seconds of each host span the program
  opens (``engine.<phase>``, ``train.<phase>``, the step marker
  ``train_step``), with the thread lines they were found on;
- ``idle``: every idle gap of 50 us or more of the first device plane's
  ``XLA Ops`` line, put down to the ``engine.`` / ``train.`` span over
  it (the phases are leaves that do not nest, so there is one), the
  uncovered rest to ``unattributed``.

Every function returns None, and never raises, where the plane, the
line, the span or the counter is missing: a CPU rehearsal, a parent
commit without the names, a later refactor.  One reader that raises
loses the whole traced run of a cell.
"""

import json
import re

from chipbench.lib import trace

MODULES_LINE = "XLA Modules"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",     # none is a
           "paged_attention_decode")                          # part of another
_OP = re.compile(r"^%?(?P<base>[A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_MODULE = re.compile(r"^(?:jit_)?(?P<name>[A-Za-z_][\w.\-]*?)(?:\(\d+\))?$")
SPAN_PREFIXES = ("engine.", "train.")
STEP_MARKER = "train_step"
MIN_GAP_NS = 50_000


def module_name(event_name: str) -> str:
    m = _MODULE.match(event_name.strip())
    return m.group("name") if m else event_name.strip()


def _modules(plane) -> dict:
    """``{name: [count, total_ns]}`` of one device plane's module
    events."""
    out = {}
    for line in plane.lines:
        if line.name != MODULES_LINE:
            continue
        for ev in line.events:
            rec = out.setdefault(module_name(ev.name), [0, 0])
            rec[0] += 1
            rec[1] += ev.duration_ns
    return out


def _attribute(gaps, spans) -> dict:
    """``{span name: idle ns}``: each gap ``(a, b)`` shared out among the
    spans ``(start, end, name)`` over it, in time order, no instant
    counted twice; what no span covers goes to ``unattributed``."""
    out = {}
    spans = sorted(spans)
    first = 0
    for a, b in gaps:
        while first < len(spans) and spans[first][1] <= a:
            first += 1
        cursor, covered = a, 0
        for s, e, name in spans[first:]:
            if s >= b:
                break
            lo, hi = max(s, cursor), min(e, b)
            if hi > lo:
                out[name] = out.get(name, 0) + hi - lo
                covered += hi - lo
                cursor = hi
        if b - a > covered:
            out["unattributed"] = out.get("unattributed", 0) + (
                b - a - covered)
    return out


def reduce_spans(path: str) -> dict:
    """See the module docstring.  Raises what the file raises."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_device, kernels, gaps = [], {}, None
    host, spans, lines = {}, [], set()
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            ops = []
            for ln in plane.lines:
                if ln.name != trace.OPS_LINE:
                    continue
                for ev in ln.events:
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    m = _OP.match(ev.name)
                    base = m.group("base") if m else ""
                    # autodiff wraps the name (transpose_jvp_flash_bwd_dq_)
                    kernel = next((k for k in KERNELS if k in base), None)
                    if kernel:
                        kernels[kernel] = kernels.get(kernel, 0) + 1
            if not ops:
                continue
            _, merged = trace._union(ops)
            per_device.append(_modules(plane))
            if gaps is None:
                gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                        if b[0] - a[1] >= MIN_GAP_NS]
        elif plane.name.startswith("/host:"):
            for n, ln in enumerate(plane.lines):
                for ev in ln.events:
                    name = ev.name
                    if not (name.startswith(SPAN_PREFIXES)
                            or name == STEP_MARKER):
                        continue
                    rec = host.setdefault(name, [0, 0])
                    rec[0] += 1
                    rec[1] += ev.duration_ns
                    lines.add(f"{plane.name}#{n}:{ln.name}")
                    if name != STEP_MARKER:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, name))
    out = {"devices": len(per_device)}
    if per_device:
        names = {k for d in per_device for k in d}
        n = len(per_device)
        out["modules"] = {
            k: {"count": sum(d.get(k, [0, 0])[0] for d in per_device) / n,
                "total_s": sum(d.get(k, [0, 0])[1]
                               for d in per_device) / n / 1e9}
            for k in sorted(names)}
        if kernels:
            out["kernel_runs"] = {k: v / n for k, v in
                                  sorted(kernels.items())}
    if host:
        out["host"] = {k: {"count": c, "total_s": ns / 1e9}
                       for k, (c, ns) in sorted(host.items())}
        out["host_lines"] = sorted(lines)
    if gaps is not None:
        by = _attribute(gaps, spans)
        total = sum(b - a for a, b in gaps)
        out["idle"] = {
            "gaps": len(gaps), "total_s": total / 1e9,
            "by_span": {k: v / 1e9 for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])},
            "attributed_share": (1.0 - by.get("unattributed", 0) / total
                                 if total else None)}
    return out


def of(run: dict) -> dict:
    """The run's reduced spans, read once and kept on the record (each
    metric's file asks for them); ``{}`` where there is no trace or it
    cannot be read.  The first reading prints one progress line, which
    is where a traced run reports its programs, its spans and what its
    idle time lay under."""
    if "spans" not in run:
        red = {}
        try:
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path:
                red = reduce_spans(path)
                print(json.dumps({"chipbench": "spans", **red}), flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "spans_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["spans"] = red
    return run["spans"]


def module_seconds(run: dict, pattern: str):
    """Seconds the programs whose name matches ``pattern`` ran inside
    the trace, per device; None where no such program ran there."""
    rx = re.compile(pattern)
    hit = [m["total_s"] for name, m in
           (of(run).get("modules") or {}).items() if rx.search(name)]
    return sum(hit) if hit else None


def kernel_runs(run: dict, name: str):
    """Executions of the kernel the program names ``name`` inside the
    trace, per device; None where it never ran there."""
    return (of(run).get("kernel_runs") or {}).get(name)


def reply_values(run: dict, key: str) -> list:
    """``key`` of each finished request's reply summary, in seconds as
    the engine stamped it; ``[]`` where the replies carry no such key."""
    serve = run.get("serve") or {}
    return [r["summary"][key] for r in serve.get("requests", [])
            if "done" in r and isinstance(r.get("summary"), dict)
            and isinstance(r["summary"].get(key), (int, float))]


def stats_delta(run: dict, key: str):
    """``EngineStats`` counter ``key`` over the measured window (after
    minus before); None where either snapshot lacks it."""
    serve = run.get("serve") or {}
    s0, s1 = serve.get("stats0") or {}, serve.get("stats1") or {}
    if key not in s0 or key not in s1:
        return None
    return s1[key] - s0[key]
