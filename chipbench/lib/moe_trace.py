"""The expert layer's device operations in a run's ``jax.profiler``
trace, under the names the trace gives them.  A TPU trace's operation
event is its whole HLO line (result type, opcode, operands with their
types) and its device times, nothing else: no op metadata, so a
``jax.named_scope`` of the program never reaches it (looked at on the
chip, PR 26: the events' stats are ``device_offset_ps``,
``device_duration_ps`` and a time scale), and XLA names fusions after
what they compute.  So there is ONE mechanism: the expert layer is
recognised by what only it touches, built from the configuration's
sizes (E experts, d hidden, f expert width).  A Pallas kernel for the
experts would carry the name its ``pallas_call(name=)`` gives it and
has to be added to ``patterns`` with the PR that writes it.

- ``products``: operations that read or produce a tensor of the experts'
  weights, ``[E, d, f]`` or ``[E, f, d]`` (with or without the stacked
  layer axis in front): the three expert products of the all-experts
  formulation, and the per-layer slices of the stacked weights that the
  grouped formulation copies out; and the grouped-matmul kernels XLA
  makes of ``jax.lax.ragged_dot`` (``ragged-dot*``);
- ``router``: operations that read the router's weights ``[d, E]``.

What carries none of these in its signature (top-k, the sort, the
gathers, the gate scaling) is NOT counted: the shares read from here are
lower bounds by those few small operations.

Each matched operation is put down to the program whose execution (line
``XLA Modules``) contains its start, so that decode and prefill can be
told apart.  Returns ``{}``, and never raises, where the configuration
has no experts or the trace has no device plane.
"""

import json
import re

from chipbench.lib import spans, trace

# operations that hold others (their time is their children's) or that
# only pass the weights through
_NOT_WORK = {"while", "call", "conditional", "tuple", "get-tuple-element"}


def opcode(event_name: str) -> str:
    m = trace._HLO.match(event_name)
    op = trace._OPCODE.search(m.group("rest")) if m else None
    return op.group(1) if op else ""


def patterns(cfg: dict) -> dict:
    e, d, f = (cfg["moe_num_primary_experts"], cfg["hidden_size"],
               cfg["moe_ffn_hidden_size"])
    return {
        "products": re.compile(
            rf"\[(\d+,)?{e},{d},{f}\]|\[(\d+,)?{e},{f},{d}\]|ragged-dot"),
        "router": re.compile(rf"\[(\d+,)?{d},{e}\]"),
    }


def reduce_moe(path: str, cfg: dict) -> dict:
    from jax.profiler import ProfileData
    pats = patterns(cfg)
    data = ProfileData.from_file(path)
    out, planes = {}, 0
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        modules, ops = [], []
        for ln in plane.lines:
            if ln.name == spans.MODULES_LINE:
                modules = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     spans.module_name(ev.name)) for ev in ln.events)
            elif ln.name == trace.OPS_LINE:
                ops = [(ev.start_ns, ev.duration_ns, ev.name)
                       for ev in ln.events]
        if not ops:
            continue
        planes += 1
        at = 0
        for start, dur, name in sorted(ops):
            if opcode(name) in _NOT_WORK:
                continue
            kind = next((k for k, rx in pats.items() if rx.search(name)),
                        None)
            if kind is None:
                continue
            while at < len(modules) and modules[at][1] <= start:
                at += 1
            module = (modules[at][2] if at < len(modules)
                      and modules[at][0] <= start else "unknown")
            rec = out.setdefault(module, {})
            rec[kind] = rec.get(kind, 0.0) + dur / 1e9
            key = kind + "_names"
            names = rec.setdefault(key, {})
            short = trace.short_name(name)
            names[short] = names.get(short, 0.0) + dur / 1e9
    if not planes:
        return {}
    for rec in out.values():
        for key, value in rec.items():
            if isinstance(value, float):
                rec[key] = value / planes
            else:
                rec[key] = dict(sorted(
                    ((n, s / planes) for n, s in value.items()),
                    key=lambda kv: -kv[1])[:6])
    return out


def of(run: dict) -> dict:
    """The run's expert-layer operations by program, read once and kept
    on the record; ``{}`` where there is nothing to read."""
    if "moe_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and cfg.get("moe_num_primary_experts"):
                red = reduce_moe(path, cfg)
                print(json.dumps({"chipbench": "moe_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "moe_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["moe_trace"] = red
    return run["moe_trace"]


def seconds(run: dict, kinds=("products", "router"), module: str = None):
    """Seconds of the matched operations inside the trace, per device:
    of all programs, or of those whose name matches ``module``; None
    where nothing matched."""
    rx = re.compile(module) if module else None
    hit = [rec[k] for name, rec in of(run).items()
           if rx is None or rx.search(name) for k in kinds if k in rec]
    return sum(hit) if hit else None
