"""The Mamba-2 and LatentMoE layers' device operations in a run's
``jax.profiler`` trace.  A TPU trace's operation event is its whole HLO
line and its device times, nothing else (``lib/moe_trace.py`` says what
was looked at): a ``jax.named_scope`` of the program (``ssm_prefill``,
``latent_moe``) never reaches it.  So, as there: a Pallas kernel is
found by the name its ``pallas_call(name=)`` gives it (``ssm_decode``,
``moe_experts_decode``), everything else by the tensors only that layer
touches, built from the configuration's sizes:

- ``ssm_kernel``: ``ssm_decode``;
- ``ssm_scan``: the chunked (SSD) prefill form's own tensors: a chunk's
  decays ``[b, H, Q, Q]``, the carried state ``[b, H, N, P]``, a chunk's
  inputs and outputs ``[b, Q, H, P]`` and ``B``, ``C`` over heads ``[b, Q,
  H, N]`` (with or without the stacked chunk axis in front);
- ``ssm_proj``: operations that read the layer's two projections
  ``[d, 2 H P + 2 G N + H]`` and ``[H P, d]``;
- ``experts``: ``moe_experts_decode``, the grouped-matmul kernels XLA
  makes of ``jax.lax.ragged_dot``, and operations that read or produce
  the held experts' weights ``[E, l, f]`` / ``[E, f, l]``;
- ``moe_dense``: operations that read the router ``[d, E_all]``, the
  latent's projections ``[d, l]`` / ``[l, d]`` or the shared expert
  ``[d, s]`` / ``[s, d]``.

What carries none of these in its signature (the convolution, norms,
top-k, the sort, the gathers) is NOT counted: the shares read from here
are lower bounds by those.  Each matched operation is put down to the
program whose execution contains its start, so that decode and prefill
can be told apart, and the two kernels' executions are counted.
Returns ``{}``, and never raises, where the configuration has no such
layers or the trace has no device plane."""

import json
import re

from chipbench.lib import spans, trace
from chipbench.lib.moe_trace import _NOT_WORK, opcode

KERNELS = ("ssm_decode", "moe_experts_decode")


def patterns(cfg: dict) -> dict:
    d, lead = cfg["hidden_size"], r"\[(\d+,)?"
    h, p, n, g = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"])
    q = cfg["chunk_size"]
    inner = h * p
    e, lat, f = (cfg["n_routed_experts"], cfg["moe_latent_size"],
                 cfg["moe_intermediate_size"])
    s, e_all = (cfg["moe_shared_expert_intermediate_size"],
                cfg["published"]["n_routed_experts"])
    any_of = lambda *shapes: "|".join(                        # noqa: E731
        lead + ",".join(str(v) for v in shape) + r"\]" for shape in shapes)
    some = r"\[(\d+,){1,2}"       # a batch, or chunks and a batch, in front
    return {
        "ssm_kernel": re.compile("ssm_decode"),
        "experts": re.compile("moe_experts_decode|ragged-dot|" + any_of(
            (e, lat, f), (e, f, lat))),
        "ssm_scan": re.compile("|".join(
            some + ",".join(str(v) for v in shape) + r"\]" for shape in
            dict.fromkeys(((h, q, q), (h, n, p), (q, h, p), (q, h, n))))),
        "ssm_proj": re.compile(any_of(
            (d, 2 * inner + 2 * g * n + h), (inner, d))),
        "moe_dense": re.compile(any_of(
            (d, e_all), (d, lat), (lat, d), (d, s), (s, d))),
    }


def reduce(path: str, cfg: dict) -> dict:
    """``{"modules": {program: {kind: seconds}}, "kernels": {name:
    {"runs", "seconds"}}}``, per device."""
    from jax.profiler import ProfileData
    pats = patterns(cfg)
    data = ProfileData.from_file(path)
    out, kernels, planes = {}, {}, 0
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
            m = spans._OP.match(name)
            kernel = next((k for k in KERNELS
                           if m and k in m.group("base")), None)
            if kernel:
                rec = kernels.setdefault(kernel, {"runs": 0, "seconds": 0.0})
                rec["runs"] += 1
                rec["seconds"] += dur / 1e9
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
    if not planes:
        return {}
    return {"modules": {name: {k: v / planes for k, v in rec.items()}
                        for name, rec in out.items()},
            "kernels": {k: {"runs": v["runs"] / planes,
                            "seconds": v["seconds"] / planes}
                        for k, v in kernels.items()}}


def of(run: dict) -> dict:
    """The run's Mamba-2 and LatentMoE operations, read once and kept on
    the record; ``{}`` where there is nothing to read."""
    if "ssm_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and cfg.get("mamba_num_heads"):
                red = reduce(path, cfg)
                print(json.dumps({"chipbench": "ssm_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "ssm_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["ssm_trace"] = red
    return run["ssm_trace"]


def kernel(run: dict, name: str) -> dict:
    """``{"runs", "seconds"}`` of the kernel ``name`` inside the trace,
    ``{}`` where it never ran there."""
    return (of(run).get("kernels") or {}).get(name) or {}


def seconds(run: dict, kinds, module: str = None):
    """Seconds of the matched operations of ``kinds`` inside the trace,
    per device: of all programs, or of those whose name matches
    ``module``; None where nothing matched."""
    rx = re.compile(module) if module else None
    hit = [rec[k] for name, rec in (of(run).get("modules") or {}).items()
           if rx is None or rx.search(name) for k in kinds if k in rec]
    return sum(hit) if hit else None


def mamba_layers(config: dict) -> int:
    """Mamba-2 layers among the layers the configuration runs."""
    config = config or {}
    return (config.get("hybrid_override_pattern") or "")[
        :config.get("num_hidden_layers", 0)].count("M")
