"""The device operations of a model whose layers are a Mamba-2 mixer and
a dense SwiGLU each (``model_type: granitemoehybrid`` keys) in a run's
``jax.profiler`` trace: what ``lib/ssm_trace.py`` is for the
``nemotron_h`` keys (its ``patterns`` indexes the expert layer's sizes,
which this configuration has none of).  As there, a TPU trace's
operation event is its whole HLO line and nothing else, so the
program's ``mamba_mixer`` / ``mamba_mlp`` scopes never reach it: the
Pallas kernel is found by the name its ``pallas_call(name=)`` gives it,
everything else by the tensors only that part of the layer touches,
built from the configuration's sizes:

- ``ssm_kernel``: ``ssm_decode``;
- ``ssm_scan``: the chunked (SSD) prefill form's own tensors: a chunk's
  decays ``[b, H, Q, Q]``, the carried state ``[b, H, N, P]``, a chunk's
  inputs and outputs ``[b, Q, H, P]`` and ``B``, ``C`` over heads ``[b,
  Q, H, N]`` (with or without the stacked chunk axis in front), ``Q``
  the published chunk or a shorter prompt bucket of the mix;
- ``ssm_proj``: operations that read the mixer's two projections ``[d,
  2 H P + 2 G N + H]`` and ``[H P, d]``;
- ``mlp``: operations that read the SwiGLU's matrices ``[d, f]`` (gate,
  up; ``[d, 2 f]`` where a compiler joins them) and ``[f, d]``: every
  layer's, the attention layers' too.

What carries none of these in its signature (the convolution, norms,
the gate, the attention layers' own projections and kernel, the head)
is NOT counted: the shares read from here are lower bounds by those.
Each matched operation is put down to the program whose execution
contains its start, and the kernel's executions are counted.  Returns
``{}``, and never raises, where the configuration has no such layers or
the trace has no device plane."""

import json
import re

from chipbench.lib import spans, trace
from chipbench.lib.moe_trace import _NOT_WORK, opcode

KERNEL = "ssm_decode"


def _chunks(cfg: dict, mix: dict) -> list:
    """The chunk lengths the prompt form runs at: the published chunk,
    and each power of two under it that is a prompt bucket of the mix (a
    prompt shorter than the chunk is one chunk of its bucket)."""
    q = cfg["mamba_chunk_size"]
    spec = (mix or {}).get("prompt_len") or {}
    lo, hi = spec.get("min", q), spec.get("max", q)
    out, b = {q}, 1
    while b < q:
        if 2 * b > lo and b <= hi:         # a bucket some prompt falls in
            out.add(b)
        b *= 2
    return sorted(out)


def patterns(cfg: dict, mix: dict = None) -> dict:
    d, f, lead = cfg["hidden_size"], cfg["shared_intermediate_size"], \
        r"\[(\d+,)?"
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner = h * p
    any_of = lambda *shapes: "|".join(                        # noqa: E731
        lead + ",".join(str(v) for v in shape) + r"\]" for shape in shapes)
    some = r"\[(\d+,){1,2}"       # a batch, or chunks and a batch, in front
    scan = dict.fromkeys(
        shape for q in _chunks(cfg, mix)
        for shape in ((h, q, q), (h, n, p), (q, h, p), (q, h, n)))
    return {
        "ssm_kernel": re.compile(KERNEL),
        "ssm_scan": re.compile("|".join(
            some + ",".join(str(v) for v in shape) + r"\]"
            for shape in scan)),
        "ssm_proj": re.compile(any_of(
            (d, 2 * inner + 2 * g * n + h), (inner, d))),
        "mlp": re.compile(any_of((d, f), (d, 2 * f), (f, d))),
    }


def reduce(path: str, pats: dict) -> dict:
    """``{"modules": {program: {kind: seconds}}, "kernel": {"runs",
    "seconds"}}``, per device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out, kernel, planes = {}, {"runs": 0, "seconds": 0.0}, 0
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
            if m and KERNEL in m.group("base"):
                kernel["runs"] += 1
                kernel["seconds"] += dur / 1e9
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
            "kernel": {k: v / planes for k, v in kernel.items()}}


def of(run: dict) -> dict:
    """The run's Mamba-2 and SwiGLU operations, read once and kept on the
    record; ``{}`` where there is nothing to read."""
    if "mamba_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and cfg.get("mamba_n_heads"):
                red = reduce(path, patterns(cfg, run.get("mix")))
                print(json.dumps({"chipbench": "mamba_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "mamba_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["mamba_trace"] = red
    return run["mamba_trace"]


def kernel(run: dict) -> dict:
    """``{"runs", "seconds"}`` of ``ssm_decode`` inside the trace, ``{}``
    where it never ran there."""
    k = of(run).get("kernel") or {}
    return k if k.get("runs") else {}


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
    return (config.get("layer_types") or [])[
        :config.get("num_hidden_layers", 0)].count("mamba")
