"""Kimi Delta Attention's device operations in a run's ``jax.profiler``
trace: the decode kernel by the name its ``pallas_call(name=)`` gives it
under a decay a channel (``kda_decode``: how often it ran and for how
long, as ``lib/gdn_trace.py`` counts ``gdn_decode``), and the CHUNKED
prompt form of the prefill programs, which is plain XLA in float32 and
so, as ``lib/moe_trace.py`` says of its own, can only be recognised by
the tensors only it touches: float32 arrays whose trailing dims are the
heads, then (the chunks,) a chunk or its sub-chunks against a chunk, a
sub-chunk or the key width: ``[.., H, n, 64, 64]``, ``[.., H, n, 64,
128]``, ``[.., H, n, 4, 16, 16(, 128)]``, ``[.., H, n, 4, 64, 128]``,
``[.., H, n, 4, 16, 64]``, and inside the chunk scan the same without
``n`` and the carried state ``[b, H, 128, 128]``.  The projections, the
convolution and the gates carry no such tensor and are NOT counted: the
share read from here is the chunk arithmetic proper, a lower bound.

Returns ``{}``, and never raises, where there is no trace, no device
plane or no such operation: a CPU rehearsal, a parent commit, a later
refactor.
"""

import json
import re

from chipbench.lib import moe_trace, spans, trace

KERNEL = "kda_decode"
CHUNK, SUB = 64, 16


def chunk_pattern(cfg: dict):
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    m = CHUNK // SUB
    tails = [(CHUNK, CHUNK), (CHUNK, d), (d, CHUNK), (m, SUB, SUB),
             (m, SUB, SUB, d), (m, SUB, d), (m, CHUNK, d), (m, SUB, CHUNK),
             (m, SUB, m, SUB)]
    dims = "|".join(",".join(str(v) for v in t) for t in tails)
    # a batch (and a chunk count) in front, the heads, (the chunk count)
    return re.compile(
        rf"f32\[(?:\d+,){{0,2}}{h},(?:\d+,)?(?:{dims})\]"
        rf"|f32\[\d+,{h},{d},{d}\]")


def reduce_kda(path: str, cfg: dict) -> dict:
    """``{"kernel": {"runs", "seconds"}, "prefill_chunk_s": s,
    "prefill_s": s}`` averaged over the device planes: the kernel, the
    chunk form's operations inside the prefill programs, and those
    programs' own device time."""
    from jax.profiler import ProfileData
    rx = chunk_pattern(cfg)
    data = ProfileData.from_file(path)
    runs = kernel_s = chunk_s = prefill_s = planes = 0
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
                ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                             for ev in ln.events)
        if not ops:
            continue
        planes += 1
        prefill_s += sum(e - s for s, e, name in modules
                         if name.startswith("engine_prefill")) / 1e9
        at = 0
        for start, dur, name in ops:
            m = spans._OP.match(name)
            if m and KERNEL in m.group("base"):
                runs += 1
                kernel_s += dur / 1e9
                continue
            if moe_trace.opcode(name) in moe_trace._NOT_WORK \
                    or not rx.search(name):
                continue
            while at < len(modules) and modules[at][1] <= start:
                at += 1
            if at < len(modules) and modules[at][0] <= start \
                    and modules[at][2].startswith("engine_prefill"):
                chunk_s += dur / 1e9
    if not planes:
        return {}
    out = {}
    if runs:
        out["kernel"] = {"runs": runs / planes, "seconds": kernel_s / planes}
    if prefill_s:
        out["prefill_s"] = prefill_s / planes
        out["prefill_chunk_s"] = chunk_s / planes
    return out


def of(run: dict) -> dict:
    """The run's KDA operations, read once and kept on the record; ``{}``
    where there is nothing to read."""
    if "kda_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and (cfg.get("linear_attn_config") or {}).get(
                    "kda_layers"):
                red = reduce_kda(path, cfg)
                print(json.dumps({"chipbench": "kda_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "kda_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["kda_trace"] = red
    return run["kda_trace"]
