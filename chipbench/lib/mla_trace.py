"""The latent attention's device operations in a run's ``jax.profiler``
trace: the absorbed decode kernel by the name its ``pallas_call(name=)``
gives it (``paged_attention_decode``, the family's one name: how often
it ran and for how long, as ``lib/gdn_trace.py`` counts ``gdn_decode``;
``lib/spans.py kernel_runs`` has the count and not the seconds), and the
EXPANDED attention of the prefill programs, which is plain XLA and so,
as ``lib/moe_trace.py`` says, can only be recognised by the tensors only
it touches: the float32 scores and their probabilities ``[(rows,) H, q,
k]`` with ``q`` and ``k`` in the hundreds or more (a block of a row's
queries against the keys up to its causal edge), which the score
product writes, the softmax's fusions read and write and the value
product reads; or, where a row's scores would pass the program's
budget and the TPU runs the flash kernel in their place
(``models/gpt.py _prefill_attend``), that kernel by its name
(``flash_fwd``) inside a prefill program.  The projections around them (``y Wq``, ``c Wkvb``, ``o
Wo``) carry no such tensor and are NOT counted: the share read from here
is the attention proper, a lower bound by the copies that lay keys out.

Returns ``{}``, and never raises, where there is no trace, no device
plane or no such operation: a CPU rehearsal, a parent commit, a later
refactor.
"""

import json
import re

from chipbench.lib import moe_trace, spans, trace

KERNEL = "paged_attention_decode"
FLASH = "flash_fwd"


def scores_pattern(cfg: dict):
    h = cfg["num_attention_heads"]
    # the rows' axis is squeezed out where a row runs alone
    return re.compile(rf"(?:f32|bf16)\[(?:\d+,)?{h},\d{{3,}},\d{{3,}}\]")


def reduce_mla(path: str, cfg: dict) -> dict:
    """``{"kernel": {"runs", "seconds"}, "prefill_attend_s": s,
    "prefill_attend_names": {...}}`` averaged over the device planes."""
    from jax.profiler import ProfileData
    rx = scores_pattern(cfg)
    data = ProfileData.from_file(path)
    runs = kernel_s = attend_s = planes = 0
    names = {}
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
        at = 0
        for start, dur, name in ops:
            m = spans._OP.match(name)
            if m and KERNEL in m.group("base"):
                runs += 1
                kernel_s += dur / 1e9
                continue
            flash = bool(m and FLASH in m.group("base"))
            if not flash and (
                    moe_trace.opcode(name) in moe_trace._NOT_WORK
                    or not rx.search(name)):
                continue
            while at < len(modules) and modules[at][1] <= start:
                at += 1
            if at < len(modules) and modules[at][0] <= start \
                    and modules[at][2].startswith("engine_prefill"):
                attend_s += dur / 1e9
                short = trace.short_name(name)
                names[short] = names.get(short, 0.0) + dur / 1e9
    if not planes:
        return {}
    out = {}
    if runs:
        out["kernel"] = {"runs": runs / planes, "seconds": kernel_s / planes}
    if attend_s:
        out["prefill_attend_s"] = attend_s / planes
        out["prefill_attend_names"] = dict(sorted(
            ((n, s / planes) for n, s in names.items()),
            key=lambda kv: -kv[1])[:6])
    return out


def of(run: dict) -> dict:
    """The run's latent-attention operations, read once and kept on the
    record; ``{}`` where there is nothing to read."""
    if "mla_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and cfg.get("kv_lora_rank"):
                red = reduce_mla(path, cfg)
                print(json.dumps({"chipbench": "mla_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "mla_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["mla_trace"] = red
    return run["mla_trace"]
