"""A drafting engine's decode step in a run's ``jax.profiler`` trace:
the paged kernel's time, and the step split into its three parts.  A TPU
trace's operation event is its whole HLO line and its device times,
nothing else (``lib/moe_trace.py`` says what was looked at): the
program's ``spec_verify`` / ``spec_accept`` / ``mtp_draft`` scopes never
reach it.  So the parts are told by ORDER, between two operations that
only one place of the step makes, found by the tensors they read:

- the head ``[d, V]`` is read twice a step: behind the stack (the two
  positions' logits) and behind the module (the next draft's);
- the module's input projection ``[2 d, d]`` is read once, where the
  module starts.

Inside each execution of ``engine_decode_block`` the leaf operations are
walked in time order: ``verify`` up to and with the first read of the
head, ``accept`` from there to the read of ``[2 d, d]``, ``draft`` from
that read up to and with the next read of the head; then ``verify``
again.  (The draw of the next draft, behind the second read, counts to
the next step's ``verify``: microseconds.)  Containers (``while``,
``call``: their time is their children's) are left out.  The kernel is
found by the name its ``pallas_call(name=)`` gives it.  Returns ``{}``,
and never raises, where the configuration has no module, the trace no
device plane or the program no such operations (a parent commit); where
a drafting configuration's trace shows no split (the compiler fused or
moved a landmark), ``of`` prints a ``spec_trace_no_split`` line, so
that the two metrics that fall silent with it do not do so unseen."""

import json
import re

from chipbench.lib import spans, trace
from chipbench.lib.moe_trace import _NOT_WORK, opcode

KERNEL = "paged_attention_decode"
BLOCK = "engine_decode_block"


def patterns(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"head": re.compile(rf"\[{d},{v}\]"),
            "module_in": re.compile(rf"\[{2 * d},{d}\]")}


def reduce(path: str, cfg: dict) -> dict:
    """``{"parts": {"verify" | "accept" | "draft": seconds}, "kernel":
    {"runs", "seconds"}}``, per device."""
    from jax.profiler import ProfileData
    pats = patterns(cfg)
    data = ProfileData.from_file(path)
    parts = {"verify": 0.0, "accept": 0.0, "draft": 0.0}
    kernel, planes, split = {"runs": 0, "seconds": 0.0}, 0, False
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        blocks, ops = [], []
        for ln in plane.lines:
            if ln.name == spans.MODULES_LINE:
                blocks = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in ln.events
                    if spans.module_name(ev.name) == BLOCK)
            elif ln.name == trace.OPS_LINE:
                ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                             for ev in ln.events)
        if not ops:
            continue
        planes += 1
        at, inside, part = 0, None, "verify"
        for start, dur, name in ops:
            if opcode(name) in _NOT_WORK:
                continue
            m = spans._OP.match(name)
            if m and KERNEL in m.group("base"):
                kernel["runs"] += 1
                kernel["seconds"] += dur / 1e9
            while at < len(blocks) and blocks[at][1] <= start:
                at += 1
            if at >= len(blocks) or blocks[at][0] > start:
                continue                      # not in a decode block
            if inside != at:                  # a new execution: a new step
                inside, part = at, "verify"
            if part == "accept" and pats["module_in"].search(name):
                part, split = "draft", True
            parts[part] += dur / 1e9
            if pats["head"].search(name):
                part = {"verify": "accept", "draft": "verify"}.get(part,
                                                                   part)
    if not planes:
        return {}
    return {"parts": ({k: v / planes for k, v in parts.items()}
                      if split else {}),
            "kernel": {k: v / planes for k, v in kernel.items()}}


def of(run: dict) -> dict:
    """The run's split, read once and kept on the record; ``{}`` where
    there is nothing to read."""
    if "spec_trace" not in run:
        red = {}
        try:
            cfg = run.get("config") or {}
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path and cfg.get("num_nextn_predict_layers"):
                red = reduce(path, cfg)
                print(json.dumps({"chipbench": "spec_trace", **red}),
                      flush=True)
                if not red.get("parts"):
                    # a silent None would hide a compiler that fused or
                    # moved one of the two landmarks
                    print(json.dumps({
                        "chipbench": "spec_trace_no_split",
                        "why": "a drafting configuration's trace in which "
                               "no engine_decode_block execution reads "
                               "[d, V], then [2 d, d], then [d, V] again: "
                               "spec_step_device_ms and "
                               "mtp_draft_time_share are left out"}),
                        flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "spec_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["spec_trace"] = red
    return run["spec_trace"]
