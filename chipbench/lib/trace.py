"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  What
it takes from the trace:

- device planes are those named ``/device:TPU:<n>``; their operations
  are the events of the line ``XLA Ops`` (one stream per chip, nested
  where a ``while`` or a fusion holds children);
- ``busy_s``: the union of those events' intervals, averaged over the
  device planes; the idle share is ``1 - busy_s / window_s``;
- ``self_s[name]``: each event's duration minus its children's, summed
  by name and averaged over the device planes, so that a ``while`` does
  not count its body twice;
- ``gaps``: the idle intervals of the first device plane, each named by
  the host event (any line of a ``/host:`` plane) that overlaps it most.

Nothing here knows a kernel's name: the per-layer metrics' own files
hold the patterns they look for.  ``start_trace`` is how every runner's
process that holds the chip turns the profiler on.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HLO = re.compile(r"^%?(?P<lhs>[^ ]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"[\]})] ([a-z][\w-]*)\(")


def start_trace(trace_dir: str) -> None:
    """``jax.profiler.start_trace`` with the profiler's PYTHON call
    tracer off: its device planes, ``TraceAnnotation`` spans and runtime
    events stay, and every reader of a trace reads those.  The call
    tracer records every Python call of every thread of the process: it
    made a block's delivery 22.5 ms against 1.8 ms untraced in
    ``serve-chat`` and 547 ms against a block of 436 ms in
    ``serve-assist``, where ``stop_trace`` then held the interpreter for
    ~11 s converting its events (PERF.md section 6, PRs 33 and 40), and
    it filled ``breakdown.idle_gaps`` with other threads' frames."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def short_name(event_name: str) -> str:
    """The trace names a device operation by its whole HLO line.  Keep
    the instruction's own name, its result type and its opcode:
    ``attn._train_attend.45 = (bf16[240,1024,64], ...) custom-call``.
    Operands are dropped, so a pattern can only match the operation
    itself, never one that merely reads its result."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:120]
    rest = m.group("rest")
    op = _OPCODE.search(rest)
    result = re.sub(r"\{[^}]*\}", "", rest[:op.start() + 1] if op else
                    rest[:60])
    return f"{m.group('lhs')} = {result[:70]} {op.group(1) if op else ''}"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _union(intervals):
    """Total length and merged list of ``[(start, end), ...]``."""
    total, merged = 0, []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                total += e - merged[-1][1]
                merged[-1][1] = e
        else:
            merged.append([s, e])
            total += e - s
    return total, merged


def _self_times(events):
    """``{name: self_ns}`` of one stream's possibly nested events, given
    as ``(start, end, name)``."""
    out, stack = {}, []          # stack of [end, name, child_ns, start]
    def close(item):
        end, name, child, start = item
        out[name] = out.get(name, 0) + (end - start) - child
        if stack:
            stack[-1][2] += end - start
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0, s])
    while stack:
        close(stack.pop())
    return out


def reduce_trace(path: str) -> dict:
    """See the module docstring."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_streams, host_events, seen = [], [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        seen[plane.name] = [ln.name for ln in lines][:12]
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                if ln.name == OPS_LINE:
                    device_streams.append((plane.name, [
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         short_name(ev.name)) for ev in ln.events]))
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.duration_ns >= 20_000:
                        host_events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name))
    out = {"planes": seen, "devices": len(device_streams)}
    if not device_streams:
        return out
    busy, self_ns = [], {}
    first_merged = None
    for _, events in device_streams:
        total, merged = _union([(s, e) for s, e, _ in events])
        busy.append(total)
        if first_merged is None:
            first_merged = merged
        st = _self_times(events)
        for name, ns in st.items():
            self_ns[name] = self_ns.get(name, 0) + ns
    n = len(device_streams)
    out["busy_s"] = sum(busy) / n / 1e9
    out["self_s"] = {k: v / n / 1e9 for k, v in self_ns.items()}
    if first_merged:
        out["span_s"] = (first_merged[-1][1] - first_merged[0][0]) / 1e9
    gaps = {}
    host_events.sort()
    for (_, a), (b, _) in zip(first_merged or [], (first_merged or [])[1:]):
        if b - a < 50_000:
            continue
        best, best_ov = "unattributed", 0
        for s, e, name in host_events:
            if s >= b:
                break
            ov = min(e, b) - max(s, a)
            if ov > best_ov:
                best, best_ov = name, ov
        gaps[best] = gaps.get(best, 0) + (b - a)
    out["gaps"] = sorted(([k, v / 1e9] for k, v in gaps.items()),
                         key=lambda kv: -kv[1])
    return out


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's last line."""
    ops = sorted(reduced.get("self_s", {}).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": reduced.get("gaps", [])[:top]}


def share_of_busy(reduced: dict, pattern: str):
    """Self time of the operations whose name matches ``pattern`` over
    the device's busy time; None where no operation matches."""
    rx = re.compile(pattern)
    hit = [v for k, v in reduced.get("self_s", {}).items() if rx.search(k)]
    if not hit or not reduced.get("busy_s"):
        return None
    return sum(hit) / reduced["busy_s"]
