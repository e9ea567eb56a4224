"""The gated-delta kernels' executions in a run's ``jax.profiler`` trace:
how often each ran and for how long, by the name its ``pallas_call(name=)``
gives it (``gdn_decode``; ``gdn_prefill`` should the chunked form become
a kernel).  ``lib/spans.py kernel_runs`` counts a fixed list of kernels
that a PR adding one may not extend, so the readers of the recurrent
layers' metrics count theirs here, the same way: events of the ``XLA
Ops`` line of each ``/device:TPU:<n>`` plane whose instruction name is
the kernel's, averaged over the device planes.

Returns ``{}``, and never raises, where there is no trace, no device
plane or no such kernel: a CPU rehearsal, a parent commit without the
kernel, a later refactor.
"""

import json

from chipbench.lib import spans, trace

KERNELS = ("gdn_decode", "gdn_prefill")


def reduce_gdn(path: str) -> dict:
    """``{kernel: {"runs": n, "seconds": s}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out, planes = {}, 0
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == trace.OPS_LINE]
        if not lines:
            continue
        planes += 1
        for ev in lines[0].events:
            m = spans._OP.match(ev.name)
            kernel = next((k for k in KERNELS
                           if m and k in m.group("base")), None)
            if kernel:
                rec = out.setdefault(kernel, {"runs": 0, "seconds": 0.0})
                rec["runs"] += 1
                rec["seconds"] += ev.duration_ns / 1e9
    return {k: {"runs": v["runs"] / planes, "seconds": v["seconds"] / planes}
            for k, v in out.items()}


def of(run: dict) -> dict:
    """The run's gated-delta kernels, read once and kept on the record;
    ``{}`` where there is nothing to read."""
    if "gdn_trace" not in run:
        red = {}
        try:
            path = run.get("trace_dir") and trace.find_xplane(
                run["trace_dir"])
            if path:
                red = reduce_gdn(path)
                print(json.dumps({"chipbench": "gdn_trace", **red}),
                      flush=True)
        except Exception as e:  # noqa: BLE001 -- a reader never raises
            print(json.dumps({"chipbench": "gdn_trace_unreadable",
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        run["gdn_trace"] = red
    return run["gdn_trace"]


def linear_layers(config: dict) -> int:
    """Linear-attention layers among the layers the configuration
    runs."""
    config = config or {}
    return (config.get("layer_types") or [])[
        :config.get("num_hidden_layers", 0)].count("linear_attention")
