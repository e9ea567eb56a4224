"""layer: kernels (ops/gated_delta.py ``kda_decode``).  moves:
serve_tpot_mean_ms.  source: device_trace + program_counter: the least
time the chip could take to move what the trace's decode steps had to
move for the KDA layers, over the time ``kda_decode`` took.

- bytes: ``lib/kda_bytes.py decode_row_bytes``: a live row's state read
  and written (2 x 2,097,152 B), its two convolution tails, its vectors,
  in one layer.  Live rows a layer step come from the engine's counters
  over the TRACED interval (``gdn_state_rows / gdn_layer_steps`` between
  the snapshots the runner takes at the trace's start and stop: the
  recurrent layers' counters, whatever their class; rows whose token was
  delivered only), times the layer steps inside the trace (the kernel
  runs once a KDA layer a step: its executions);
- time: the kernel's device time in the trace;
- bound: HBM bandwidth (``lib/peaks.py``).

A kernel that moved a dead row's state, or every entry, could not pass
(live rows / rows) here; the counter counts delivered tokens, so it
cannot read above 1."""

from chipbench.lib import kda_bytes, kda_trace
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, rows = delta("gdn_layer_steps"), delta("gdn_state_rows")
    kernel = kda_trace.of(run).get("kernel") or {}
    if not steps or not rows or not kernel.get("seconds") \
            or run["device"]["platform"] != "tpu":
        return None
    need = kda_bytes.decode_row_bytes(run["config"]) * (
        rows / steps * kernel["runs"])
    return (need / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
            / kernel["seconds"])
