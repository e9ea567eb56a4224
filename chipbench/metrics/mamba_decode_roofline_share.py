"""layer: kernels (ops/mamba2.py ``ssm_decode``).  moves:
serve_tpot_mean_ms.  source: device_trace + program_counter: the least
time the chip could take to move what the trace's decode steps had to
move for the Mamba-2 layers, over the time ``ssm_decode`` took; what
``ssm_roofline_share`` is under the ``nemotron_h`` key names.

- bytes: ``lib/mamba_bytes.py decode_row_bytes``: a live row's state
  read and written (2 x 2,097,152 B at 64 heads x 64 x 128), its
  convolution tail, its vectors, in one layer.  Live rows a layer step
  come from the engine's counters over the TRACED interval
  (``gdn_state_rows / gdn_layer_steps``, the one pair of counters every
  recurrent layer class feeds, between the snapshots the runner takes at
  the trace's start and stop: rows whose token was delivered only),
  times the layer steps inside the trace (the kernel runs once a
  Mamba-2 layer a step: its executions);
- time: the kernel's device time in the trace;
- bound: HBM bandwidth (``lib/peaks.py``).

A kernel that moved a dead row's state, or every entry, could not pass
(live rows / rows) here; the counter counts delivered tokens, so it
cannot read above 1."""

from chipbench.lib import mamba_bytes, mamba_trace
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, rows = delta("gdn_layer_steps"), delta("gdn_state_rows")
    kernel = mamba_trace.kernel(run)
    if not steps or not rows or not kernel.get("seconds") \
            or run["device"]["platform"] != "tpu":
        return None
    need = mamba_bytes.decode_row_bytes(run["config"]) * (
        rows / steps * kernel["runs"])
    return (need / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"]
            / kernel["seconds"])
