"""layer: kernels (ops/moe.py, the dropless expert layer).  moves:
serve_tpot_mean_ms.  source: device_trace + program_counter: the least
time the chip could take to read the experts the trace's decode steps
touched, over the time their expert products took.

- bytes: ``lib/moe_bytes.py``: three bf16 matrices an expert touched.
  Experts touched a layer step come from the engine's counters over the
  TRACED interval (``moe_experts_touched / moe_layer_steps`` between the
  snapshots the runner takes at the trace's start and stop; rows that
  hold a request only), times the layer steps inside the trace (the
  paged decode kernel runs once a layer a step: its executions);
- time: the products of ``engine_decode_block`` in the trace
  (``lib/moe_trace.py``), prefill left out;
- bound: HBM bandwidth (``lib/peaks.py``); the products' operations at
  33 rows are a seventh of that time.

A formulation that reads every expert whatever the routing cannot pass
(experts touched / experts) here."""

from chipbench.lib import moe_bytes, moe_trace, spans
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, touched = delta("moe_layer_steps"), delta("moe_experts_touched")
    traced_steps = spans.kernel_runs(run, "paged_attention_decode")
    took = moe_trace.seconds(run, ("products",), r"^engine_decode_block$")
    if not steps or not touched or not traced_steps or not took \
            or run["device"]["platform"] != "tpu":
        return None
    need = moe_bytes.touched_bytes(run["config"],
                                   touched / steps * traced_steps)
    return need / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"] / took
