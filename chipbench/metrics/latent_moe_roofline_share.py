"""layer: kernels (ops/moe.py ``LatentMoE``, ``moe_experts_decode``).
moves: serve_tpot_mean_ms.  source: device_trace + program_counter: the
least time the chip could take to read what the trace's decode steps had
to read for the expert layers, over the time their operations took.

- bytes: ``lib/ssm_bytes.py latent_moe_bytes``: two bf16 matrices a HELD
  expert touched, and once a layer step the shared expert, the latent's
  projections and the router.  Experts touched a layer step come from
  the engine's counters over the TRACED interval (``moe_experts_touched /
  moe_layer_steps`` between the snapshots the runner takes at the
  trace's start and stop; rows that hold a request only), times the
  layer steps inside the trace (the expert kernel runs once an expert
  layer a step: its executions);
- time: the expert layers' operations of ``engine_decode_block`` in the
  trace (``lib/ssm_trace.py``), prefill left out;
- bound: HBM bandwidth (``lib/peaks.py``); the products' operations at
  65 rows are a third of that time.

A formulation that reads every held expert whatever the routing cannot
pass (experts touched / experts held) here."""

from chipbench.lib import ssm_bytes, ssm_trace
from chipbench.lib.peaks import peaks_for


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    delta = lambda k: s1[k] - s0[k] if k in s0 and k in s1 else None  # noqa: E731
    steps, touched = delta("moe_layer_steps"), delta("moe_experts_touched")
    traced_steps = ssm_trace.kernel(run, "moe_experts_decode").get("runs")
    took = ssm_trace.seconds(run, ("experts", "moe_dense"),
                             r"^engine_decode_block$")
    if not steps or not touched or not traced_steps or not took \
            or run["device"]["platform"] != "tpu":
        return None
    need = ssm_bytes.latent_moe_bytes(
        run["config"], touched / steps * traced_steps, traced_steps)
    return need / peaks_for(run["device"]["kind"])["hbm_bytes_per_s"] / took
