"""layer: jitted step (serve/llm_engine.py ``engine_decode_block``) of a
model with Kimi Delta Attention layers.  moves: serve_tpot_mean_ms.
source: device_trace: device time of the engine's decode-block program
per decode step, as ``hybrid_step_device_ms`` is, with the steps counted
by the kernel every KDA layer runs once a step: steps = executions of
``kda_decode`` / KDA layers among the layers the configuration runs (20
of 27; ``decode_step_device_ms`` divides the paged kernel's executions by
ALL layers, and 7 layers in 27 run that kernel)."""

from chipbench.lib import kda_bytes, kda_trace, spans


def read(run):
    block = spans.module_seconds(run, r"^engine_decode_block$")
    runs = (kda_trace.of(run).get("kernel") or {}).get("runs")
    layers = kda_bytes.kda_layers(run.get("config") or {})
    if not block or not runs or not layers:
        return None
    return 1e3 * block / (runs / layers)
