"""layer: jitted step (serve/llm_engine.py ``engine_decode_block``) of a
model with recurrent layers.  moves: serve_tpot_mean_ms.  source:
device_trace: device time of the engine's decode-block program per
decode step, as ``decode_step_device_ms`` is, with the steps counted by
the kernel every linear_attention layer runs once a step: steps =
executions of ``gdn_decode`` / linear layers among the layers the
configuration runs.  (``decode_step_device_ms`` divides the paged
kernel's executions by ALL layers; here 3 layers in 12 run that kernel,
and it would read 4 times too few steps.)"""

from chipbench.lib import gdn_trace, spans


def read(run):
    block = spans.module_seconds(run, r"^engine_decode_block$")
    runs = (gdn_trace.of(run).get("gdn_decode") or {}).get("runs")
    layers = gdn_trace.linear_layers(run.get("config"))
    if not block or not runs or not layers:
        return None
    return 1e3 * block / (runs / layers)
