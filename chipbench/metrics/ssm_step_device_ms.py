"""layer: jitted step (serve/llm_engine.py ``engine_decode_block``) of a
model with Mamba-2 layers.  moves: serve_tpot_mean_ms.  source:
device_trace: device time of the engine's decode-block program per
decode step, as ``hybrid_step_device_ms`` is, with the steps counted by
the kernel every Mamba-2 layer runs once a step: steps = executions of
``ssm_decode`` / Mamba-2 layers among the layers the configuration
runs."""

from chipbench.lib import spans, ssm_trace


def read(run):
    block = spans.module_seconds(run, r"^engine_decode_block$")
    runs = ssm_trace.kernel(run, "ssm_decode").get("runs")
    layers = ssm_trace.mamba_layers(run.get("config"))
    if not block or not runs or not layers:
        return None
    return 1e3 * block / (runs / layers)
