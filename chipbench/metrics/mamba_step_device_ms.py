"""layer: jitted step (serve/llm_engine.py ``engine_decode_block``) of a
model whose Mamba-2 layers carry a SwiGLU each.  moves:
serve_tpot_mean_ms.  source: device_trace: device time of the engine's
decode-block program per decode step it ran, as ``ssm_step_device_ms``
is, with the steps counted by the kernel every Mamba-2 layer runs once
a step: steps = executions of ``ssm_decode`` / Mamba-2 layers among the
layers the configuration runs (``lib/mamba_trace.py``)."""

from chipbench.lib import mamba_trace, spans


def read(run):
    block = spans.module_seconds(run, r"^engine_decode_block$")
    runs = mamba_trace.kernel(run).get("runs")
    layers = mamba_trace.mamba_layers(run.get("config"))
    if not block or not runs or not layers:
        return None
    return 1e3 * block / (runs / layers)
