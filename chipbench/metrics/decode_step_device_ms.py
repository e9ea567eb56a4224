"""layer: jitted step (serve/llm_engine.py ``engine_decode_block``).
moves: serve_tpot_mean_ms.  source: device_trace: device time of the
engine's decode-block program (its events on the trace's ``XLA Modules``
line) per decode step.  The steps are counted on the device, inside the
same trace: the paged decode kernel runs once a layer a step, so steps =
its executions / layers.  (A block of 32 steps outlasts half the traced
window, so most of its executions are cut by the trace's edge: seconds
and steps are cut alike, executions x block size would not be.)  What
the device takes for one token of every slot, prefill waves, slot waits
and the host left out."""

from chipbench.lib import spans


def read(run):
    block = spans.module_seconds(run, r"^engine_decode_block$")
    kernel = spans.kernel_runs(run, "paged_attention_decode")
    layers = (run.get("config") or {}).get("num_hidden_layers")
    if not block or not kernel or not layers:
        return None
    return 1e3 * block / (kernel / layers)
