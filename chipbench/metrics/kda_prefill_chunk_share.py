"""layer: jitted step (serve/llm_engine.py ``engine_prefill``,
ops/gated_delta.py ``gated_delta_chunked`` under a decay a channel).
moves: serve_tpot_mean_ms.  source: device_trace: device time of the
chunked prompt form's own operations inside the prefill programs
(``lib/kda_trace.py``: found by the tensors only the chunk arithmetic
touches; a lower bound) over those programs' device time.  The form is
plain XLA in float32 at the highest matrix precision: this is what a
prefill kernel for the channel-wise rule would buy, and a prefill wave
stalls every decode row."""

from chipbench.lib import kda_trace


def read(run):
    red = kda_trace.of(run)
    if not red.get("prefill_s") or red.get("prefill_chunk_s") is None:
        return None
    return red["prefill_chunk_s"] / red["prefill_s"]
