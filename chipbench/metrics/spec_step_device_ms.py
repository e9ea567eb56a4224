"""layer: jitted step (serve/llm_engine.py ``engine_decode_block`` of a
drafting engine).  moves: serve_tpot_mean_ms.  source: device_trace:
device time of the decode-block program per decode step, verify and
draft together, as ``decode_step_device_ms`` is, with the steps counted
by the paged kernel, which runs once a pool layer a step: steps = its
executions / (the stack's layers + the module's)."""

from chipbench.lib import spans, spec_bytes, spec_trace


def read(run):
    config = run.get("config") or {}
    if not config.get("num_nextn_predict_layers"):
        return None
    block = spans.module_seconds(run, r"^engine_decode_block$")
    runs = (spec_trace.of(run).get("kernel") or {}).get("runs")
    if not block or not runs:
        return None
    return 1e3 * block / (runs / spec_bytes.pool_layers(config))
