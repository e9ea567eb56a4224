"""layer: jitted step (serve/llm_engine.py ``engine_prefill``,
``engine_prefill_suffix``).  moves: serve_tpot_mean_ms: a prefill wave
runs between two decode blocks, so its time is added to every slot's
gap between tokens.  source: device_trace: device time of the engine's
prefill programs (``XLA Modules`` line) over the device's busy time."""

from chipbench.lib import spans


def read(run):
    prefill = spans.module_seconds(run, r"^engine_prefill")
    busy = (run.get("trace") or {}).get("busy_s")
    if not prefill or not busy:
        return None
    return prefill / busy
