"""layer: jitted step (serve/llm_engine.py ``engine_prefill``,
models/gpt.py ``_prefill_attend`` on expanded latent attention).  moves:
serve_tpot_mean_ms.  source: device_trace: device time of the prefill
programs' attention proper (the ``flash_fwd`` kernel where a row's
scores pass the program's budget, else the operations that write or
read the float32 scores ``[rows, heads, q, k]``: ``lib/mla_trace.py``)
over the device's busy time.  At 8,192 tokens the expanded attention is more
operations than the layer's matrix products, and a prefill wave stalls
every decode row: what chunked or blocked-sparse prefill would win shows
here first."""

from chipbench.lib import mla_trace


def read(run):
    took = mla_trace.of(run).get("prefill_attend_s")
    busy = (run.get("trace") or {}).get("busy_s")
    if not took or not busy:
        return None
    return took / busy
