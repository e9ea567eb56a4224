"""layer: kernels (ops/moe.py, the dropless expert layer).  moves:
serve_tpot_mean_ms.  source: device_trace: time of the expert layer's
device operations over the device's busy time, in every program of the
trace.  Recognised as ``lib/moe_trace.py`` says: the operations that
touch the experts' or the router's weights and the grouped-matmul
kernels; the top-k, sort, gathers and gate scaling carry no such tensor
and are not counted, so this is a lower bound by those."""

from chipbench.lib import moe_trace


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    moe = moe_trace.seconds(run)
    if not busy or moe is None:
        return None
    return moe / busy
