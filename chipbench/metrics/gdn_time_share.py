"""layer: kernels (ops/gated_delta.py).  moves: serve_tpot_mean_ms.
source: device_trace: self time of the device operations named
``gdn_decode`` or ``gdn_prefill`` (the names their ``pallas_call``s
give them) over the device's busy time, in every program of the trace.
The recurrent layers' projections, convolution and norms are plain XLA
fusions and are not in it; the chunked prefill form is plain XLA too
(no ``gdn_prefill`` kernel yet), so today this is the decode kernel."""

from chipbench.lib.trace import share_of_busy

PATTERN = r"gdn_decode|gdn_prefill"


def read(run):
    return share_of_busy(run.get("trace") or {}, PATTERN)
