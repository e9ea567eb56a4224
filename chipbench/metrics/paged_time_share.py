"""layer: kernels (ops/paged_attention.py).  moves: serve_tpot_mean_ms.
source: device_trace: self time of the paged decode kernel's device
operations over the device's busy time.  The kernel has no name of its
own; the trace shows it under the calling method's."""

from chipbench.lib.trace import share_of_busy

PATTERN = r"_decode_attend_paged|paged_attention"


def read(run):
    return share_of_busy(run.get("trace") or {}, PATTERN)
