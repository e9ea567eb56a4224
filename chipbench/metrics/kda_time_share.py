"""layer: kernels (ops/gated_delta.py under a decay a channel).  moves:
serve_tpot_mean_ms.  source: device_trace: self time of the device
operations named ``kda_decode`` (the name the ``pallas_call`` gives the
decode kernel where the decay is a vector) over the device's busy time,
in every program of the trace.  The KDA layers' projections,
convolutions, gates and norms are plain XLA fusions and are not in it;
the chunked prompt form is plain XLA too (``kda_prefill_chunk_share``)."""

from chipbench.lib.trace import share_of_busy

PATTERN = r"kda_decode"


def read(run):
    return share_of_busy(run.get("trace") or {}, PATTERN)
