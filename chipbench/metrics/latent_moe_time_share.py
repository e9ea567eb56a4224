"""layer: kernels (ops/moe.py ``LatentMoE``).  moves: serve_tpot_mean_ms.
source: device_trace: time of the expert layer's device operations over
the device's busy time, in every program of the trace.  Recognised as
``lib/ssm_trace.py`` says (the program's ``latent_moe`` scope does not
reach a TPU trace): ``moe_experts_decode``, the grouped-matmul kernels,
and the operations that touch the experts', the router's, the latent
projections' or the shared expert's weights; the top-k, sort, gathers
and gate scaling carry no such tensor and are not counted, so this is a
lower bound by those."""

from chipbench.lib import ssm_trace


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    took = ssm_trace.seconds(run, ("experts", "moe_dense"))
    if not busy or took is None:
        return None
    return took / busy
