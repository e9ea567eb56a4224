"""layer: kernels (ops/mamba2.py and ``Mamba2Mixer``'s projections).
moves: serve_tpot_mean_ms.  source: device_trace: time of the device
operations named ``ssm_decode``, of the chunked prompt form's own
tensors and of the operations that read the mixer's two projections
(``lib/mamba_trace.py``: found by the tensors only they touch), in every
program of the trace, over the device's busy time.  The mixer's
convolution, gate and norms are fusions that carry no such tensor and
are not in it: a lower bound, as ``ssm_time_share`` is.  Beside
``mlp_time_share`` it says whether the mixers or the SwiGLUs set the
step."""

from chipbench.lib import mamba_trace


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    took = mamba_trace.seconds(run, ("ssm_kernel", "ssm_scan", "ssm_proj"))
    if not busy or took is None:
        return None
    return took / busy
