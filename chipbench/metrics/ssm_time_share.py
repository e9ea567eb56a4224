"""layer: kernels (ops/mamba2.py).  moves: serve_tpot_mean_ms.  source:
device_trace: time of the device operations named ``ssm_decode`` (the
name its ``pallas_call`` gives it) in every program of the trace, and of
the chunked prefill form's operations in the prefill programs
(``lib/ssm_trace.py``: the program's ``ssm_prefill`` scope does not
reach a TPU trace, so they are found by the tensors only that form
makes), over the device's busy time.  The layers' projections,
convolution and norms are plain XLA fusions and are not in it."""

from chipbench.lib import ssm_trace


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    took = [t for t in (ssm_trace.kernel(run, "ssm_decode").get("seconds"),
                        ssm_trace.seconds(run, ("ssm_scan",),
                                          r"^engine_prefill"))
            if t is not None]
    if not busy or not took:
        return None
    return sum(took) / busy
