"""layer: kernels (models/gpt.py ``MLP``, the dense SwiGLU every layer
of this model has).  moves: serve_tpot_mean_ms.  source: device_trace:
time of the device operations that read the SwiGLU's matrices (``[d,
f]``, ``[d, 2 f]``, ``[f, d]``: ``lib/mamba_trace.py``) in every program
of the trace, over the device's busy time.  A decode step reads all of
them whatever the load (4.0 of the 6.4 GB of weights), so beside
``mamba_mixer_time_share``, whose state traffic grows with the live
rows, it says which of the two sets the step."""

from chipbench.lib import mamba_trace


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    took = mamba_trace.seconds(run, ("mlp",))
    if not busy or took is None:
        return None
    return took / busy
