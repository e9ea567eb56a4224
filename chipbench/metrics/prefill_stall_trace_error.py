"""layer: engine scheduler (serve/llm_engine.py, its account of the
waves).  moves: serve_tpot_mean_ms, through ``tpot_prefill_stall_ms``,
whose seconds are these.  source: device_trace + program_counter: how
far the engine's own reckoning of its prefill waves' device seconds
(``EngineStats.prefill_wave_s``, between the snapshots the runner takes
at the trace's start and stop) lies from the device time of the
``engine_prefill*`` programs inside the trace (``XLA Modules`` line):
abs(engine - device) / device.  The engine has no clock on the device:
it reckons a wave from the host's stamps around it.  A wave cut by an
edge of the trace is whole on one side and cut on the other, so a trace
of few long waves reads higher than the engine errs."""

from chipbench.lib import spans


def read(run):
    traced = run.get("traced") or {}
    s0, s1 = traced.get("stats0") or {}, traced.get("stats1") or {}
    device = spans.module_seconds(run, r"^engine_prefill")
    if not device or "prefill_wave_s" not in s0 \
            or "prefill_wave_s" not in s1:
        return None
    return abs(s1["prefill_wave_s"] - s0["prefill_wave_s"] - device) / device
