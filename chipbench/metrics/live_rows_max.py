"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_counter:
``EngineStats.live_rows_max`` after the window: the most rows whose
token ONE decode step delivered since the runner started the engine's
high-water marks again, just before the window.  Beside
``live_rows_mean`` it says what a burst did: the recurrent layers' state
traffic in the widest step is this many rows' (4.3 MB each a layer).
Not a difference of two snapshots: a high-water mark is not
cumulative."""


def read(run):
    serve = run.get("serve") or {}
    return (serve.get("stats1") or {}).get("live_rows_max")
