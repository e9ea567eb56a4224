"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms once the device stops setting the pace.  source:
program_counter: ``EngineStats``' time accounts over the window: the
share of the loop thread's working time (its life less the time it was
blocked with nothing to do) that it was NOT blocked fetching from the
device: 1 - fetch_wait_s / (loop_s - idle_wait_s)."""

from chipbench.lib import spans


def read(run):
    loop, idle, fetch = (spans.stats_delta(run, k) for k in
                         ("loop_s", "idle_wait_s", "fetch_wait_s"))
    if loop is None or idle is None or fetch is None or loop - idle <= 0:
        return None
    return 1.0 - fetch / (loop - idle)
