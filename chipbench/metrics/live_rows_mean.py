"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_counter: ``EngineStats`` over the
window: rows whose token was delivered a decode step (``gdn_state_rows /
gdn_layer_steps`` between the window's two snapshots).  It is what the
recurrent layers' state traffic follows (it grows with live rows, not
with context), so beside a TPOT it tells a change of load from a change
of kernel."""

from chipbench.lib import spans


def read(run):
    rows, steps = (spans.stats_delta(run, k) for k in
                   ("gdn_state_rows", "gdn_layer_steps"))
    if rows is None or not steps:
        return None
    return rows / steps
