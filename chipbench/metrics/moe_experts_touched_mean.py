"""layer: kernels (ops/moe.py, the dropless expert layer).  moves:
serve_tpot_mean_ms.  source: program_counter: ``EngineStats`` over the
window: experts the live rows' routing touched a layer step
(``moe_experts_touched / moe_layer_steps`` between the window's two
snapshots).  It is what a decode step's expert products cost follows
(0.2 ms of TPOT an expert in serve-reason: PERF.md, PR 27), so beside a
TPOT it tells a change of the routing from a change of the kernel."""

from chipbench.lib import spans


def read(run):
    touched, steps = (spans.stats_delta(run, k) for k in
                      ("moe_experts_touched", "moe_layer_steps"))
    if touched is None or not steps:
        return None
    return touched / steps
