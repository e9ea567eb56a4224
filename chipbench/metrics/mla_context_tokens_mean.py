"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_counter: ``EngineStats`` over the
window: cached positions the absorbed latent kernel read a layer step
(``mla_context_tokens / mla_layer_steps`` between the window's two
snapshots; rows whose token was delivered).  It is what the kernel's
bytes follow (1,152 B a position), so beside a TPOT it tells a change of
the traffic from a change of the kernel."""

from chipbench.lib import spans


def read(run):
    tokens, steps = (spans.stats_delta(run, k) for k in
                     ("mla_context_tokens", "mla_layer_steps"))
    if tokens is None or not steps:
        return None
    return tokens / steps
