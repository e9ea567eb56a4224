"""layer: engine scheduler (serve/llm_engine.py, a drafting engine's
block).  moves: serve_tpot_mean_ms.  source: program_counter:
``EngineStats`` over the window: tokens DELIVERED from decode steps over
the steps that gave them (``step_tokens / drafts_proposed``: a delivered
step verifies one draft a row).  1 + ``mtp_accept_share`` less the
second tokens cut off where a request ended at the first of a pair (one
a request at most)."""

from chipbench.lib import spans


def read(run):
    tokens, steps = (spans.stats_delta(run, k) for k in
                     ("step_tokens", "drafts_proposed"))
    if tokens is None or not steps:
        return None
    return tokens / steps
