"""layer: engine scheduler (serve/llm_engine.py, the waves it forms).
moves: serve_tpot_mean_ms: a wave stops decode for as long as it
computes, padding included.  source: program_counter: ``EngineStats``
over the window: 1 - prefill_prompt_tokens / prefill_padded_tokens, the
share of the positions the prefill waves computed (wave size x bucket)
that held nobody's prompt."""

from chipbench.lib import spans


def read(run):
    prompt, padded = (spans.stats_delta(run, k) for k in
                      ("prefill_prompt_tokens", "prefill_padded_tokens"))
    if prompt is None or not padded:
        return None
    return 1.0 - prompt / padded
