"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms, by a trade: a prefill wave stops decode while it
runs, so admitting sooner shortens this and lengthens the gaps between
tokens, and admitting later does the reverse.  No first-token time is
judged end to end yet (PERF.md section 2), so this is where a change that
buys shorter gaps with later first tokens shows.  source: program_span:
the replies' own ``time_to_first_token_s`` (submit to first sampled
token), 95th percentile."""

from chipbench.lib.serve_views import finished
from chipbench.lib.stats import percentile


def read(run):
    p = percentile([r["summary"]["time_to_first_token_s"]
                    for r in finished(run) if r.get("summary")], 95)
    return p and 1e3 * p
