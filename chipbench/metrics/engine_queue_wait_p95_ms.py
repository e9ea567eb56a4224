"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms, by the trade ``engine_ttft_p95_ms`` states: it is
that metric's first part.  source: program_span: the replies' own
``queue_wait_s`` (submitted -> popped from the pending queue with its
pages, stamped by the engine), 95th percentile."""

from chipbench.lib import spans
from chipbench.lib.stats import percentile


def read(run):
    p = percentile(spans.reply_values(run, "queue_wait_s"), 95)
    return None if p is None else 1e3 * p
