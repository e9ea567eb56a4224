"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: host_clock: 95th percentile over requests of
(last token - first token) / (tokens - 1), as the client received them:
the tail of the quantity whose mean is judged end to end.  With some
seventy requests in a window it has under four samples beyond it and
moves by 0.5-2% from run to run (PERF.md), so it judges nothing."""

from chipbench.lib.serve_views import client_tpot_s, finished
from chipbench.lib.stats import percentile


def read(run):
    xs = [client_tpot_s(r) for r in finished(run)]
    p = percentile([x for x in xs if x is not None], 95)
    return p and 1e3 * p
