"""layer: jitted step (ops/paged_attention.py: the decode kernel's page
loop under a sliding window).  moves: serve_tpot_mean_ms.  source:
program_counter: ``EngineStats`` over the window: pages the window
layers' decode reads left out because they lie wholly behind the window,
over the pages the lengths alone would have made them read
(``window_pages_skipped / (window_pages_read + window_pages_skipped)``).
0 until a context passes the window."""

from chipbench.lib import spans


def read(run):
    read_, skipped = (spans.stats_delta(run, k) for k in
                      ("window_pages_read", "window_pages_skipped"))
    if read_ is None or skipped is None or read_ + skipped <= 0:
        return None
    return skipped / (read_ + skipped)
