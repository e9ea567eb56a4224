"""Mean over every request due in the window of (last token - first
token) / (tokens - 1), as the client received them on the streaming
path.  Host clock.  End to end, the cells judged on latency.  A failed
request misses the mean (and makes the run incorrect)."""

from chipbench.lib.serve_views import client_tpot_s, finished


def read(run):
    xs = [x for x in map(client_tpot_s, finished(run)) if x is not None]
    return 1e3 * sum(xs) / len(xs) if xs else None
