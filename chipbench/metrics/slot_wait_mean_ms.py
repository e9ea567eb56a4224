"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms: the wait lies between a request's first and second
token.  source: program_span: the replies' own ``slot_wait_s`` (first
token known -> installed in a decode slot, stamped by the engine), mean
over the requests finished in the run."""

from chipbench.lib import spans


def read(run):
    waits = spans.reply_values(run, "slot_wait_s")
    return 1e3 * sum(waits) / len(waits) if waits else None
