"""layer: jitted step (serve/llm_engine.py ``engine_decode_block`` of a
drafting engine).  moves: serve_tpot_mean_ms.  source: device_trace: the
prediction module's share of the decode step's device time: the leaf
operations from the read of the module's input projection to the read
of the head behind it, over all of the block program's
(``lib/spec_trace.py``: the parts are told by order, the program's
scopes do not reach a TPU trace).  What drafting costs a step, to set
against ``mtp_accept_share``, what it buys."""

from chipbench.lib import spec_trace


def read(run):
    parts = spec_trace.of(run).get("parts") or {}
    whole = sum(parts.values())
    if not whole:
        return None
    return parts["draft"] / whole
