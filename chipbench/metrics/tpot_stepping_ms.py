"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_span: the replies' own
``stepping_s``, a token: the decode blocks themselves (live rows' steps,
the first block's wait behind the request's own prefill wave, and what
the host adds between blocks where the device is not the pace): the
remainder of the identity.  With the other two ``tpot_*_ms`` it adds up
to the engine's own mean of (latency_s - time_to_first_token_s) /
(num_tokens - 1), over the same requests (``lib/decode_account.py``)."""

from chipbench.lib import decode_account


def read(run):
    return decode_account.mean_ms(run, "stepping_s")
