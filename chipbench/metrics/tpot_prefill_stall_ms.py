"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_span: the replies' own
``prefill_stall_s``, a token: the device seconds of OTHER requests'
prefill waves that ran ahead of decode blocks the request rode, after
the block that first stepped it.  With the other two ``tpot_*_ms`` it
adds up to the engine's own mean of (latency_s - time_to_first_token_s)
/ (num_tokens - 1), over the same requests
(``lib/decode_account.py``)."""

from chipbench.lib import decode_account


def read(run):
    return decode_account.mean_ms(run, "prefill_stall_s")
