"""layer: engine scheduler (serve/llm_engine.py).  moves:
serve_tpot_mean_ms.  source: program_span: the replies' own
``block_tail_s``, a token: from the instant the device produced the
request's last token (step k of its last block, placed in that block's
own seconds by k / block_size) to the stamp of its result: the junk
steps behind the last token and the host's delivery of that block up to
this row.  With the other two ``tpot_*_ms`` it adds up to the engine's
own mean of (latency_s - time_to_first_token_s) / (num_tokens - 1), over
the same requests (``lib/decode_account.py``)."""

from chipbench.lib import decode_account


def read(run):
    return decode_account.mean_ms(run, "block_tail_s")
