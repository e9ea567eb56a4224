"""The engine's own account of the time between a request's first and
last token, a token, from the replies (ISSUE 41): ``stepping_s +
prefill_stall_s + block_tail_s == latency_s - time_to_first_token_s`` on
every reply of two tokens or more (``serve/llm_engine.py
GenerationResult``).  The three ``tpot_*_ms`` readers share this file so
that their means are over the same requests and add up to the engine's
mean time a token.
"""

PARTS = ("stepping_s", "prefill_stall_s", "block_tail_s")


def mean_ms(run: dict, part: str):
    """Mean over the finished requests of two tokens or more of
    ``1e3 * part / (num_tokens - 1)``; None where no reply carries the
    account (a parent commit, a run without requests)."""
    serve = run.get("serve") or {}
    each = []
    for rec in serve.get("requests", []):
        s = rec.get("summary") if "done" in rec else None
        if not isinstance(s, dict):
            continue
        n = s.get("num_tokens")
        if not isinstance(n, int) or n < 2 or not all(
                isinstance(s.get(k), (int, float)) for k in PARTS):
            continue
        each.append(1e3 * s[part] / (n - 1))
    return sum(each) / len(each) if each else None
