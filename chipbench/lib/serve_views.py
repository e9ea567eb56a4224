"""The client's and the engine's view of each finished request, shared by
the serve metrics' files."""


def client_ttft_s(rec):
    """First token received minus the time the request was DUE."""
    return rec["token_t"][0] - rec["due"] if rec["token_t"] else None


def client_tpot_s(rec):
    n = len(rec["token_t"])
    if n < 2:
        return None
    return (rec["token_t"][-1] - rec["token_t"][0]) / (n - 1)


def finished(run):
    s = run.get("serve")
    return [r for r in s["requests"] if "done" in r] if s else []


def client_summary(recs) -> dict:
    """What no metric of ``BENCHMARK.json`` judges at present, for the
    progress line, in milliseconds: first-token times from DUE, how late
    the generator sent, and the handle hop (first token from SENT minus
    the reply's own ``time_to_first_token_s``)."""
    from chipbench.lib.stats import percentile
    done = [r for r in recs if "done" in r and r["token_t"]]
    ttft = [1e3 * client_ttft_s(r) for r in done]
    hop = [1e3 * (r["token_t"][0] - r["sent"]
                  - r["summary"]["time_to_first_token_s"])
           for r in done if r.get("summary")]
    late = [1e3 * (r["sent"] - r["due"]) for r in recs if "sent" in r]
    return {"ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
            "generator_late_p95_ms": percentile(late, 95),
            "ingress_overhead_p50_ms": percentile(hop, 50)}
