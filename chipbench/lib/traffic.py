"""The one general traffic generator: a mix file of parameters in, a
schedule of requests out.

Steadiness rule: whatever a run's cost follows is part of the work, so
the mix fixes it; ``--seed`` draws only what the cost does not follow.
The schedule (when each request is due, how long its prompt and its
answer are) is drawn once from the mix's own ``draw_seed`` and is the
same for every ``--seed``.  In a mix of kind ``serve`` (a dense model: a
step costs the same whatever its weights and tokens are) ``--seed`` draws
the token ids here and the weights in the runner.  In a mix of kind
``serve_arch`` whose model routes to experts, a decode step costs what
the live rows' routing touches and random routers are skewed, so the
weights are part of the work too, and so are the token ids, since greedy
answers on random weights fall into cycles and a row in a cycle keeps
touching the same few experts: the mix names both (``weights_seed``,
``contents_seed``; read in ``runners/serve_arch.py``) and ``--seed``
draws what is left, in serve-reason nothing: a run differs from the next
by the machine alone.  (Nine ``--seed``s spread its mean TPOT by 6.2%
where one repeated to 0.04%: my chip runs, PR 27; with the weights alone
fixed ten still spread it by 4.3%: PR 29; PERF.md.)  Permuting the same lengths and gaps by the
seed was tried and taken out: with some seventy requests in a window the
engine's 32-step quanta make the 95th percentiles depend on which requests
share a quantum, and six orders of the same work spread TTFT p95 by 9.5%
and TPOT p95 by 2.6% (quartile distance over median; my chip runs, PR 23)
while two runs of one order agreed to 0.02%.  The order is part of the
work, so the mix fixes it.

Serve mix parameters (``chipbench/traffic/<mix>.json``)::

    kind: "serve"
    rate_per_s          offered requests per second, open loop
    arrivals            {"process": "poisson"} or
                        {"process": "gamma", "cv": 3.0}
    prompt_len, output_len
                        {"dist": "lognormal", "median": m, "sigma": s,
                         "min": a, "max": b} or
                        {"dist": "uniform", "min": a, "max": b}
    draw_seed           seed of the fixed set
    weights_seed, contents_seed
                        ``serve_arch`` mixes only, read by that runner
                        (the generator takes the seed it is given)

The number of requests is ``round(rate_per_s * seconds)``; the gaps are
scaled so that the last request is due just inside the window.
"""

import math
import random


def _draw_len(rng: random.Random, spec: dict) -> int:
    if spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"])
    elif spec["dist"] == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(max(round(x), spec.get("min", 1)),
                   spec.get("max", 1 << 30)))


def _draw_gap(rng: random.Random, spec: dict) -> float:
    if spec["process"] == "poisson":
        return rng.expovariate(1.0)
    if spec["process"] == "gamma":          # mean 1, given cv
        shape = 1.0 / spec["cv"] ** 2
        return rng.gammavariate(shape, 1.0 / shape)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab_size: int) -> list:
    """``[{"due_s", "prompt", "max_new_tokens"}, ...]`` ordered by
    ``due_s``, all due inside ``[0, seconds)``."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    fixed = random.Random(mix["draw_seed"])
    lengths = [(_draw_len(fixed, mix["prompt_len"]),
                _draw_len(fixed, mix["output_len"])) for _ in range(n)]
    gaps = [_draw_gap(fixed, mix["arrivals"]) for _ in range(n)]
    contents = random.Random(seed)
    scale = seconds / sum(gaps)             # sum(gaps[:i]) < seconds
    out, t = [], 0.0
    for (plen, olen), gap in zip(lengths, gaps):
        prompt = [contents.randrange(1, vocab_size) for _ in range(plen)]
        out.append({"due_s": t, "prompt": prompt, "max_new_tokens": olen})
        t += gap * scale
    return out


def describe(schedule: list, seconds: float) -> dict:
    """What the generator made, for the progress lines and the tests."""
    from chipbench.lib.stats import percentile
    plens = [len(r["prompt"]) for r in schedule]
    olens = [r["max_new_tokens"] for r in schedule]
    return {"requests": len(schedule),
            "offered_per_s": len(schedule) / seconds,
            "prompt_tokens": sum(plens), "output_tokens": sum(olens),
            "prompt_len_p50": percentile(plens, 50),
            "prompt_len_max": max(plens),
            "output_len_p50": percentile(olens, 50),
            "output_len_max": max(olens),
            "last_due_s": schedule[-1]["due_s"]}
