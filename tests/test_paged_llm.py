"""Paged KV-cache serving: block tables, pool recycling, prefill-ahead.

VERDICT round-4 task #1: replace the dense per-slot ``[max_seq]`` KV rows
with paged allocation (ops/paged_attention.py + llm_engine paged mode).
The bar: slot decode matches lone generation at mixed offsets, pages
recycle safely across requests, and queued requests get their first
token from the slotless prefill stage (the TTFT knob) instead of
waiting for slot turnover.  CPU-sized; real-chip numbers live in
benchmarks/serve_llm.py --paged.
"""

import threading
import time

import pytest


def _tiny():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT

    cfg = get_config("tiny")
    model = GPT(cfg, decode=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def tiny_parts():
    return _tiny()


def _lone_expect(cfg, params, prompts, n=8):
    import jax.numpy as jnp
    from ray_tpu.models.generate import Generator

    lone = Generator(cfg, params)
    return [
        [int(t) for t in lone.generate(jnp.asarray([p], jnp.int32),
                                       max_new_tokens=n,
                                       temperature=0.0)[0]]
        for p in prompts
    ]


def _submit_all(eng, prompts, n=8, timeout=240):
    results = [None] * len(prompts)
    threads = []
    for i, p in enumerate(prompts):
        def go(i=i, p=p):
            results[i] = eng.submit(p, max_new_tokens=n, temperature=0.0)
        t = threading.Thread(target=go)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=timeout)
    return results


def test_paged_model_matches_dense_at_mixed_offsets(tiny_parts):
    """Model-level: paged prefill + per-page decode reproduces the dense
    decode path exactly with rows at different offsets and disjoint
    (deliberately shuffled) physical pages."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import init_decode_cache
    from ray_tpu.models.gpt import GPT

    cfg, params = tiny_parts
    ps = 16
    max_pages = cfg.max_seq_len // ps
    paged = GPT(cfg, decode=True, paged_pages=32, page_size=ps)
    cache = init_decode_cache(paged, 1)

    prompts = [[1, 2, 3], [7, 8, 9, 10, 11]]
    expect = _lone_expect(cfg, params, prompts)

    # non-contiguous, interleaved physical pages
    bt = np.zeros((2, max_pages), np.int32)
    bt[0] = (np.arange(max_pages) * 2 + 1) % 31 + 1
    bt[1] = (np.arange(max_pages) * 2 + 2) % 31 + 1
    assert len(set(bt[0]) & set(bt[1])) == 0
    bt = jnp.asarray(bt)

    bucket = 8
    toks = np.zeros((2, bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    positions = jnp.broadcast_to(jnp.arange(bucket), (2, bucket))
    logits, mut = paged.apply({"params": params, "cache": cache},
                              jnp.asarray(toks), positions,
                              block_tables=bt, mutable=["cache"])
    cache = mut["cache"]
    out = [[int(jnp.argmax(logits[i, len(p) - 1]))]
           for i, p in enumerate(prompts)]
    tok = jnp.asarray([o[0] for o in out], jnp.int32)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
    for _ in range(7):
        logits, mut = paged.apply({"params": params, "cache": cache},
                                  tok[:, None], pos[:, None],
                                  block_tables=bt, mutable=["cache"])
        cache = mut["cache"]
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for i in range(2):
            out[i].append(int(tok[i]))
        pos = pos + 1
    assert out == expect


def test_paged_engine_matches_lone_generation(tiny_parts):
    """Engine-level (the VERDICT bar): greedy decode through the paged
    engine — slotless prefill, install, per-row tables — equals each
    prompt generated alone, with more requests than decode slots."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [50, 60], [5] * 9]
    expect = _lone_expect(cfg, params, prompts)
    eng = LLMEngine(cfg, params, num_slots=2, block_size=4, paged=True,
                    page_size=16, kv_pool_pages=1 + 8)
    try:
        results = _submit_all(eng, prompts)
        for i in range(len(prompts)):
            assert results[i] is not None
            assert results[i].tokens == expect[i], (
                f"paged decode diverged for prompt {i}")
            assert results[i].prompt_len == len(prompts[i])
    finally:
        eng.close()


def test_page_recycling_stays_exact(tiny_parts):
    """Pool smaller than the workload: pages must recycle through the
    redirect fence across ~4x pool turnover with every output still
    exactly the lone generation (a page recycled one dispatch too early
    would corrupt a live row's KV and diverge)."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    prompts = [[i + 1, i + 2, i + 3] for i in range(16)]
    expect = _lone_expect(cfg, params, prompts, n=6)
    # 4 usable pages, 1 page per request -> at most 4 in flight, 16 total
    eng = LLMEngine(cfg, params, num_slots=2, block_size=4, paged=True,
                    page_size=16, kv_pool_pages=1 + 4)
    try:
        results = _submit_all(eng, prompts, n=6)
        for i in range(16):
            assert results[i] is not None, f"request {i} hung"
            assert results[i].tokens == expect[i], (
                f"page recycling corrupted request {i}")
    finally:
        eng.close()


def test_prefill_ahead_ttft_decoupled_from_slot_wait(tiny_parts):
    """With one busy decode slot, queued requests still get their first
    token from the slotless prefill stage: TTFT well under the full
    latency (which includes waiting for the slot)."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, num_slots=1, block_size=4, paged=True,
                    page_size=16, kv_pool_pages=1 + 8)
    try:
        eng.warmup(prompt_lens=[3])
        firsts_seen = []
        results = {}
        lock = threading.Lock()

        def go(rid, n):
            r = eng.submit([rid + 1, rid + 2, rid + 3], max_new_tokens=n,
                           temperature=0.0,
                           on_token=(lambda t, rid=rid: firsts_seen.append(
                               (rid, time.monotonic()))))
            with lock:
                results[rid] = r

        # request 0 holds the only slot for 72 tokens (5 of the 8 pool
        # pages; the three queued requests need one each).  Wait for its
        # first token, not for a fixed sleep: on a fast box 40 tokens were
        # done within the old 0.3 s and nobody waited for the slot at all
        threads = [threading.Thread(target=go, args=(0, 72))]
        threads[0].start()
        deadline = time.monotonic() + 120
        while not firsts_seen and time.monotonic() < deadline:
            time.sleep(0.001)
        assert firsts_seen, "request 0 never produced a token"
        for rid in range(1, 4):
            th = threading.Thread(target=go, args=(rid, 8))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=240)
        assert sorted(results) == [0, 1, 2, 3]
        for rid in range(1, 4):
            r = results[rid]
            assert len(r.tokens) == 8
            # first token arrived from prefill-ahead, long before the
            # slot freed: TTFT must undercut the queued request's
            # end-to-end latency decisively
            assert r.time_to_first_token_s < r.latency_s / 2, (
                rid, r.time_to_first_token_s, r.latency_s)
    finally:
        eng.close()


def test_paged_eos_streaming_and_oversized(tiny_parts):
    """eos stops a paged row; on_token streams in order; a request that
    can never fit the pool fails alone without wedging the loop."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, num_slots=2, block_size=4, paged=True,
                    page_size=16, kv_pool_pages=1 + 6, max_prompt_len=60)
    try:
        seen = []
        probe = eng.submit([3, 4, 5], max_new_tokens=4, temperature=0.0,
                           on_token=seen.append)
        assert seen == probe.tokens
        eos = probe.tokens[0]
        r = eng.submit([3, 4, 5], max_new_tokens=64, temperature=0.0,
                       eos_id=eos)
        assert r.finish_reason == "eos"
        assert r.tokens == [eos]
        # needs ceil(min(60+128, max_seq 128)/16) = 8 pages > pool's 6
        with pytest.raises(ValueError):
            eng.submit([9] * 60, max_new_tokens=128)
        # engine still serves afterwards
        r2 = eng.submit([3, 4, 5], max_new_tokens=4, temperature=0.0)
        assert r2.tokens == probe.tokens
    finally:
        eng.close()
