"""The serving engine's KV pool: block tables, pool recycling,
prefill-ahead.

``LLMEngine`` serves from a shared page pool (ops/paged_attention.py)
and nothing else.  The bar: slot decode matches lone generation
(``models/generate.py Generator``, the plain reference) at mixed
offsets, pages recycle safely across requests, and queued requests get
their first token from the slotless prefill stage (the TTFT knob)
instead of waiting for slot turnover.  CPU-sized; the chip's numbers
are the benchmark's (chipbench/) and benchmarks/serve_llm.py's.
"""

import threading
import time

import jax
import pytest


def _tiny(scan_layers=True):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT

    cfg = get_config("tiny", scan_layers=scan_layers)
    model = GPT(cfg, decode=True)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def tiny_parts():
    return _tiny()


@pytest.fixture(scope="module", params=[True, False],
                ids=["scanned", "unrolled"])
def tiny_parts_either(request):
    """The tiny model with its layers scanned and unrolled: the KV pool
    is one stacked leaf in both, the layer index traced or static."""
    return _tiny(scan_layers=request.param)


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


def _serve_each(eng, requests, timeout=240):
    """Every request (kwargs of ``submit``) at once, a thread each; the
    results in order."""
    out = [None] * len(requests)

    def go(i):
        out[i] = eng.submit(**requests[i])
    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    return out


def _submit_all(eng, prompts, n=8, timeout=240):
    return _serve_each(eng, [dict(prompt=p, max_new_tokens=n,
                                  temperature=0.0) for p in prompts],
                       timeout)


def test_paged_model_matches_dense_at_mixed_offsets(tiny_parts_either):
    """Model-level: paged prefill + per-page decode reproduces the dense
    decode path exactly with rows at different offsets and disjoint
    (deliberately shuffled) physical pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import init_decode_cache
    from ray_tpu.models.gpt import GPT

    cfg, params = tiny_parts_either
    ps = 16
    max_pages = cfg.max_seq_len // ps
    paged = GPT(cfg, decode=True, paged_pages=32, page_size=ps)
    cache = init_decode_cache(paged, 1)
    # one stacked pool leaf, whether the layers are scanned or not
    assert [x.shape for x in jax.tree.leaves(cache)] == [
        (cfg.n_layers, 32, cfg.n_kv_heads, ps, 2 * cfg.head_dim)]

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


def _gqa_engine(scan_layers, **kw):
    """An engine of the tiny model with 4 query heads on 2 KV heads."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine
    cfg = get_config("tiny", n_kv_heads=2, scan_layers=scan_layers)
    params = GPT(cfg, decode=True).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, params, LLMEngine(
        cfg, params, **{"num_slots": 2, "page_size": 4, "max_seq_len": 128,
                        "max_prompt_len": 64, "block_size": 4,
                        "min_prefill_bucket": 8, **kw})


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scanned", "unrolled"])
@pytest.mark.parametrize("lengths", [(23,), (13, 40), (64, 5)],
                         ids=["one-row", "two-rows", "fills-its-bucket"])
def test_a_wave_of_several_chunks_is_the_one_pass_on_real_positions(
        lengths, scan_layers, prefill_chunk):
    """Dense GQA: a wave at bucket 64 in chunks of 16 (``Block._chunked``)
    against the same wave in one pass: real positions' hidden states and
    K|V rows, first tokens; the pool finite, and zero where no chunk
    ran."""
    from conftest import assert_chunked_wave_is_the_whole_wave
    _, _, eng = _gqa_engine(scan_layers)
    try:
        assert_chunked_wave_is_the_whole_wave(eng, lengths, 64, 16,
                                              prefill_chunk)
    finally:
        eng.close()


def test_a_wave_of_one_chunk_is_the_program_it_was(prefill_chunk):
    """As tests/test_latent_attention.py's, on K|V pages: one chunk or
    less traces to what it always did, several chunks to a loop."""
    from conftest import assert_only_several_chunks_loop
    _, _, eng = _gqa_engine(True)
    try:
        assert_only_several_chunks_loop(eng, prefill_chunk)
    finally:
        eng.close()


def test_the_engine_counts_the_chunks_a_wave_ran(prefill_chunk):
    """Two prompts of 17 and 20 tokens in ONE wave at bucket 32, chunks
    of 8: the wave runs the longer row's 3 chunks for both rows, so
    ``prefill_padded_tokens`` grows by 2 x 24 (what was computed, not
    less than the 37 real tokens), and the wave's key, by which the
    engine remembers what such a program took, names the 24."""
    from ray_tpu.models import gpt
    from ray_tpu.serve.llm_engine import _Request
    _, _, eng = _gqa_engine(True)
    prefill_chunk(8)
    assert [gpt.prefill_positions(32, n) for n in (1, 8, 9, 17, 32)] == [
        8, 8, 16, 24, 32]
    assert gpt.prefill_positions(8, 3) == 8         # one chunk: all of it
    assert gpt.prefill_positions(20, 3) == 20       # not whole chunks
    # buckets double: the prompts of one of two chunks always run both,
    # so its program is not told the lengths and stays the one pass
    assert [eng._skips_pad(b) for b in (8, 16, 32, 64)] == [
        False, False, True, True]
    try:
        todo = [(_Request(list(range(1, n + 1)), 4, 0.0, None,
                          lambda ok, value: None, None),
                 list(range(1 + 8 * r, 9 + 8 * r)))
                for r, n in enumerate((17, 20))]
        ((firsts, metas, key),) = eng._dispatch_prefill_waves(todo)
        assert firsts.shape == (2,) and len(metas) == 2
        assert key == (32, 2, False, 24)
        st = eng.stats.snapshot(2)
        assert (st["prefill_waves"], st["prefill_prompt_tokens"],
                st["prefill_padded_tokens"]) == (1, 37, 48)
    finally:
        eng.close()


def test_a_hybrid_model_s_prompt_waves_stay_one_pass(prefill_chunk):
    """A model with recurrent layers is always told the real lengths
    (its recurrence needs them); its full-attention blocks are not, so
    its prefill program is the same whatever a chunk is, and the engine
    counts the whole bucket as computed."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.serve.llm_engine import LLMEngine
    cfg, params, kw = _hybrid_parts()
    eng = LLMEngine(cfg, params, **kw)

    def program():
        eng._prefill_jit.clear()
        return str(jax.make_jaxpr(eng._get_prefill_paged(32, 2))(
            eng.params, eng._cache,
            jnp.zeros((2, eng.packed_width(32)), jnp.int32),
            jnp.zeros((2, eng.max_pages), jnp.int32), jax.random.PRNGKey(0)))
    try:
        whole = program()
        prefill_chunk(8)
        assert program() == whole and not eng._skips_pad(32)
    finally:
        eng.close()


@pytest.mark.parametrize("front", ["LLMEngine", "LLMServer"])
def test_paged_false_is_refused(front):
    """The dense engine is gone: the keyword has one legal value (it
    stays while chipbench/ passes it, ROADMAP C12) and the other one
    raises, before any weights are made."""
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm_engine import LLMEngine

    with pytest.raises(ValueError, match="dense engine was removed"):
        if front == "LLMServer":
            LLMServer("no-such-preset", paged=False)
        else:
            LLMEngine(None, None, paged=False)


def test_a_server_nobody_configured_names_its_decode_kernel():
    """``LLMServer("tiny")`` serves from the pool: its replica reports
    which paged-decode implementation it resolved to, never ``None``."""
    from ray_tpu.serve.llm import LLMServer

    srv = LLMServer("tiny")
    try:
        info = srv.device_info()
        assert info["paged_impl"] in ("tpu", "xla")
        assert "paged" not in info
        eng = srv.engine
        assert eng.load_snapshot()["pool_pages"] == (
            1 + (eng.num_slots + 1) * eng.max_pages)
    finally:
        srv.engine.close()


# (engine arguments, prompts): the shapes that once had an engine test
# each.  ``rows-wide-wave``: three prompts into num_slots=3, an
# admission wave (4) as wide as the engine's row count (3 + scratch).
# ``default-engine``: no argument at all, more requests than its slots.
_EQUALITY_CASES = {
    "more-requests-than-slots": (
        dict(num_slots=2, block_size=4, page_size=16, kv_pool_pages=1 + 8),
        [[1, 2, 3], [7, 8, 9, 10, 11], [50, 60], [5] * 9]),
    "rows-wide-wave": (
        dict(num_slots=3, block_size=4),
        [[11, 12, 13], [21, 22], [31, 32, 33, 34]]),
    "one-block-covers-the-answer": (
        dict(num_slots=4),
        [[1, 2, 3], [7, 8, 9, 10, 11], [50, 60]]),
    "default-engine": (
        dict(),
        [[i + 1, i + 2, i + 3][:1 + i % 3] for i in range(11)]),
}


@pytest.mark.parametrize("case", list(_EQUALITY_CASES))
def test_engine_matches_lone_generation(tiny_parts, case):
    """Engine-level (the VERDICT bar): greedy decode through the engine
    — slotless prefill, install, per-row tables — equals each prompt
    generated alone, whatever its neighbours in the batch; a pool that
    nobody sized holds every slot at full length plus scratch."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    kw, prompts = _EQUALITY_CASES[case]
    expect = _lone_expect(cfg, params, prompts)
    eng = LLMEngine(cfg, params, **kw)
    try:
        assert eng.load_snapshot()["pool_pages"] == kw.get(
            "kv_pool_pages", 1 + (eng.num_slots + 1) * eng.max_pages)
        results = _submit_all(eng, prompts)
        for i in range(len(prompts)):
            assert results[i] is not None
            assert results[i].tokens == expect[i], (
                f"decode diverged for prompt {i}")
            assert results[i].prompt_len == len(prompts[i])
    finally:
        eng.close()


def test_default_pool_holds_every_slot_at_full_length(tiny_parts):
    """``num_slots`` requests that each run to ``max_seq_len`` all get
    their pages at once on the default pool: every first token is out
    before any request has finished (a request that had to wait for
    pages gets them only when another finishes), and every page comes
    back."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, num_slots=3)
    try:
        done_at_first_token = {}

        def submit(rid):
            def on_token(_tok):
                # the loop thread's own count, read on the loop thread
                done_at_first_token.setdefault(
                    rid, eng.stats.requests_completed)
            return eng.submit([rid + 1, rid + 2], temperature=0.0,
                              max_new_tokens=2 * cfg.max_seq_len,
                              on_token=on_token)

        results = [None] * 3
        threads = [threading.Thread(
            target=lambda i=i: results.__setitem__(i, submit(i)))
            for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert done_at_first_token == {0: 0, 1: 0, 2: 0}
        for r in results:
            assert r is not None and r.finish_reason == "length"
            assert r.prompt_len + len(r.tokens) == cfg.max_seq_len
        snap = eng.load_snapshot()
        assert snap["free_pages"] == snap["pool_pages"] - 1
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
    eng = LLMEngine(cfg, params, num_slots=2, block_size=4,
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
    eng = LLMEngine(cfg, params, num_slots=1, block_size=4,
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


# ---- a prefilled request joins the block behind its wave (ISSUE 38) ----


def _dispatch_log(eng):
    """What the engine hands the device, in order: ``("prefill",)`` a
    prefill wave (full or suffix), ``("block", positions)`` a decode
    block with the positions of the rows it installs on a real table
    (redirect and pad rows left out)."""
    import numpy as np

    log = []
    block = eng._block_jit

    def block_spy(params, cache, state, meta, lasts, tables):
        meta, first_page = np.asarray(meta), np.asarray(tables)[:, 0]
        log.append(("block", sorted(
            int(pos) for slot, pos, page in zip(meta[0], meta[1], first_page)
            if slot < eng.num_slots and page != 0)))
        return block(params, cache, state, meta, lasts, tables)

    eng._block_jit = block_spy
    for name in ("_get_prefill_paged", "_get_prefill_suffix"):
        def get_spy(bucket, wave, get=getattr(eng, name)):
            fn = get(bucket, wave)

            def run(*operands):
                log.append(("prefill",))
                return fn(*operands)
            return run
        setattr(eng, name, get_spy)
    return log


def _blocks_behind_waves(log):
    """The installed positions of the block dispatched right behind
    each prefill wave (``None`` where no block follows a wave)."""
    return [log[i + 1][1] if i + 1 < len(log) and log[i + 1][0] == "block"
            else None for i, entry in enumerate(log) if entry[0] == "prefill"]


def _second_while_first_decodes(eng, first, second):
    """Submit ``first`` (kwargs of ``submit``), and ``second`` the
    moment ``first``'s first token is out.  Returns both results and
    how many requests had completed when ``second``'s first token came
    (0: ``first`` was still decoding)."""
    out = {}
    started = threading.Event()
    done_before = []

    def go(name, kw, on_first):
        seen = []

        def on_token(_tok):
            if not seen:
                seen.append(1)
                on_first()
        out[name] = eng.submit(on_token=on_token, temperature=0.0, **kw)

    threads = [
        threading.Thread(target=go, args=("first", first, started.set)),
        threading.Thread(target=go, args=("second", second, lambda: (
            done_before.append(eng.stats.requests_completed))))]
    threads[0].start()
    assert started.wait(timeout=120), "the first request made no token"
    threads[1].start()
    for t in threads:
        t.join(timeout=240)
    return out["first"], out["second"], done_before[0]


def _all_came_back(eng):
    snap = eng.load_snapshot()
    assert snap["busy_slots"] == 0 and snap["ready"] == 0
    assert snap["free_pages"] + snap["prefix_pages_cached"] == (
        snap["pool_pages"] - 1)
    assert snap["state_entries_in_use"] == 0


def _hybrid_parts():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT

    cfg = get_config("tiny-olmo-hybrid")
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, dict(num_slots=2, page_size=4, max_seq_len=64,
                             max_prompt_len=32, block_size=4,
                             min_prefill_bucket=8)


@pytest.mark.parametrize("case", [
    "joins-the-block-in-flight", "state-entry", "prefix-hit",
    "no-free-slot", "eos-at-first-token", "eos-at-first-token-state-entry",
    "one-token-is-all", "engine-fatal"])
def test_prefilled_request_is_stepped_by_the_block_behind_its_wave(
        tiny_parts, case):
    """A request admitted while a slot is free is installed into the
    block dispatched right behind its prefill wave, its first token
    going from the wave to the block on the device
    (``installs_with_prefill``), and decodes exactly as alone.  Each
    case is one of the things the host learns only afterwards, or a
    reason not to install."""
    from ray_tpu.serve.llm_engine import LLMEngine

    hybrid = case.endswith("state-entry")
    if hybrid:
        cfg, params, kw = _hybrid_parts()
    else:
        cfg, params = tiny_parts
        kw = dict(num_slots=2, block_size=4, page_size=16,
                  kv_pool_pages=1 + 8)
    if case == "no-free-slot":
        kw["num_slots"] = 1
    if case == "prefix-hit":
        kw.update(kv_pool_pages=1 + 16, prefix_cache_pages=8)
    shared = list(range(3, 3 + 32))               # two full pages
    long, short = ([4, 5, 6], [7, 8, 9, 10, 11]) if case != "prefix-hit" \
        else (shared + [40, 41], shared + [50, 51, 52, 53, 54])
    n_long, n_short = (40, 8) if not hybrid else (24, 8)

    def alone(prompt, n):
        if not hybrid:
            return _lone_expect(cfg, params, [prompt], n=n)[0]
        lone = LLMEngine(cfg, params, **kw)
        try:
            return lone.submit(prompt, max_new_tokens=n,
                               temperature=0.0).tokens
        finally:
            lone.close()

    eng = LLMEngine(cfg, params, **kw)
    try:
        log = _dispatch_log(eng)
        st = eng.stats
        if case in ("joins-the-block-in-flight", "state-entry",
                    "prefix-hit", "no-free-slot"):
            if case == "prefix-hit":
                # the run both prompts share is cached by a request
                # that came and went
                eng.submit(shared + [60], max_new_tokens=2)
                log.clear()
            first, second, done_before = _second_while_first_decodes(
                eng, dict(prompt=long, max_new_tokens=n_long),
                dict(prompt=short, max_new_tokens=n_short))
            assert done_before == (1 if case == "prefix-hit" else 0)
            assert first.tokens == alone(long, n_long)
            assert second.tokens == alone(short, n_short)
            behind = _blocks_behind_waves(log)
            assert first.slot_wait_s == 0.0
            if case == "no-free-slot":
                # the one slot is taken: prefilled ahead, it waits in
                # _ready for the slot as it always did
                assert behind == [[len(long)], []]
                assert st.installs_with_prefill == 1 and st.prefills == 2
                assert second.slot_wait_s > 0.0
            else:
                assert behind == [[len(long)], [len(short)]]
                assert st.installs_with_prefill == st.prefills == (
                    3 if case == "prefix-hit" else 2)
                assert second.slot_wait_s == 0.0
            if case == "prefix-hit":
                assert st.prefix_hits == 2
        elif case.startswith("eos-at-first-token"):
            # test_page_recycling_stays_exact's form, every second
            # request ending at its first token: it was installed and
            # is stepping when the host learns that, and its slot, pages
            # and entry go the way an evicted row's go
            prompts = [[i + 1, i + 2, i + 3] for i in range(12)]
            expect = [alone(p, 6) for p in prompts]
            r = eng.submit(prompts[1], max_new_tokens=64, temperature=0.0,
                           eos_id=expect[1][0])
            assert (r.finish_reason, r.tokens) == ("eos", expect[1][:1])
            assert _blocks_behind_waves(log) == [[3]]
            assert st.installs_with_prefill == 1
            _all_came_back(eng)
            results = [None] * len(prompts)

            def go(i):
                results[i] = eng.submit(
                    prompts[i], max_new_tokens=6, temperature=0.0,
                    eos_id=expect[i][0] if i % 2 else None)
            threads = [threading.Thread(target=go, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            for i, r in enumerate(results):
                assert r is not None, f"request {i} hung"
                assert r.tokens == (expect[i][:1] if i % 2 else expect[i]), (
                    f"request {i} diverged")
        elif case == "one-token-is-all":
            # the host knows it ends at its first token: no slot, no
            # block at all
            want = alone(long, 1)
            for _ in range(2):
                r = eng.submit(long, max_new_tokens=1, temperature=0.0)
                assert (r.finish_reason, r.tokens) == ("length", want)
            r = eng.submit([9] * 3, temperature=0.0,
                           max_new_tokens=2 * cfg.max_seq_len)
            assert len(r.tokens) == cfg.max_seq_len - 3
            edge = [9] * (cfg.max_seq_len // 2)
            eng2 = LLMEngine(cfg, params, max_seq_len=len(edge) + 1,
                             max_prompt_len=len(edge), **kw)
            try:
                log2 = _dispatch_log(eng2)
                r = eng2.submit(edge, max_new_tokens=8, temperature=0.0)
                assert r.finish_reason == "length" and len(r.tokens) == 1
                assert log2 == [("prefill",)]
            finally:
                eng2.close()
            assert _blocks_behind_waves(log)[:2] == [None, None]
            assert (st.installs_with_prefill, st.prefills) == (1, 3)
        elif case == "engine-fatal":
            # installed, not yet fetched, and the block fails: the
            # request fails once, everything is taken back
            spied = eng._block_jit

            def fail_once(*operands):
                eng._block_jit = spied
                raise RuntimeError("injected block failure")
            eng._block_jit = fail_once
            reached = []     # deliveries that found it undelivered
            deliver = eng._safe_deliver
            eng._safe_deliver = lambda req, ok, value: (
                req.delivered or reached.append(ok),
                deliver(req, ok, value))
            with pytest.raises(RuntimeError, match="injected"):
                eng.submit(long, max_new_tokens=8, temperature=0.0)
            assert reached == [False]
            assert _blocks_behind_waves(log) == [None]
        _all_came_back(eng)
        # and the engine serves on, exactly
        assert eng.submit(short, max_new_tokens=6,
                          temperature=0.0).tokens == alone(short, 6)
        _all_came_back(eng)
    finally:
        eng.close()


def test_install_path_compiles_nothing_after_warmup(tiny_parts):
    """The programs that hand a wave's first tokens to the block behind
    it are compiled by ``warmup(prompt_lens=())``, which every benchmark
    replica calls after its own prefill warm-up: bursts that form every
    wave size, each installed whole with its prefill, then compile
    nothing at all (counted the way the benchmark's
    ``no_compile_in_window`` counts: ``chipbench/lib/compile_watch``)."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import compile_watch
    from ray_tpu.serve.llm_engine import _WAVE_SIZES, LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, num_slots=_WAVE_SIZES[-1], block_size=4,
                    page_size=16)
    try:
        bucket = eng._bucket(3)
        for wave in _WAVE_SIZES:       # a replica's own prefill warm-up
            packed = np.zeros((wave, eng.packed_width(bucket)), np.int32)
            packed[:, bucket] = 1
            _, eng._cache = eng._get_prefill_paged(bucket, wave)(
                eng.params, eng._cache, jnp.asarray(packed),
                jnp.zeros((wave, eng.max_pages), jnp.int32),
                jax.random.PRNGKey(0))
        before = compile_watch.snapshot()
        eng.warmup(prompt_lens=())
        warmed = compile_watch.names_since(before)
        assert sum("engine_install_firsts" in name for name in warmed) == len(
            _WAVE_SIZES), warmed

        def burst(size):
            async def run():
                with eng._lock:        # one admission, hence one wave
                    futs = [eng.submit([i + 1, i + 2, i + 3],
                                       max_new_tokens=6, temperature=0.0)
                            for i in range(size)]
                return await asyncio.gather(*futs)
            return asyncio.run(run())

        burst(1)       # what any first request compiles (the key split)
        expect = _lone_expect(cfg, params, [[1, 2, 3], [32, 33, 34]], n=6)
        mark = compile_watch.snapshot()
        st = eng.stats
        waves0, early0 = st.prefill_waves, st.installs_with_prefill
        for size in reversed(_WAVE_SIZES):
            results = burst(size)
            assert results[0].tokens == expect[0]
            if size == _WAVE_SIZES[-1]:
                assert results[-1].tokens == expect[1]
        assert st.prefill_waves - waves0 == len(_WAVE_SIZES)
        assert st.installs_with_prefill - early0 == sum(_WAVE_SIZES)
        after = compile_watch.snapshot()
        assert after["backend_compiles"] == mark["backend_compiles"], (
            compile_watch.names_since(mark))
        assert compile_watch.names_since(mark) == []
    finally:
        eng.close()


@pytest.mark.parametrize("kw", [
    dict(num_slots=2),
    dict(num_slots=2, max_prompt_len=16),
    dict(num_slots=2, block_size=4, page_size=16, kv_pool_pages=1 + 6,
         max_prompt_len=60),
], ids=["default-pool", "short-prompts", "small-pool"])
def test_eos_streaming_and_refusals(tiny_parts, kw):
    """on_token fires once per generated token, in order; eos stops a
    row; an answer stops at max_seq_len; a prompt over max_prompt_len,
    or a request that can never fit the pool, fails alone without
    wedging the loop."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, **kw)
    try:
        seen = []
        probe = eng.submit([3, 4, 5], max_new_tokens=5, temperature=0.0,
                           on_token=seen.append)
        assert seen == probe.tokens and len(seen) == 5
        # the first greedily generated token as a fake eos: the request
        # must stop right there
        eos = probe.tokens[0]
        r = eng.submit([3, 4, 5], max_new_tokens=64, temperature=0.0,
                       eos_id=eos)
        assert r.finish_reason == "eos"
        assert r.tokens == [eos]
        with pytest.raises(ValueError, match="max_prompt_len"):
            eng.submit([9] * (eng.max_prompt_len + 1), max_new_tokens=4)
        longest = dict(max_new_tokens=2 * cfg.max_seq_len)
        if eng.max_pages > eng.kv_pool_pages - 1:
            # needs ceil(max_seq 128 / 16) = 8 pages > the pool's 6
            with pytest.raises(ValueError, match="KV pages"):
                eng.submit([9] * 60, **longest)
        else:
            r = eng.submit([3, 4, 5], **longest)       # > max_seq_len cap
            assert r.finish_reason == "length"
            assert r.prompt_len + len(r.tokens) == cfg.max_seq_len
        # engine still serves afterwards
        r2 = eng.submit([3, 4, 5], max_new_tokens=5, temperature=0.0)
        assert r2.tokens == probe.tokens
    finally:
        eng.close()


# ---- the stacked pool, addressed by (layer, page) (ISSUE 25) ----


def _random_pool(rs, layers, pages, kvh, ps, hd, dtype="float32"):
    import jax.numpy as jnp
    return jnp.asarray(rs.randn(layers, pages, kvh, ps, 2 * hd), dtype)


def _dense_decode_attention(q, pool_layer, tables, lengths):
    """Per-row dense softmax attention over the row's own pages, in
    numpy float64: the oracle of the oracle."""
    import numpy as np
    pool_layer = np.asarray(pool_layer, np.float64)
    q = np.asarray(q, np.float64)
    rows, heads, hd = q.shape
    kvh, ps = pool_layer.shape[1], pool_layer.shape[2]
    out = np.zeros((rows, heads, hd))
    for r in range(rows):
        kv = np.concatenate([pool_layer[p] for p in np.asarray(tables[r])],
                            axis=1)[:, :int(lengths[r])]    # [kvh, n, 2hd]
        for h in range(heads):
            k, v = kv[h // (heads // kvh), :, :hd], kv[h // (heads // kvh),
                                                       :, hd:]
            s = k @ q[r, h] * hd ** -0.5
            w = np.exp(s - s.max())
            out[r, h] = (w / w.sum()) @ v
    return out


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_paged_attention_xla_reads_its_layer(head_dim, layer):
    """The oracle on the stacked pool, layer given as a traced scalar,
    against dense attention over that layer's pages alone, at
    2*head_dim 128 and 256."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention_xla

    rs = np.random.RandomState(head_dim + layer)
    pool = _random_pool(rs, 5, 13, 2, 16, head_dim)
    q = jnp.asarray(rs.randn(3, 4, head_dim), jnp.float32)
    tables = jnp.asarray(rs.permutation(12)[:9].reshape(3, 3) + 1,
                         jnp.int32)
    lengths = jnp.asarray([1, 17, 48], jnp.int32)
    got = jax.jit(lambda l: paged_attention_xla(
        q, pool, tables, lengths, layer=l))(jnp.int32(layer))
    want = _dense_decode_attention(q, pool[layer], tables, lengths)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


_PS = 16          # page size of the kernel cases below


@pytest.fixture
def tpu_interpreter(monkeypatch):
    """Run ``pallas_call`` in the TPU interpreter (DMAs, semaphores and
    SMEM simulated on the CPU; memory nobody wrote reads as NaN), with
    the kernel's chunk cut to two pages so that a row of a few pages
    already spans several chunks."""
    import functools
    import importlib

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=pltpu.InterpretParams()))
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.paged_attention"),
        "_CHUNK_TOKENS", 2 * _PS)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_pallas_decode_kernel_matches_oracle_in_tpu_interpreter(
        tpu_interpreter, head_dim, layer):
    """The Pallas kernel itself, run on the CPU by the TPU interpreter
    (it simulates the DMAs and semaphores): it must fetch
    ``kv_pages[layer, page]`` — a wrong layer or page is a wrong answer
    — and agree with the gather oracle to bf16 precision."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import (paged_attention_tpu,
                                             paged_attention_xla)

    rs = np.random.RandomState(7 * head_dim + layer)
    pool = _random_pool(rs, 3, 13, 2, 16, head_dim, "bfloat16")
    q = jnp.asarray(rs.randn(3, 4, head_dim), jnp.bfloat16)
    tables = jnp.asarray(rs.permutation(12).reshape(3, 4) + 1, jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)
    got = paged_attention_tpu(q, pool, tables, lengths,
                              layer=jnp.int32(layer))
    want = paged_attention_xla(q, pool, tables, lengths, layer=layer)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    other = paged_attention_xla(q, pool, tables, lengths,
                                layer=(layer + 1) % 3)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(other, np.float32)).max() > 0.1


# ---- the kernel follows the bytes live rows read (ISSUE 32) ----


def _kernel_case(rs, head_dim, lengths, max_pages, kvh=2, group=3):
    """A bf16 pool whose page 0 is scratch, a query, and shuffled
    disjoint tables for rows of ``lengths``."""
    import jax.numpy as jnp

    rows = len(lengths)
    pool = _random_pool(rs, 2, 1 + rows * max_pages, kvh, _PS, head_dim,
                        "bfloat16")
    q = jnp.asarray(rs.randn(rows, kvh * group, head_dim), jnp.bfloat16)
    tables = (rs.permutation(rows * max_pages).reshape(rows, max_pages)
              + 1).astype("int32")
    return q, pool, tables, jnp.asarray(lengths, jnp.int32)


def _layer_1(fn, q, pool, tables, lengths, window, live):
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(fn(
        q, pool, jnp.asarray(tables), lengths, layer=1, window=window,
        live=None if live is None else jnp.asarray(live)), np.float32)


def _assert_kernel_is_oracle(*case):
    import numpy as np
    from ray_tpu.ops.paged_attention import (paged_attention_tpu,
                                             paged_attention_xla)

    live = case[-1]
    got = _layer_1(paged_attention_tpu, *case)
    want = _layer_1(paged_attention_xla, *case)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2)
    if live is not None:
        dead = ~np.asarray(live)
        assert (got[dead] == 0).all() and (want[dead] == 0).all()
    return got


# lengths on the page edges, and a one-page row handing the pipeline
# over to a many-page row and back
_KERNEL_LENGTHS = [1, _PS, _PS + 1, 7 * _PS - 3, 5, 4 * _PS]
_LIVE_PATTERNS = {
    "all-live": None,
    "dead-first": [0, 1, 1, 1, 1, 1],
    "dead-middle": [1, 1, 0, 0, 1, 1],
    "dead-last": [1, 1, 1, 1, 1, 0],
    "one-live": [0, 0, 0, 1, 0, 0],
    "none-live": [0, 0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("pattern", list(_LIVE_PATTERNS))
@pytest.mark.parametrize("window", [None, 3 * _PS + 5, 1 << 20],
                         ids=["no-window", "window-inside", "window-past"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_pallas_decode_kernel_reads_live_rows_only(
        tpu_interpreter, head_dim, window, pattern):
    """Kernel against ``paged_attention_xla`` in the TPU interpreter:
    the padded-query form (head_dim 64) and the split-page form (128),
    with no window operand, a window that cuts the long rows and one
    that cuts nothing, under every shape of ``live``; a dead row's
    output is exactly zero on both sides."""
    import numpy as np

    live = _LIVE_PATTERNS[pattern]
    live = None if live is None else np.asarray(live, bool)
    rs = np.random.RandomState(head_dim + len(pattern))
    q, pool, tables, lengths = _kernel_case(rs, head_dim, _KERNEL_LENGTHS, 7)
    if live is not None:
        tables[~live] = 0                 # as the engine leaves them
    _assert_kernel_is_oracle(q, pool, tables, lengths, window, live)


@pytest.mark.parametrize("window", [None, 3 * _PS + 5],
                         ids=["no-window", "window"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_pallas_decode_kernel_never_loads_what_it_must_not_read(
        tpu_interpreter, head_dim, window):
    """NaN in the scratch page, in every page of the dead rows' tables,
    in the pages past each live row's length and in the pages wholly
    behind the window: a page that is loaded and only masked turns
    ``0 * NaN`` into NaN in ``p @ v``, so a finite output equal to the
    clean pool's says those pages were never DMA'd."""
    import jax.numpy as jnp
    import numpy as np

    live = np.asarray([1, 0, 1, 1, 0, 1], bool)
    rs = np.random.RandomState(head_dim)
    q, pool, tables, lengths = _kernel_case(rs, head_dim, _KERNEL_LENGTHS, 7)
    clean = _assert_kernel_is_oracle(q, pool, tables, lengths, window, live)
    poison = [0]
    for r, n in enumerate(_KERNEL_LENGTHS):
        first = 0 if window is None else max(0, n - window) // _PS
        last = -(-n // _PS)
        poison += (list(tables[r]) if not live[r] else
                   list(tables[r, :first]) + list(tables[r, last:]))
    assert len(poison) > 7
    pool = pool.at[:, jnp.asarray(poison)].set(jnp.nan)
    from ray_tpu.ops.paged_attention import paged_attention_tpu
    np.testing.assert_array_equal(
        _layer_1(paged_attention_tpu, q, pool, tables, lengths, window,
                 live), clean)


# ---- the kernel writes the step's row itself (ISSUE 45) ----

_WPS = 32         # two bf16 sublane tiles: the write-back is HALF a page

# name: (head_dim, or None for a latent row 640 / 512), each row's
# length WITH the new token, window, live.  Chunks are two pages.
_WRITE_CASES = {
    "halves-split": (128, [37, 70, 5], None, None),
    "padded-query": (64, [37, 70, 5], None, None),
    "latent-row": (None, [37, 70, 5], None, None),
    "window": (128, [37, 150, 100], 40, None),
    # row 1 is dead on its table's scratch page, row 3 dead with pages
    # of its own, which the kernel must leave as they were
    "dead-rows": (64, [37, 70, 5, 90], None, [1, 0, 1, 0]),
    "offset-0": (128, [33, 65, 129], None, None),       # a fresh page
    "offset-last": (128, [32, 64, 96], None, None),
    "odd-offset": (64, [36, 70, 8], None, None),  # packed second halves
    "one-position": (128, [1, 1, 1], None, None),
    # 4, 2 and 6 pages: the tail page is the SECOND of its chunk
    "tail-not-first-in-chunk": (128, [100, 60, 168], None, None),
}


@pytest.mark.parametrize("case", list(_WRITE_CASES))
def test_pallas_decode_kernel_writes_the_new_row_in_tpu_interpreter(
        tpu_interpreter, monkeypatch, case):
    """The kernel handed the step's rows against its oracle,
    ``write_kv_pages`` then ``paged_attention_xla``: the output within
    the kernel's tolerance; the returned pool BIT for bit the oracle's
    on every kept row's tail page and, everywhere else (other layers,
    other pages, a dead row's pages; scratch page 0 left out), the pool
    that went in.  Every page holds finite junk, a fresh one too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_CHUNK_TOKENS", 2 * _WPS)
    monkeypatch.setattr(pa, "_LATENT_CHUNK_TOKENS", 2 * _WPS)
    hd, lengths, window, live = _WRITE_CASES[case]
    rows, mp, layer = len(lengths), 6, 1
    kvh, heads, row, v_width = ((2, 4, 2 * hd, None) if hd
                                else (1, 8, 640, 512))
    rs = np.random.RandomState(len(case))
    pool = jnp.asarray(rs.randn(3, 1 + rows * mp, kvh, _WPS, row),
                       jnp.bfloat16)
    q = jnp.asarray(rs.randn(rows, heads, hd or row), jnp.bfloat16)
    new = jnp.asarray(rs.randn(rows, kvh, row), jnp.bfloat16)
    tables = (rs.permutation(rows * mp).reshape(rows, mp) + 1).astype(
        "int32")
    if live is not None:
        live = np.asarray(live, bool)
        tables[1] = 0 if not live[1] else tables[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    kw = dict(layer=layer, window=window, v_width=v_width,
              live=None if live is None else jnp.asarray(live))
    got, got_pool = pa.paged_attention_tpu(
        q, pool, jnp.asarray(tables), lengths, new_rows=new, **kw)
    want_pool = pa.write_kv_pages(pool, new[:, None], jnp.asarray(tables),
                                  lengths[:, None] - 1, layer=layer)
    want = pa.paged_attention_xla(q, want_pool, jnp.asarray(tables),
                                  lengths, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)

    def bits(x):
        return np.array(jax.lax.bitcast_convert_type(x, jnp.uint16))

    got_bits, want_bits, expect = bits(got_pool), bits(want_pool), bits(pool)
    kept = np.flatnonzero(np.ones(rows, bool) if live is None else live)
    for r in kept:
        page = tables[r, (int(lengths[r]) - 1) // _WPS]
        assert (want_bits[layer, page] != expect[layer, page]).any()
        expect[layer, page] = want_bits[layer, page]
    np.testing.assert_array_equal(got_bits[:, 1:], expect[:, 1:])


def test_paged_attention_with_new_rows_off_the_chip_is_write_then_read():
    """``paged_attention(new_rows=)`` where the kernel does not run (the
    CPU, test-size heads): ``write_kv_pages`` in front of the gather,
    ``(out, pool)``; without new rows the result is the output alone."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import paged_attention as pa

    rs = np.random.RandomState(3)
    pool = _random_pool(rs, 2, 7, 2, 4, 8)
    q = jnp.asarray(rs.randn(2, 4, 8), jnp.float32)
    new = jnp.asarray(rs.randn(2, 2, 16), jnp.float32)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([5, 12], jnp.int32)
    got, got_pool = pa.paged_attention(q, pool, tables, lengths,
                                       new_rows=new, layer=1)
    want_pool = pa.write_kv_pages(pool, new[:, None], tables,
                                  lengths[:, None] - 1, layer=1)
    np.testing.assert_array_equal(np.asarray(got_pool),
                                  np.asarray(want_pool))
    assert (np.asarray(got_pool) != np.asarray(pool)).any()
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(pa.paged_attention(
            q, want_pool, tables, lengths, layer=1)))


@pytest.mark.parametrize("window", [1, 8, 16, 48],
                         ids=["decode", "part-page", "page", "3-pages"])
@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
def test_write_kv_pages_touches_only_its_rows(layer, window):
    """Both forms of the write (decode's row scatter, prefill's chunk
    loop) against plain numpy indexing: the named (layer, page, offset)
    rows change, nothing else does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import write_kv_pages

    ps, kvh, hd = 16, 2, 8
    rs = np.random.RandomState(window)
    pool = _random_pool(rs, 3, 9, kvh, ps, hd)
    tables = np.asarray([[3, 1, 7, 5], [2, 8, 4, 6]], np.int32)
    # decode rows sit mid-page at different offsets; prefill windows
    # start on page boundaries (the engine's contract)
    start = np.asarray([21, 40] if window == 1 else [16, 0])
    positions = start[:, None] + np.arange(window)[None]
    kv = rs.randn(2, window, kvh, 2 * hd).astype(np.float32)
    got = jax.jit(lambda p, l: write_kv_pages(
        p, jnp.asarray(kv), jnp.asarray(tables), jnp.asarray(positions),
        layer=l))(pool, jnp.int32(layer))
    want = np.array(pool)
    for r in range(2):
        for t in range(window):
            pos = positions[r, t]
            want[layer, tables[r, pos // ps], :, pos % ps] = kv[r, t]
    np.testing.assert_array_equal(np.asarray(got), want)


def _handoff_round_trip(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine

    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [5] * 19]
    expect = _lone_expect(cfg, params, prompts)
    pre, dec = LLMEngine(cfg, params, **kw), LLMEngine(cfg, params, **kw)
    ps = pre.page_size
    try:
        for prompt, want in zip(prompts, expect):
            h = pre.export_prefill(prompt, max_new_tokens=8,
                                   temperature=0.0)
            assert h.kv.shape == (cfg.n_layers, -(-len(prompt) // ps),
                                  cfg.n_kv_heads, ps, 2 * cfg.head_dim)
            assert dec.import_prefill(h).tokens == want
    finally:
        pre.close()
        dec.close()


def test_handoff_round_trip_equals_lone_generation(tiny_parts_either):
    """export_prefill on one engine, import_prefill on another: the
    pages ship as [layers, npages, ...] out of one stacked pool into
    another, and decode continues exactly as lone generation."""
    _handoff_round_trip(*tiny_parts_either, num_slots=2, block_size=4,
                        page_size=16, kv_pool_pages=1 + 8)


def test_handoff_between_default_engines(tiny_parts):
    """Any two engines of one model can hand off: nothing has to be
    asked for."""
    _handoff_round_trip(*tiny_parts)


def test_prefix_suffix_prefill_equals_full_prefill(tiny_parts_either):
    """A prompt whose first pages are cached prefills only its suffix,
    attending back through the pool at (layer, page): the tokens are
    those of a full prefill."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts_either
    shared = list(range(3, 3 + 32))               # two full pages
    prompts = [shared + [40, 41], shared + [50, 51, 52, 53, 54]]
    expect = _lone_expect(cfg, params, prompts)
    eng = LLMEngine(cfg, params, num_slots=2, block_size=4,
                    page_size=16, kv_pool_pages=1 + 16,
                    prefix_cache_pages=8)
    try:
        for prompt, want in zip(prompts, expect):
            assert eng.submit(prompt, max_new_tokens=8,
                              temperature=0.0).tokens == want
        assert eng.stats.prefix_hits >= 1
        assert eng.stats.prefix_tokens_saved >= 32
    finally:
        eng.close()


def _step_blocks(eng, live_slots, blocks=2):
    """Drive ``_block_jit`` by hand: install ``live_slots`` = {slot:
    first page} at position 5 (greedy), the other rows idle on scratch
    page 0, and run ``blocks`` blocks.  Returns each live slot's tokens
    and the final positions."""
    import numpy as np

    rows = eng.num_slots + 1
    meta = np.asarray(eng._no_admit[0]).copy()
    tables = np.zeros((meta.shape[1], eng.max_pages), np.int32)
    for i, (slot, page) in enumerate(live_slots.items()):
        meta[:3, i] = (slot, 5, 0)
        tables[i, 0] = page
    out = {slot: [] for slot in live_slots}
    for _ in range(blocks):
        block, eng._state, eng._cache = eng._block_jit(
            eng.params, eng._cache, eng._state, meta,
            np.zeros((meta.shape[1],), np.int32), tables)
        block = np.asarray(block)[:rows * eng.block_size].reshape(
            rows, eng.block_size)
        for slot in live_slots:
            out[slot] += block[slot].tolist()
        meta = np.asarray(eng._no_admit[0])
        tables = np.zeros_like(tables)
    return out, np.asarray(eng._state[1]).tolist()


@pytest.mark.parametrize("live_slots", [
    {1: 3}, {0: 3}, {2: 3}, {1: 3, 0: 5}, {2: 3, 1: 5}, {0: 3, 1: 5, 2: 7}],
    ids=["slot1-alone", "slot0-alone", "slot2-alone", "dead-last",
         "dead-first", "none-dead"])
def test_idle_rows_stay_at_position_zero(tiny_parts, monkeypatch,
                                         live_slots):
    """The decode kernel reads ceil((position+1)/page_size) pages a row a
    layer, so a row that holds no request (table -> scratch page 0) must
    not walk towards max_seq_len while it steps junk; a live row
    advances by the block.  ``Block`` hands the decode attention the
    rows that hold a request (table not on the scratch page), and the
    tokens of a live row are the same whichever slots around it are
    dead."""
    import importlib

    import numpy as np
    from ray_tpu.serve.llm_engine import LLMEngine

    paged = importlib.import_module("ray_tpu.ops.paged_attention")
    masks = []

    def spy(q, *a, live=None, **kw):
        masks.append(None if live is None else live.shape)
        return real(q, *a, live=live, **kw)

    real = paged.paged_attention
    monkeypatch.setattr(paged, "paged_attention", spy)
    cfg, params = tiny_parts

    def run(slots):
        eng = LLMEngine(cfg, params, num_slots=3, block_size=4,
                        page_size=16, kv_pool_pages=1 + 8)
        try:
            return _step_blocks(eng, slots)
        finally:
            eng.close()

    tokens, positions = run(live_slots)
    assert masks and all(m == (4,) for m in masks)     # 3 slots + scratch
    assert positions == [5 + 2 * 4 if s in live_slots else 0
                         for s in range(3)] + [0]
    # the row on page 3, alone in slot 1 with every other row dead
    alone, _ = run({1: 3})
    (first,) = [s for s, page in live_slots.items() if page == 3]
    assert tokens[first] == alone[1]


@pytest.mark.parametrize("preset", ["tiny", "tiny-smallthinker"])
def test_decode_pages_read_counts_what_the_kernel_reads(preset):
    """``EngineStats.decode_pages_read`` over a short run is the sum,
    over delivered decode steps and layers, of the pages the kernel's
    loop bounds name: ``ceil((pos + 1) / page)`` from page 0 in a layer
    without a window, from ``max(0, pos + 1 - window) // page`` in one
    with; ``window_pages_read`` is the window layers' part of it."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = get_config(preset)
    params = GPT(cfg, decode=True).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    ps, w = 4, cfg.sliding_window
    window_layers = sum(cfg.window_layout) if cfg.window_layout else 0
    eng = LLMEngine(cfg, params, num_slots=2, page_size=ps, block_size=4,
                    max_seq_len=64, max_prompt_len=32,
                    min_prefill_bucket=4)
    requests = ((13, 20), (6, 9), (3, 2))        # prompt, new tokens
    try:
        for plen, new in requests:
            out = eng.submit(list(range(1, plen + 1)), max_new_tokens=new,
                             temperature=0.0)
            assert len(out.tokens) == new
        st = eng.stats.snapshot(2)
    finally:
        eng.close()
    plain = windowed = 0
    for plen, new in requests:
        # the first token is the prefill's; the step that makes token k
        # sits at position plen + k - 1
        for pos in range(plen, plen + new - 1):
            pages = -(-(pos + 1) // ps)
            plain += pages * (cfg.n_layers - window_layers)
            if window_layers:
                windowed += window_layers * (
                    pages - max(0, pos + 1 - w) // ps)
    assert plain > 0 and bool(windowed) == bool(window_layers)
    assert st["decode_pages_read"] == plain + windowed
    assert st["window_pages_read"] == windowed
    # the rows the decode kernel wrote: one a delivered step a pool layer,
    # and chipbench's reader gives them over the pool layer steps
    from chipbench.metrics import kv_rows_written_mean
    steps = sum(new - 1 for _, new in requests)
    assert st["decode_rows_written"] == steps * cfg.n_layers
    assert st["pool_layer_steps"] == st["steps"] * cfg.n_layers
    assert kv_rows_written_mean.read(
        {"serve": {"stats0": {"decode_rows_written": 0,
                              "pool_layer_steps": 0}, "stats1": st}}
    ) == steps / st["steps"]
    assert kv_rows_written_mean.read({"serve": {"stats0": {},
                                                "stats1": {}}}) is None


# ---- a block knows each row's budget (ISSUE 48) ----


def _blocks_run(eng):
    """The steps each block ran, as ``_deliver_block`` was told them,
    in order: a list that grows while the engine serves."""
    ran, deliver = [], eng._deliver_block

    def spy(block, rows, ahead, steps_run):
        deliver(block, rows, ahead, steps_run)
        ran.append(steps_run)
    eng._deliver_block = spy
    return ran


def _until(what, timeout=60.0):
    end = time.monotonic() + timeout
    while not what():
        assert time.monotonic() < end, "timed out"
        time.sleep(0.01)


def _decode_account_holds(r):
    parts = (r.stepping_s, r.prefill_stall_s, r.block_tail_s)
    assert all(p >= 0.0 for p in parts), parts
    assert sum(parts) == pytest.approx(
        r.latency_s - r.time_to_first_token_s, abs=1e-9, rel=1e-12)


# a request of n tokens installed at a block's step 0 (its first token is
# the prefill's) emits its last at step (n - 2) % block_size
_ENDS_AT = {"step-0": 2, "step-1": 3, "step-17": 19, "step-31": 33,
            "second-block": 41}


@pytest.fixture(scope="module")
def mixed_batch(tiny_parts):
    """One engine of 32-step blocks serving, all at once, requests that
    end at steps 0, 1, 17 and 31 of a block and in a second block, one
    that meets its eos in the middle of a block and one that max_seq_len
    ends: ``(names, requests, replies, the lone streams, stats, the steps
    each block ran)``."""
    import numpy as np
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    names = list(_ENDS_AT) + ["eos", "max_seq_len"]
    prompts = [[1 + i, 2 + i, 3 + i][:1 + i % 3] + [40 + i]
               for i in range(len(names))]
    prompts[-1] = list(range(7, 7 + 50))
    lone = _lone_expect(cfg, params, prompts, n=48)
    asked = list(_ENDS_AT.values()) + [48, 48]
    # the last token of its stream's first twenty that none before it
    # equals, as the eos: the request stops there, mid-block
    stream = lone[-2]
    at = max(j for j in range(3, 20) if stream[j] not in stream[:j])
    requests = [dict(prompt=p, max_new_tokens=n, temperature=0.0)
                for p, n in zip(prompts, asked)]
    requests[-2]["eos_id"] = stream[at]
    want = [s[:n] for s, n in zip(lone, asked)]
    want[-2] = stream[:at + 1]
    want[-1] = lone[-1][:64 - 50]
    eng = LLMEngine(cfg, params, num_slots=8, max_seq_len=64,
                    max_prompt_len=56, page_size=16)
    try:
        ran = _blocks_run(eng)
        replies = _serve_each(eng, requests)
        _until(lambda: eng.load_snapshot()["busy_slots"] == 0
               and sum(ran) == eng.stats.steps and len(ran) >= 2
               and ran[-1] == 0)
        tokens, positions, _, tables = eng._state[:4]
        state = [np.asarray(a).tolist()
                 for a in (tokens, positions, tables[:, 0])]
        return (names, requests, replies, want, eng.stats.snapshot(8),
                list(ran), state, cfg)
    finally:
        eng.close()


@pytest.mark.parametrize("who", list(_ENDS_AT) + ["eos", "max_seq_len"])
def test_a_mixed_batch_streams_what_lone_generation_does(mixed_batch, who):
    """Token for token the plain reference's stream, wherever in a block
    a request ends and whatever ends it; and the decode-time account
    holds on every reply, with blocks of unequal length."""
    names, requests, replies, want, *_ = mixed_batch
    i = names.index(who)
    assert replies[i] is not None
    assert replies[i].tokens == want[i]
    assert replies[i].finish_reason == ("eos" if who == "eos" else "length")
    if who == "max_seq_len":
        assert replies[i].prompt_len + len(replies[i].tokens) == 64
    _decode_account_holds(replies[i])


def test_a_mixed_batch_runs_the_steps_its_longest_row_needs(mixed_batch):
    """The device ends each block with its last live row: no block runs
    past the longest answer's last step, the block behind the last runs
    none, the counters say so, and what was written is what was
    delivered; the rows all ended themselves (table on scratch, token 0,
    position 0) with no redirect from the host."""
    _, _, replies, _, st, ran, state, cfg = mixed_batch
    delivered = sum(len(r.tokens) - 1 for r in replies)
    longest = max(len(r.tokens) - 1 for r in replies)
    assert st["steps"] == st["block_steps_run"] == sum(ran)
    assert st["block_steps_offered"] == 32 * len(ran) == 32 * st["quanta"]
    # every request was installed behind its prefill wave, in one block or
    # in neighbouring ones: far fewer steps than a scan of 32 would run
    assert longest <= st["steps"] < st["block_steps_offered"] - 32
    assert ran[-1] == 0 and max(ran) <= 32
    assert st["step_tokens"] == delivered
    assert st["decode_rows_written"] == delivered * cfg.n_layers
    assert st["pool_layer_steps"] == st["steps"] * cfg.n_layers
    tokens, positions, first_pages = state
    assert not any(tokens) and not any(positions) and not any(first_pages)


@pytest.mark.parametrize("n, blocks", [(8, [7, 0]), (33, [32, 0]),
                                       (40, [32, 7, 0])],
                         ids=["inside-a-block", "a-whole-block",
                              "into-a-second"])
def test_a_lone_request_costs_the_steps_of_its_tokens(tiny_parts, n, blocks):
    """``n`` tokens are the prefill's and ``n - 1`` decode steps, not a
    multiple of the block; the block that was dispatched before the host
    had seen the last token finds no live row and runs no step."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    (want,) = _lone_expect(cfg, params, [[3, 4, 5]], n=n)
    eng = LLMEngine(cfg, params, num_slots=2)
    try:
        ran = _blocks_run(eng)
        r = eng.submit([3, 4, 5], max_new_tokens=n, temperature=0.0)
        assert r.tokens == want
        _decode_account_holds(r)
        _until(lambda: len(ran) == len(blocks))
        st = eng.stats
        assert ran == blocks
        assert st.steps == n - 1
        assert st.block_steps_run == n - 1 < st.block_steps_offered == (
            32 * len(blocks))
        snap = st.snapshot(2)
        assert (snap["block_steps_run"], snap["block_steps_offered"]) == (
            n - 1, 32 * len(blocks))
    finally:
        eng.close()


def test_a_lone_request_s_eos_ends_its_block_one_step_on(tiny_parts):
    """The loop goes on by what the rows held BEFORE a step (their
    budgets), so an eos, which only the step's own token shows, ends the
    block one step later: the request's steps, one for nobody, and the
    block behind runs none."""
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    (stream,) = _lone_expect(cfg, params, [[3, 4, 5]], n=24)
    at = max(j for j in range(3, 20) if stream[j] not in stream[:j])
    eng = LLMEngine(cfg, params, num_slots=2)
    try:
        ran = _blocks_run(eng)
        r = eng.submit([3, 4, 5], max_new_tokens=64, temperature=0.0,
                       eos_id=stream[at])
        assert (r.tokens, r.finish_reason) == (stream[:at + 1], "eos")
        _decode_account_holds(r)
        _until(lambda: len(ran) == 2)
        assert ran == [at + 1, 0]
        assert eng.stats.step_tokens == at
    finally:
        eng.close()


def test_an_ended_row_is_neither_read_nor_written(tiny_parts):
    """The block program by hand: row 0 with a budget of 3 tokens, row 1
    ended by its eos, row 2 with no budget named, each on a page of its
    own from position 5.  A row writes the K/V rows of the steps it took
    and not one more, in this block or the next; the block ends when
    nobody is live and reports the steps it ran."""
    import numpy as np
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    eng = LLMEngine(cfg, params, num_slots=3, block_size=4, page_size=16,
                    kv_pool_pages=1 + 8)
    try:
        rows = eng.num_slots + 1

        def block(meta, tables):
            out, eng._state, eng._cache = eng._block_jit(
                eng.params, eng._cache, eng._state, meta,
                np.zeros((3,), np.int32), tables)
            out = np.asarray(out)
            return (out[:-2].reshape(rows, 4), int(out[-2]),
                    np.asarray(eng._cache["kv_pages"]))

        def install(eos_of_row_1):
            meta = np.asarray(eng._no_admit[0]).copy()
            tables = np.zeros((3, eng.max_pages), np.int32)
            for i in range(3):
                meta[:3, i] = (i, 5, 0)
                tables[i, 0] = 3 + i
            meta[-2, 0] = 3
            meta[-1, 1] = eos_of_row_1 + 1
            return meta, tables
        # what row 1 emits at its second step, unbounded, is its eos next
        tokens, steps, _ = block(*install(-1))
        assert steps == 4
        eos = int(tokens[1, 1])
        assert eos != int(tokens[1, 0])
        eng._state, eng._cache = eng._init_state(0), eng._init_cache(rows)

        tokens, steps, pool = block(*install(eos))
        assert steps == 4                          # row 2 ran them all
        written = np.abs(pool).sum(axis=(0, 2, 4))  # [page, offset]
        for page, took in ((3, 3), (4, 2), (5, 4)):
            assert (written[page, 5:5 + took] > 0).all()
            assert not written[page, 5 + took:].any(), page
            assert not written[page, :5].any()
        state = [np.asarray(a) for a in eng._state]
        assert state[3][:, 0].tolist() == [0, 0, 5, 0]       # tables
        assert state[1].tolist() == [0, 0, 9, 0]             # positions
        assert state[0][:2].tolist() == [0, 0]               # tokens
        # the next block: rows 0 and 1 stay as they ended, their pages
        # bit for bit; row 2 alone is stepped
        idle = (np.asarray(eng._no_admit[0]), np.zeros_like(install(0)[1]))
        _, steps, after = block(*idle)
        assert steps == 4
        assert (after[:, 3:5] == pool[:, 3:5]).all()
        assert (after[:, 5] != pool[:, 5]).any()
        # and with row 2 redirected, as the host does, nobody is live
        meta = idle[0].copy()
        meta[0, 0] = 2
        _, steps, last = block(meta, idle[1])
        assert steps == 0
        assert (last == after).all()
    finally:
        eng.close()


@pytest.fixture(scope="module")
def drafting_parts():
    """The tiny preset that drafts with its own prediction module."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT, get_config

    cfg = get_config("tiny-k-exaone")
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params


def _drafting_engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    return LLMEngine(cfg, params, **{
        "num_slots": 6, "page_size": 4, "max_seq_len": 96,
        "max_prompt_len": 32, "min_prefill_bucket": 8, **kw})


def test_a_drafting_engine_s_mixed_batch_is_greedy_without_the_module(
        drafting_parts):
    """``_spec_block_fn`` under budgets: greedy requests that end at
    steps 0, 1, 17, 31 of a block and in a second one, by an eos in the
    middle of a block and by max_seq_len, get the tokens the plain
    reference gives the same model without its module."""
    import dataclasses

    import flax.linen as nn

    cfg, params = drafting_parts
    plain = (dataclasses.replace(cfg, mtp_layers=0),
             {k: v for k, v in nn.unbox(params).items() if k != "mtp"})
    prompts = [[1 + i, 2 + i, 3 + i][:1 + i % 3] + [40 + i]
               for i in range(len(_ENDS_AT) + 1)] + [list(range(7, 7 + 30))]
    lone = _lone_expect(*plain, prompts, n=70)
    asked = list(_ENDS_AT.values()) + [70, 70]
    stream = lone[-2]
    at = max(j for j in range(3, 20) if stream[j] not in stream[:j])
    requests = [dict(prompt=p, max_new_tokens=n, temperature=0.0)
                for p, n in zip(prompts, asked)]
    requests[-2]["eos_id"] = stream[at]
    want = [s[:n] for s, n in zip(lone, asked)]
    want[-2] = stream[:at + 1]
    want[-1] = lone[-1][:96 - 30]
    eng = _drafting_engine(cfg, params, num_slots=8)
    try:
        ran = _blocks_run(eng)
        replies = _serve_each(eng, requests)
        _until(lambda: ran and ran[-1] == 0
               and eng.load_snapshot()["busy_slots"] == 0)
        st = eng.stats
        for r, tokens in zip(replies, want):
            assert r.tokens == tokens
            _decode_account_holds(r)
        assert replies[-2].finish_reason == "eos"
        assert st.steps == sum(ran) < st.block_steps_offered - 32
        assert st.drafts_proposed > 0
        assert st.step_tokens == st.drafts_proposed + st.drafts_accepted
    finally:
        eng.close()


@pytest.mark.parametrize("n", [9, 10, 2, 3], ids=[
    "ends-at-a-second", "ends-at-the-first-of-a-pair", "two", "three"])
def test_a_lone_drafted_request_costs_the_steps_of_its_pairs(
        drafting_parts, monkeypatch, n):
    """Every draft made to stand: a step is a pair, so ``n`` tokens are
    the prefill's and ``ceil((n - 1) / 2)`` steps, the last of which may
    overshoot the budget by the pair's second token (the host's to
    drop).  The loop goes on by what the rows held before a step, one
    token counted a step: a row that ends ON a pair's second token had
    two left, so one step for nobody runs behind it; the block behind
    runs no step."""
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    real = generate.verify_draft

    def every_draft_stands(rng, logits1, logits2, q_logits, draft, **kw):
        n_, first, second = real(rng, logits1, logits2, q_logits, draft, **kw)
        return jnp.full_like(n_, 2), draft.astype(first.dtype), second
    monkeypatch.setattr(generate, "verify_draft", every_draft_stands)
    cfg, params = drafting_parts
    eng = _drafting_engine(cfg, params, num_slots=2)
    try:
        ran = _blocks_run(eng)
        r = eng.submit(list(range(1, 10)), max_new_tokens=n,
                       temperature=1.0)
        assert len(r.tokens) == n and r.finish_reason == "length"
        _decode_account_holds(r)
        _until(lambda: len(ran) == 2)
        steps = -(-(n - 1) // 2)
        st = eng.stats
        assert ran == [steps + ((n - 1) % 2 == 0), 0]
        assert st.steps == st.block_steps_run == sum(ran)
        assert st.block_steps_offered == 64
        assert (st.drafts_proposed, st.drafts_accepted) == (steps, steps)
        assert st.step_tokens == n - 1
    finally:
        eng.close()


def _preset_engine(preset, **over):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT, get_config
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = get_config(preset, **over)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return LLMEngine(cfg, params, num_slots=3, page_size=4, max_seq_len=96,
                     max_prompt_len=32, block_size=4, min_prefill_bucket=8)


def _prefill_program_outs(eng, bucket=32, wave=2):
    """The output tree of the engine's prefill program, described."""
    import jax
    import jax.numpy as jnp
    return jax.eval_shape(
        eng._get_prefill_paged(bucket, wave), eng.params, eng._cache,
        jnp.zeros((wave, eng.packed_width(bucket)), jnp.int32),
        jnp.zeros((wave, eng.max_pages), jnp.int32), jax.random.PRNGKey(0))


@pytest.mark.parametrize("preset,over,chunk", [
    ("tiny-kanana", {"moe_experts_held": 2, "moe_held_first": 3}, None),
    ("tiny-kanana", {"moe_experts_held": 2, "moe_held_first": 3}, 8),
    ("tiny-k-exaone", {"moe_experts_held": 2, "moe_held_first": 1}, None),
    ("tiny-nemotron-h", {"moe_experts_held": 2}, None)],
    ids=["wave", "chunked-wave", "drafting", "period-latent-moe"])
def test_a_held_engine_counts_the_pair_rows_its_prefill_ran(
        monkeypatch, prefill_chunk, preset, over, chunk):
    """A model that holds a share of its experts: after prompt waves
    (one pass; chunks of 8 under ``_skips_pad``; a drafting engine's
    two model calls; a period's ``LatentMoE``) the grouped products ran
    over some pair rows and over no more than they were given (the
    warm-up's all-pad programs left out), and a caller of the program still gets ``(first tokens,
    cache)``."""
    from ray_tpu.ops import moe
    if chunk:
        prefill_chunk(chunk)
    monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", 0)      # grouped at any size
    monkeypatch.setattr(moe, "SLAB_TILE", 8)            # and in slabs
    eng = _preset_engine(preset, **over)
    try:
        assert eng._counts_pair_rows
        assert bool(chunk) == eng._skips_pad(32)
        # a warm-up's programs hold no request: run, and not counted
        eng._get_prefill_paged(32, 1)
        eng.warmup(prompt_lens=(30,))
        assert not eng._pair_rows
        assert eng.stats.snapshot(2)["moe_prefill_pairs"] == 0
        for n in (30, 17):
            assert len(eng.submit(list(range(3, 3 + n)),
                                  max_new_tokens=3).tokens) == 3
        st = eng.stats.snapshot(2)
        given, ran = st["moe_prefill_pairs"], st["moe_prefill_pairs_run"]
        assert 0 < ran < given and not eng._pair_rows
        assert given % (eng.cfg.moe_top_k * 8) == 0
        firsts, cache = _prefill_program_outs(eng)
        assert jax.tree.structure(cache) == jax.tree.structure(eng._cache)
        assert jax.tree.leaves(firsts)[0].shape == (2,)
    finally:
        eng.close()


@pytest.mark.parametrize("preset", [
    "tiny", "tiny-smallthinker", "tiny-olmo-hybrid", "tiny-granite-h"],
    ids=["dense", "every-expert-held", "period", "mamba2"])
def test_an_engine_that_holds_no_share_runs_the_programs_it_ran(
        monkeypatch, preset):
    """No share held: nothing is counted, nothing is kept, and the
    prefill program is the jitted function itself, returning ``(first
    tokens, cache)`` and no third output."""
    from ray_tpu.ops import moe
    monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", 0)
    eng = _preset_engine(preset)
    try:
        assert not eng._counts_pair_rows
        assert eng._prefill_mutable == ["cache"]
        eng.submit(list(range(3, 33)), max_new_tokens=2)
        st = eng.stats.snapshot(2)
        assert (st["moe_prefill_pairs"], st["moe_prefill_pairs_run"]) == (
            0, 0)
        assert not eng._pair_rows
        program = eng._get_prefill_paged(32, 2)
        assert hasattr(program, "lower") and hasattr(program, "trace")
        firsts, cache = _prefill_program_outs(eng)
        assert firsts.shape == (2,)
        assert jax.tree.structure(cache) == jax.tree.structure(eng._cache)
    finally:
        eng.close()
