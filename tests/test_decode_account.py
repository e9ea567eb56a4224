"""The engine's account of the time between a request's first and last
token (ISSUE 41): ``stepping_s + prefill_stall_s + block_tail_s ==
latency_s - time_to_first_token_s`` on every reply of two tokens or more,
the sums and the waves' seconds in ``EngineStats``.

CPU-sized: the seconds here are a CPU's and are held to the identities
only, never to a size.
"""

import asyncio
import threading

import pytest

ENGINE_KW = dict(num_slots=2, block_size=4, page_size=8, max_seq_len=256,
                 kv_pool_pages=1 + 96, prefix_cache_pages=16)
SHARED = list(range(3, 3 + 16))                    # two full pages
PARTS = ("stepping_s", "prefill_stall_s", "block_tail_s")


def _engine(**kw):
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = get_config("tiny")
    params = GPT(cfg, decode=True).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    return LLMEngine(cfg, params, **{**ENGINE_KW, **kw})


def _decode_s(r):
    return r.latency_s - r.time_to_first_token_s


def _holds(r):
    """The identity, to float rounding, and no part below zero."""
    parts = [getattr(r, k) for k in PARTS]
    assert all(p >= 0.0 for p in parts), parts
    if len(r.tokens) < 2:
        assert parts == [0.0, 0.0, 0.0]
    else:
        assert sum(parts) == pytest.approx(_decode_s(r), abs=1e-9, rel=1e-12)


class _Crowd:
    """Requests released, each on a thread of its own, when a long
    request has produced so many tokens: prompts arrive while others
    decode, without a sleep."""

    def __init__(self, eng):
        self.eng, self.out, self.threads = eng, {}, []
        self.gates = {}                # tokens of the long one -> [Event]
        self.seen = 0

    def on_long_token(self, _tok):
        self.seen += 1
        for ev in self.gates.get(self.seen, ()):
            ev.set()

    def after(self, tokens, name, call):
        gate = threading.Event()
        self.gates.setdefault(tokens, []).append(gate)

        def go():
            assert gate.wait(timeout=120), f"{name}: never released"
            self.out[name] = call()
        self.threads.append(threading.Thread(target=go))

    def run(self, name, **submit_kw):
        for t in self.threads:
            t.start()
        self.out[name] = self.eng.submit(on_token=self.on_long_token,
                                         temperature=0.0, **submit_kw)
        for t in self.threads:
            t.join(timeout=240)
            assert not t.is_alive()
        return self.out


@pytest.fixture(scope="module")
def mixed():
    """One run of six kinds of request on one engine of two slots: a
    long answer, and while it decodes one that joins it, one that finds
    no free slot, one that ends at its first token, a prefix-cache hit
    and an imported handoff.  Returns the results by kind and the
    counters' growth over the run."""
    eng = _engine()
    try:
        # warm every program the run uses, fill the prefix cache, and
        # make the handoff
        eng.submit(SHARED + [60], max_new_tokens=6, temperature=0.0)
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.0)
        handoff = eng.export_prefill([9, 8, 7, 6, 5], max_new_tokens=9,
                                     temperature=0.0)
        eng.import_prefill(handoff)
        before = eng.stats.snapshot(eng.num_slots)
        crowd = _Crowd(eng)
        submit = lambda **kw: eng.submit(temperature=0.0, **kw)  # noqa: E731
        crowd.after(3, "joins", lambda: submit(
            prompt=[7, 8, 9, 10, 11], max_new_tokens=40))
        crowd.after(9, "no-free-slot", lambda: submit(
            prompt=[5, 4, 3], max_new_tokens=11))
        crowd.after(13, "one-token", lambda: submit(
            prompt=[2, 2, 2, 2], max_new_tokens=1))
        crowd.after(17, "prefix-hit", lambda: submit(
            prompt=SHARED + [40, 41], max_new_tokens=7))
        crowd.after(21, "imported", lambda: eng.import_prefill(handoff))
        out = crowd.run("long", prompt=[4, 5, 6], max_new_tokens=160)
        after = eng.stats.snapshot(eng.num_slots)
        grew = {k: after[k] - before[k] for k in after
                if isinstance(after[k], (int, float))}
        return out, grew
    finally:
        eng.close()


@pytest.mark.parametrize("who", ["long", "joins", "no-free-slot",
                                 "one-token", "prefix-hit", "imported"])
def test_parts_add_up_to_the_time_after_the_first_token(mixed, who):
    out, grew = mixed
    r = out[who]
    _holds(r)
    if who == "long":
        # every other request's wave ran between its tokens
        assert 0.0 < r.prefill_stall_s <= _decode_s(r)
        assert grew["prefill_waves"] >= 4
    if who == "no-free-slot":
        assert r.slot_wait_s > 0.0
    if who == "one-token":
        assert len(r.tokens) == 1
    if who == "prefix-hit":
        assert grew["prefix_hits"] == 1
    if who == "imported":
        assert grew["imports"] == 1 and len(r.tokens) == 9
    if len(r.tokens) > 1:
        # the junk steps behind its last token are inside the tail: a
        # request whose last token is not the last step of a block has
        # some
        assert r.block_tail_s > 0.0
        assert r.stepping_s > 0.0


def test_counters_sum_the_replies(mixed):
    out, grew = mixed
    decoded = [r for r in out.values() if len(r.tokens) > 1]
    assert len(decoded) == 5
    assert grew["decode_row_s"] == pytest.approx(
        sum(_decode_s(r) for r in decoded), abs=1e-9)
    assert grew["prefill_stall_row_s"] == pytest.approx(
        sum(r.prefill_stall_s for r in decoded), abs=1e-9)
    assert grew["block_tail_row_s"] == pytest.approx(
        sum(r.block_tail_s for r in decoded), abs=1e-9)
    assert 0.0 < grew["prefill_stall_row_s"] < grew["decode_row_s"]
    assert grew["prefill_wave_s"] > 0.0


def test_alone_it_stalls_behind_nobody():
    eng = _engine()
    try:
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.0)
        r = eng.submit([4, 5, 6, 7], max_new_tokens=30, temperature=0.0)
        _holds(r)
        assert r.prefill_stall_s == 0.0
        assert eng.stats.prefill_stall_row_s == 0.0
        # its own wave's seconds are counted, once
        assert eng.stats.prefill_wave_s > 0.0
    finally:
        eng.close()


def test_a_wave_is_charged_to_every_live_row_and_counted_once():
    """Two requests decode side by side when a third one's prompt runs:
    both are charged that wave, whole and the same seconds, and
    ``prefill_wave_s`` grows by it once."""
    eng = _engine()
    try:
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.0)
        crowd = _Crowd(eng)
        marks = {}

        def arrive(name, prompt, n):
            # the waves before it are counted: the long one is past its
            # first block (and so is the second when the third comes)
            marks[name] = (eng.stats.prefill_wave_s,
                           eng.stats.prefill_waves)
            return eng.submit(prompt, max_new_tokens=n, temperature=0.0)
        crowd.after(6, "second", lambda: arrive("second", [7, 8, 9, 10],
                                                120))
        crowd.after(40, "third", lambda: arrive("third", [1, 2, 3], 3))
        out = crowd.run("first", prompt=[4, 5, 6, 7], max_new_tokens=160)
        for r in out.values():
            _holds(r)
        st = eng.stats
        assert [marks["second"][1], marks["third"][1], st.prefill_waves] \
            == [2, 3, 4]
        second_s = marks["third"][0] - marks["second"][0]
        third_s = st.prefill_wave_s - marks["third"][0]
        assert second_s > 0.0 and third_s > 0.0
        close = dict(abs=1e-12)
        assert out["second"].prefill_stall_s == pytest.approx(third_s,
                                                              **close)
        assert out["first"].prefill_stall_s == pytest.approx(
            second_s + third_s, **close)
        assert out["third"].prefill_stall_s == 0.0
        assert st.prefill_stall_row_s == pytest.approx(
            second_s + 2 * third_s, **close)
    finally:
        eng.close()


A, B, NEW = (8, 1, False), (16, 2, False), (32, 1, True)


@pytest.mark.parametrize("case,keys,wave_s", [
    ("the loop saw the wave end", [A], 0.05),
    ("two waves, each known from when it ran alone", [A, B], 0.07),
    ("two waves, one never seen alone: the excess over a block", [A, NEW],
     0.03),
    ("nothing to reckon by", [NEW, NEW], 0.0),
])
def test_where_a_blocks_interval_went(case, keys, wave_s):
    """``_account_block`` on hand-made stamps: an end the loop saw is
    taken as seen and remembered for its program; unseen waves are
    reckoned from what their programs took alone, else from this
    block's steps at the last measured block's pace, else not at all; and a snapshot counts waves under
    way up to now while they run, and no more than such waves took
    before once they have ended unseen."""
    import time

    from ray_tpu.serve.llm_engine import EngineStats, LLMEngine, _Ahead

    eng = LLMEngine.__new__(LLMEngine)
    eng.stats = EngineStats()
    eng._stall_s = 1.0
    eng._wave_like = {A: 0.03, B: 0.04}
    # a step's pace, from a measured block of 4 steps in 0.10 s
    eng._step_like = None if case.startswith("nothing") else 0.025
    class Tokens:                      # a wave's first tokens, faked
        ready = False

        def is_ready(self):
            return self.ready

    last = Tokens()
    ahead = _Ahead(0, [(Tokens(), [], key) for key in keys[:-1]]
                   + [(last, [], keys[-1])], eng._wave_like)
    start = time.monotonic() - 10.0
    eng._begin(ahead, start)
    # still running: a snapshot counts them up to now
    assert eng.stats.snapshot(1)["prefill_wave_s"] >= 10.0
    last.ready = True
    under_way = eng.stats.snapshot(1)["prefill_wave_s"]
    if len(keys) == 1:
        assert under_way >= 10.0       # the loop is about to see its end
        eng._waves_done(ahead, start + 0.05)
    else:
        # ended nobody saw when: no more than such waves took before
        assert under_way == (0.07 if keys == [A, B] else 0.0)
    interval, got = eng._account_block(ahead, start + 0.13, 4)
    assert interval == pytest.approx(0.13)
    assert got == pytest.approx(wave_s)
    assert eng._block_s == pytest.approx(0.13 - wave_s)
    assert eng._stall_s == pytest.approx(1.0 + wave_s)
    assert eng.stats.prefill_wave_s == pytest.approx(wave_s)
    assert eng.stats.snapshot(1)["prefill_wave_s"] == pytest.approx(wave_s)
    # only what was measured teaches
    assert eng._wave_like[A] == pytest.approx(
        0.05 if len(keys) == 1 else 0.03)
    assert NEW not in eng._wave_like
    assert eng._step_like == (pytest.approx(0.02) if len(keys) == 1
                              else None if case.startswith("nothing")
                              else 0.025)


def test_decode_pool_summary_has_the_parts():
    """The disaggregated decode path's summary carries the same three
    keys, on the importing engine's clock."""
    from ray_tpu.serve.llm import LLMServer

    srv = LLMServer("tiny", num_slots=2, block_size=4, max_seq_len=64,
                    page_size=8)
    try:
        handoff = srv.engine.export_prefill([3, 1, 4, 1, 5],
                                            max_new_tokens=7)

        async def drain():
            return [item async for item in srv.decode(handoff, {})]
        items = asyncio.run(drain())
        summary = items[-1]
        assert summary["num_tokens"] == 7
        parts = [summary[k] for k in PARTS]
        assert all(p >= 0.0 for p in parts)
        assert sum(parts) == pytest.approx(
            summary["latency_s"] - summary["time_to_first_token_s"],
            abs=1e-9)
    finally:
        srv.engine.close()
