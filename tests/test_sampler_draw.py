"""A greedy step draws no noise (ISSUE 52).

``models/generate.py sample_logits`` in its array-temperature form puts
the draw (tempering, trimming, ``rows x vocabulary`` Gumbel noise and
its argmax) inside a ``lax.cond`` on "some row has a temperature"; the
engine tells a decode step the temperatures of its LIVE rows and counts
on the device the steps that drew (``stats.block_steps_drawn``).  The
bar: the tokens are what the expression without the conditional gives,
to the bit, whatever the mix of rows and however the sampler is run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROWS, VOCAB, STEPS = 5, 64, 4

_TEMPS = {
    "all-greedy": [0.0, 0.0, 0.0, 0.0, 0.0],
    "all-sampled": [1.0, 0.7, 1.3, 0.2, 2.0],
    "mixed": [0.0, 0.8, 0.0, 1.5, 0.0],
}
_TRIMS = {
    "untrimmed": dict(top_k=0, top_p=1.0),
    "top_k": dict(top_k=7, top_p=1.0),
    "top_p": dict(top_k=0, top_p=0.8),
    "top_k-top_p": dict(top_k=9, top_p=0.9),
}


def _without_conditional(rng, logits, temps, top_k, top_p):
    """The sampler as it was before the conditional: both sides of the
    ``where`` computed for every row."""
    from ray_tpu.models.generate import _tempered

    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(
        rng, _tempered(logits, temps, top_k, top_p), axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def _looped(sampler, keys, logits, temps):
    """``sampler`` as the body of a ``lax.while_loop`` over pre-split
    ``keys``, as a decode block runs it: [STEPS, ROWS] tokens."""
    def body(carry):
        step, out = carry
        return step + 1, out.at[step].set(
            sampler(keys[step], logits[step], temps))

    return jax.lax.while_loop(
        lambda carry: carry[0] < STEPS, body,
        (jnp.zeros((), jnp.int32), jnp.zeros((STEPS, ROWS), jnp.int32)))[1]


@pytest.mark.parametrize("how", ["eager", "jit", "while_loop"])
@pytest.mark.parametrize("trim", list(_TRIMS))
@pytest.mark.parametrize("mix", list(_TEMPS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sample_logits_gives_the_tokens_of_the_plain_expression(
        dtype, mix, trim, how):
    from ray_tpu.models.generate import sample_logits

    kw = _TRIMS[trim]
    temps = jnp.asarray(_TEMPS[mix], jnp.float32)
    logits = (3 * jax.random.normal(
        jax.random.PRNGKey(1), (STEPS, ROWS, VOCAB))).astype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(2), STEPS)

    def ours(key, lg, t):
        return sample_logits(key, lg, temperature=t, **kw)

    def plain(key, lg, t):
        # on the head's float32 logits, as it was handed them
        return _without_conditional(key, lg.astype(jnp.float32), t, **kw)

    if how == "while_loop":
        got, want = (jax.jit(_looped, static_argnums=0)(f, keys, logits, temps)
                     for f in (ours, plain))
    else:
        wrap = jax.jit if how == "jit" else (lambda f: f)
        got, want = (jnp.stack([wrap(f)(keys[i], logits[i], temps)
                                for i in range(STEPS)])
                     for f in (ours, plain))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    rows = np.asarray(temps) == 0
    np.testing.assert_array_equal(np.asarray(got)[:, rows], greedy[:, rows])


def test_the_draw_is_traced_inside_the_conditional_alone():
    """One ``cond`` in the sampler's jaxpr, the noise in one of its
    branches and nowhere else; the other branch passes the argmax
    through."""
    from ray_tpu.models.generate import sample_logits

    jaxpr = jax.make_jaxpr(
        lambda k, lg, t: sample_logits(k, lg, temperature=t))(
        jax.random.PRNGKey(0), jnp.zeros((ROWS, VOCAB)), jnp.zeros((ROWS,)))
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    outside = " ".join(str(e) for e in jaxpr.eqns if e is not cond)
    assert "random_bits" not in outside and "argmax" in outside
    draws = ["random_bits" in str(b) for b in cond.params["branches"]]
    assert sorted(draws) == [False, True]
    (greedy,) = [b for b, d in zip(cond.params["branches"], draws) if not d]
    assert not greedy.eqns


def test_narrowed_logits_are_the_same_numbers():
    """What the engine hands the sampler: the head's float32 logits in
    the width they were computed in, where that loses nothing (a
    power-of-two ``logits_scaling`` or none), and as they are where it
    would."""
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import _scaled_logits, narrowed_logits

    products = (3 * jax.random.normal(
        jax.random.PRNGKey(3), (ROWS, VOCAB))).astype(jnp.bfloat16)
    for scaling, narrow in ((1.0, True), (8.0, True), (0.25, True),
                            (6.0, False)):
        cfg = get_config("tiny", dtype=jnp.bfloat16, logits_scaling=scaling)
        logits = _scaled_logits(cfg, products)
        assert logits.dtype == jnp.float32
        got = narrowed_logits(cfg, logits)
        assert got.dtype == (jnp.bfloat16 if narrow else jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)), np.asarray(logits))
    cfg = get_config("tiny", dtype=jnp.float32)
    assert narrowed_logits(cfg, logits).dtype == jnp.float32


# ---- through the engine ----

@pytest.fixture(scope="module")
def tiny_parts():
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT

    cfg = get_config("tiny")
    params = GPT(cfg, decode=True).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, params


def _engine(tiny_parts, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg, params = tiny_parts
    return LLMEngine(cfg, params, **{"num_slots": 3, "block_size": 4,
                                     "page_size": 16, "seed": 11, **kw})


_PROMPT = [5, 9, 2, 7, 3]
# what the tree before the conditional served for ``_PROMPT`` alone at
# temperature 0.9 from a fresh engine of ``_engine``'s arguments (its
# first token from the prefill program's draw, the rest from the decode
# block's, three blocks of them)
_PARENT_SAMPLED = [89, 219, 196, 230, 247, 201, 169, 69, 73, 207, 65, 46]


def test_a_sampled_requests_tokens_are_the_parents(tiny_parts):
    eng = _engine(tiny_parts)
    try:
        got = eng.submit(_PROMPT, max_new_tokens=12, temperature=0.9)
        snap = eng.stats.snapshot(eng.num_slots)
    finally:
        eng.close()
    assert got.tokens == _PARENT_SAMPLED
    # every step that ran had the sampled row live
    assert snap["block_steps_drawn"] == snap["block_steps_run"] == 11


def test_a_greedy_request_beside_a_sampled_one_is_itself(tiny_parts):
    import threading

    eng = _engine(tiny_parts)
    try:
        alone = eng.submit(_PROMPT, max_new_tokens=10, temperature=0.0)
        assert eng.stats.block_steps_drawn == 0
        assert eng.stats.block_steps_run > 0
        out = {}
        threads = [threading.Thread(target=lambda t=t: out.__setitem__(
            t, eng.submit(_PROMPT, max_new_tokens=10, temperature=t)))
            for t in (0.0, 1.2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert out[0.0].tokens == alone.tokens
        assert out[1.2].tokens != alone.tokens
        drawn = eng.stats.block_steps_drawn
        assert 0 < drawn <= eng.stats.block_steps_run
        # greedy traffic behind a sampled request that has ended (its
        # temperature still in its slot) draws nothing
        again = eng.submit(_PROMPT, max_new_tokens=10, temperature=0.0)
        assert again.tokens == alone.tokens
        assert eng.stats.block_steps_drawn == drawn
    finally:
        eng.close()


def test_steps_drawn_are_the_steps_a_sampled_row_was_live(tiny_parts):
    """The block program by hand: row 0 sampled with 3 tokens left, rows
    1 and 2 greedy with 7.  The first block runs its 4 steps and draws
    in 3; the second runs the greedy rows' last 3 and draws in none,
    though row 0's temperature is still in its slot.  The greedy rows'
    tokens are what they are with a greedy row in slot 0."""
    def run(temp0):
        eng = _engine(tiny_parts)
        try:
            rows = eng.num_slots + 1
            meta = np.asarray(eng._no_admit[0]).copy()
            tables = np.zeros((meta.shape[1], eng.max_pages), np.int32)
            for i, budget in enumerate((3, 7, 7)):
                meta[:3, i] = (i, 5, int(temp0 * 1e6) if i == 0 else 0)
                meta[-2, i] = budget
                tables[i, 0] = 3 + i
            blocks = []
            for _ in range(2):
                out, eng._state, eng._cache = eng._block_jit(
                    eng.params, eng._cache, eng._state, meta,
                    np.zeros((meta.shape[1],), np.int32), tables)
                out = np.asarray(out)
                blocks.append((out[:-2].reshape(rows, eng.block_size),
                               int(out[-2]), int(out[-1])))
                meta = np.asarray(eng._no_admit[0])
                tables = np.zeros_like(tables)
            return blocks, np.asarray(eng._state[2])
        finally:
            eng.close()

    sampled, temps = run(0.9)
    assert temps[0] == pytest.approx(0.9)
    assert [(steps, drawn) for _, steps, drawn in sampled] == [(4, 3), (3, 0)]
    greedy, _ = run(0.0)
    assert [(steps, drawn) for _, steps, drawn in greedy] == [(4, 0), (3, 0)]
    for (a, _, _), (b, _, _) in zip(sampled, greedy):
        np.testing.assert_array_equal(a[1:3], b[1:3])
