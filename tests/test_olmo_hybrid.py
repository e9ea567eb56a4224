"""A model whose layers are of two block CLASSES, at test size on the CPU
against the plain reference ``chipbench/lib/reference_olmo_hybrid.py``
(ISSUE 33): a period of three Gated-DeltaNet layers (4 heads, keys of 8,
values of 32, a convolution of 4 taps, write strength in (0, 2)) and one
full-attention layer (QK-norm, post-norm, no rotation), two periods,
pages of 4.  Seeded random weights; numbers are compared, not tokens,
but where greedy tokens are the only thing the engine hands out."""

import asyncio
import threading
import time

import pytest

PRESET = "tiny-olmo-hybrid"


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference reads."""
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "rms_norm_eps": cfg.norm_eps,
            "layer_types": list(cfg.layer_types),
            "linear_num_key_heads": cfg.linear_key_heads,
            "linear_num_value_heads": cfg.linear_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel,
            "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval}


@pytest.fixture(scope="module")
def parts():
    """``(cfg, scanned params, the reference's weights, its config)``."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(PRESET)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, ref.from_program_params(params), _published(cfg)


def _gdn_inputs(key, b, s, h=3, dk=8, dv=16):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, dk))),
            jax.random.normal(ks[2], (b, s, h, dv)),
            -jnp.exp(jax.random.uniform(ks[3], (b, s, h), minval=-6.0,
                                        maxval=0.5)),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))


def test_num_params_counts_both_block_classes():
    from ray_tpu.models import get_config
    cfg = get_config("olmo-hybrid-7b")
    assert cfg.layer_params("linear_attention") == 215_570_172
    assert cfg.layer_params("full_attention") == 185_809_920
    assert cfg.period == ("linear_attention",) * 3 + ("full_attention",)
    assert cfg.layers_of("linear_attention") == 24
    # the published model whole: 7.43 B up to the norms
    assert cfg.num_params() == 24 * 215_570_172 + 8 * 185_809_920 \
        + 2 * 100352 * 3840 + 3840
    assert round(cfg.num_params() / 1e9, 2) == 7.43
    # a model without layer_types counts what it always did
    assert get_config("tiny").num_params() == 115_008
    assert get_config("tiny").period is None


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_forward_pass_matches_the_reference(parts, scan):
    """``GPT`` (the period scanned, or every period unrolled) against the
    reference's token-by-token recurrence: float32 on both sides, so the
    hidden states agree to rounding (1e-4 of a row's length: some 5e-6
    measured, 70 positions through 8 layers and a chunk boundary)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config
    cfg, params, weights, published = parts
    if not scan:                 # the same weights, one subtree a period
        p = nn.unbox(params)
        params = {k: v for k, v in p.items() if k != "blocks"}
        for i in range(cfg.n_layers // len(cfg.period)):
            params[f"block_{i}"] = jax.tree.map(lambda a: a[i], p["blocks"])
        cfg = get_config(PRESET, scan_layers=False)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 70))
    got = GPT(cfg).apply({"params": params}, jnp.asarray(tokens),
                         return_hidden=True)
    for row in range(2):
        want = ref.hidden(weights, tokens[row], published)
        err = jnp.linalg.norm(got[row] - want, axis=-1) / jnp.linalg.norm(
            want, axis=-1)
        assert float(jnp.max(err)) < 1e-4


@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_chunked_form_matches_the_one_step_form(length):
    """Outputs and final state, around the chunk's edges.  float32; the
    two forms order their sums differently: 1e-5."""
    import jax
    import numpy as np
    from ray_tpu.ops import gated_delta as gd
    args = _gdn_inputs(jax.random.PRNGKey(length), 2, length)
    o1, s1 = gd.gated_delta_recurrent(*args)
    o2, s2 = gd.gated_delta_chunked(*args)
    np.testing.assert_allclose(o2, o1, atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)


def test_rows_of_different_real_lengths_in_one_padded_batch():
    """Positions at or past a row's real length leave its state
    untouched, so a padded batch gives each row what it gets alone; and
    a later call from that state continues the sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import gated_delta as gd
    args = _gdn_inputs(jax.random.PRNGKey(7), 3, 128)
    lengths = [128, 70, 5]
    o, s = gd.gated_delta_chunked(*args, lengths=jnp.asarray(lengths))
    for r, n in enumerate(lengths):
        o1, s1 = gd.gated_delta_recurrent(*[a[r:r + 1, :n] for a in args])
        np.testing.assert_allclose(o[r:r + 1, :n], o1, atol=1e-5)
        np.testing.assert_allclose(s[r:r + 1], s1, atol=1e-5)
    # without lengths the padding IS absorbed: that is the fault
    _, absorbed = gd.gated_delta_chunked(*args)
    assert float(jnp.abs(absorbed[2] - s[2]).max()) > 1e-3
    more = _gdn_inputs(jax.random.PRNGKey(8), 3, 9)
    o2, s2 = gd.gated_delta_chunked(*more, state0=s)
    o3, s3 = gd.gated_delta_recurrent(*more, state0=s)
    np.testing.assert_allclose(o2, o3, atol=1e-5)
    np.testing.assert_allclose(s2, s3, atol=1e-5)


def test_decode_kernel_in_the_interpreter_leaves_dead_rows_alone():
    """``gdn_decode`` (the Pallas kernel, interpreted) against the jnp
    form on a stacked state: live rows agree, a dead row's entry and
    every entry no row names are bit for bit what they were, whichever
    layer is addressed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import gated_delta as gd
    rows, h, dk, dv, layers, entries = 6, 4, 8, 32, 2, 9
    q, k, v, g, beta = (a[:, 0] for a in _gdn_inputs(
        jax.random.PRNGKey(3), rows, 1, h, dk, dv))
    state = jax.random.normal(jax.random.PRNGKey(9),
                              (layers, entries, dk, h * dv))
    ent = jnp.asarray([3, 1, 8, 5, 7, 0])
    live = jnp.asarray([1, 1, 0, 1, 0, 0], bool)
    for layer in (0, 1):
        o1, s1 = gd.gdn_decode_xla(q, k, v, g, beta, state, ent, live,
                                   layer=layer)
        o2, s2 = gd.gdn_decode_tpu(q, k, v, g, beta, state, ent, live,
                                   layer=layer, interpret=True)
        np.testing.assert_allclose(o2, o1, atol=1e-6)
        np.testing.assert_allclose(s2, s1, atol=1e-6)
        assert not np.asarray(o2)[~np.asarray(live)].any()
        for got in (s1, s2):
            touched = np.zeros((layers, entries), bool)
            touched[layer, [3, 1, 5]] = True
            same = np.asarray((got == state).all(axis=(2, 3)))
            assert (same == ~touched).all()
        # and it is the recurrence: one step of the oracle
        want, _ = gd.gated_delta_recurrent(
            q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
            gd.unpack_state(state[layer][ent], h))
        np.testing.assert_allclose(
            np.asarray(o2)[np.asarray(live)],
            np.asarray(want[:, 0])[np.asarray(live)], atol=1e-6)
    # no live row at all: nothing moves
    _, s3 = gd.gdn_decode_tpu(q, k, v, g, beta, state, ent,
                              jnp.zeros((rows,), bool), layer=1,
                              interpret=True)
    assert bool((s3[:, 1:] == state[:, 1:]).all())


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw = {"num_slots": 2, "page_size": 4, "max_seq_len": 64,
          "max_prompt_len": 32, "block_size": 4, "min_prefill_bucket": 8,
          **kw}
    return LLMEngine(cfg, params, **kw)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 2e-2)])
def test_paged_prefill_and_decode_match_the_reference(parts, dtype, tol):
    """Two prompts of different lengths in ONE prefill wave at a padded
    bucket (13 and 21 tokens at 32), then decode steps through the
    state entries and the pages, against the reference's full forward
    on each whole sequence: logits, as a share of the row's largest.
    float32: rounding, 1e-4.  bfloat16 (weights, activations and the
    convolution tail in bfloat16, state in float32): 2e-2 is what the
    published width reads on the chip (PERF.md); at this test's width
    of 64 a rounding is a far larger share of a row, the REFERENCE with
    nothing but its products' operands rounded to bfloat16 is 2-17%
    from itself in float32, and the program, which also keeps its
    residual stream in bfloat16, is held to four times that
    reference's distance at the same position."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_olmo_hybrid as ref
    cfg, params, weights, published = parts
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    eng = _engine(cfg, params)
    if dtype == "bfloat16":      # the reference reads the served weights
        weights = ref.from_program_params(eng.params)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 256, n) for n in (13 + 8, 21 + 8)]
    n_prompt, bucket, wave = (13, 21), 32, 2
    tokens = np.zeros((wave, bucket), np.int32)
    tables = np.zeros((wave, eng.max_pages), np.int32)
    for r, (seq, n) in enumerate(zip(seqs, n_prompt)):
        tokens[r, :n] = seq[:n]
        tables[r, :8] = 1 + 8 * r + np.arange(8)
    entries = jnp.asarray([2, 5], jnp.int32)
    logits, cache = eng._last_logits(
        eng.model, eng.params, eng._cache, jnp.asarray(tokens),
        jnp.broadcast_to(jnp.arange(bucket), (wave, bucket)),
        jnp.asarray(n_prompt, jnp.int32), jnp.asarray(tables), entries)
    want = [ref.logits(weights, seq, published) for seq in seqs]
    noise = [jnp.abs(ref.logits(weights, seq, published, bits=7) - w).max(-1)
             if dtype == "bfloat16" else jnp.zeros(len(seq))
             for seq, w in zip(seqs, want)]

    def close(got, row, pos):
        scale = float(jnp.abs(want[row][pos]).max())
        assert float(jnp.abs(got - want[row][pos]).max()) < max(
            tol * scale, 4 * float(noise[row][pos]))
    for r, n in enumerate(n_prompt):
        close(logits[r], r, n - 1)
    # decode in the engine's shape: 3 rows, row 1 dead
    rows = eng._rows
    tabs = np.zeros((rows, eng.max_pages), np.int32)
    tabs[0], tabs[2] = tables[0], tables[1]
    ents = jnp.asarray([2, 0, 5], jnp.int32)
    for step in range(8):
        toks = np.zeros((rows, 1), np.int32)
        poss = np.zeros((rows, 1), np.int32)
        for row, r in ((0, 0), (2, 1)):
            toks[row, 0] = seqs[r][n_prompt[r] + step]
            poss[row, 0] = n_prompt[r] + step
        out, mut = eng.model.apply(
            {"params": eng.params, "cache": cache}, jnp.asarray(toks),
            jnp.asarray(poss), block_tables=jnp.asarray(tabs),
            mutable=["cache"], state_rows=ents)
        cache = mut["cache"]
        for row, r in ((0, 0), (2, 1)):
            close(out[row, 0], r, n_prompt[r] + step)
    eng.close()


def _greedy(weights, published, prompt, n):
    """The reference's own greedy continuation, one forward a token."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_olmo_hybrid as ref
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref.logits(
            weights, np.asarray(seq), published)[-1])))
    return seq[len(prompt):]


def test_the_engine_s_greedy_tokens_are_the_reference_s(parts):
    """Through ``submit``: admission, prefill wave, install, decode
    blocks; four requests on two slots, so rows and entries are reused
    and requests wait prefilled for a slot.  A row reused by a second
    request answers as a fresh engine does: no state leaks across
    requests."""
    import numpy as np
    cfg, params, weights, published = parts
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (13, 21, 9, 17)]
    want = [_greedy(weights, published, p, 6) for p in prompts]
    eng = _engine(cfg, params)
    try:
        async def burst():
            return await asyncio.gather(*[
                eng.submit(p, max_new_tokens=6) for p in prompts])
        got = [r.tokens for r in asyncio.run(burst())]
        assert got == want
        # the same prompts one by one, every one on a used row and entry
        for p, w in zip(prompts, want):
            assert eng.submit(p, max_new_tokens=6).tokens == w
        snap = eng.load_snapshot()
        assert snap["state_entries_in_use"] == 0
        assert snap["free_pages"] == snap["pool_pages"] - 1
        st = eng.stats.snapshot(2)
        assert st["gdn_layer_steps"] == st["steps"] * 6   # 6 linear layers
        assert 0 < st["gdn_state_rows"] <= 2 * st["gdn_layer_steps"]
    finally:
        eng.close()


def test_entries_go_with_pages_and_admission_waits_for_one(parts,
                                                           monkeypatch):
    """One slot, one entry ahead: at most two requests hold state at a
    time whatever the queue, each holds its entry from admission to
    finish, and the rest wait as they would for pages."""
    import ray_tpu.serve.llm_engine as llm_engine
    cfg, params, weights, published = parts
    monkeypatch.setattr(llm_engine, "_STATE_AHEAD", 1)
    eng = _engine(cfg, params, num_slots=1)
    assert eng.state_entries == 3 and sorted(eng._free_states) == [1, 2]
    seen, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            snap = eng.load_snapshot()
            seen.append((snap["state_entries_in_use"], snap["pending"]))
            time.sleep(0.002)
    watcher = threading.Thread(target=watch)
    watcher.start()
    try:
        async def burst():
            return await asyncio.gather(*[
                eng.submit([7 + i] * 11, max_new_tokens=12)
                for i in range(5)])
        results = asyncio.run(burst())
    finally:
        stop.set()
        watcher.join()
    assert [len(r.tokens) for r in results] == [12] * 5
    assert max(n for n, _ in seen) == 2
    assert any(n == 2 and waiting for n, waiting in seen)
    assert sorted(eng._free_states) == [1, 2]
    assert results[0].tokens == _greedy(weights, published, [7] * 11, 12)
    eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "export", "import"])
def test_what_carries_pages_only_refuses_a_recurrent_model(parts, what):
    """No silent wrong answer: the prefix cache would resume a prompt
    from cached pages with an empty state, and the prefill handoff would
    ship pages without the state; each says which mechanism is
    missing."""
    import numpy as np
    from ray_tpu.serve.llm_engine import PrefillHandoff
    cfg, params, _, _ = parts
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="snapshot of the recurrent"):
            _engine(cfg, params, prefix_cache_pages=8)
        return
    eng = _engine(cfg, params)
    try:
        with pytest.raises(ValueError, match="carries KV pages only"):
            if what == "export":
                eng.export_prefill([1, 2, 3], max_new_tokens=4)
            else:
                eng.import_prefill(PrefillHandoff(
                    kv=np.zeros((2, 1, 4, 4, 32), np.float32), page_size=4,
                    npages=1, prompt_len=3, first_token=1,
                    max_new_tokens=4, temperature=0.0, eos_id=None))
    finally:
        eng.close()


def test_dense_cache_decode_refuses_a_recurrent_model(parts):
    """``Generator`` keeps K/V in a dense cache and has nowhere to keep
    a recurrent state: it must say so, not decode from an empty one."""
    import jax.numpy as jnp
    from ray_tpu.models.generate import Generator
    cfg, params, _, _ = parts
    with pytest.raises(ValueError, match="no dense-cache decode"):
        Generator(cfg, params).generate(jnp.ones((1, 4), jnp.int32),
                                        max_new_tokens=2)


@pytest.mark.parametrize("rows,group", [(8, 4), (6, 3), (5, 1)])
def test_a_prefill_wave_s_attention_in_groups_of_rows_is_the_whole_one(
        monkeypatch, rows, group):
    """``models/gpt.py _prefill_attend`` past ``_PREFILL_SCORE_BYTES``
    (1 GiB of float32 scores: 16 x 1024 and 4 x 2048 at 30 heads, which
    the serve-assist cell warms) runs ``xla_attention`` a group of rows
    at a time: the same numbers, row for row, whatever the group (the
    largest divisor of the wave that fits; a prime wave goes row by
    row)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import gpt
    from ray_tpu.ops.attention import xla_attention
    t, h, hd = 16, 3, 8
    q, k, v = (jax.random.normal(key, (rows, t, h, hd))
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    whole = xla_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(gpt._prefill_attend(q, k, v), whole)
    # room for ``group`` rows' scores and not one more
    monkeypatch.setattr(gpt, "_PREFILL_SCORE_BYTES",
                        4 * h * t * t * group + 4 * h * t * t - 1)
    lowered = jax.jit(gpt._prefill_attend).lower(q, k, v).as_text()
    assert f"{rows // group}x{group}x{t}x{h}x{hd}" in lowered  # grouped
    np.testing.assert_allclose(gpt._prefill_attend(q, k, v), whole,
                               rtol=1e-6, atol=1e-6)
