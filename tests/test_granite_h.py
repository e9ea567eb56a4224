"""A model whose layers are a Mamba-2 mixer AND a dense SwiGLU (with an
attention layer every few), under four published scalar multipliers, at
test size on the CPU against the plain reference
``chipbench/lib/reference_granite_h.py`` (ISSUE 50): two periods of
``m m a m`` (Mamba-2 of 8 heads of 16 in ONE group, state 16, a
convolution of 4 taps with a bias; attention of 4 query on 2 KV heads of
16 without rotation at a softmax scale of 1/8, not 16^-1/2; a SwiGLU of
96 in every layer; a tied head), embedding x 12, residual branches x
0.22, logits / 8, pages of 4.  Seeded random weights; logits are
compared, not tokens, but where greedy tokens are the only thing the
engine hands out."""

import asyncio

import pytest

PRESET = "tiny-granite-h"
TOL = 2e-4          # of the row's largest logit, float32
OTHER_TINY = ("tiny", "tiny-moe", "tiny-smallthinker", "tiny-olmo-hybrid",
              "tiny-kanana", "tiny-nemotron-h", "tiny-k-exaone")


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference reads."""
    names = {"mamba2_mlp": "mamba", "full_attention": "attention"}
    return {"num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
            "hidden_size": cfg.d_model,
            "layer_types": [names[k] for k in cfg.layer_types],
            "mamba_n_heads": cfg.mamba_heads,
            "mamba_d_head": cfg.mamba_head_dim,
            "mamba_d_state": cfg.ssm_state_size,
            "mamba_n_groups": cfg.mamba_groups,
            "mamba_d_conv": cfg.mamba_conv_kernel,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling}


@pytest.fixture(scope="module")
def parts():
    """``(cfg, scanned params, the reference's weights, its config)``."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_granite_h as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(PRESET)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, ref.from_program_params(params), _published(cfg)


def _worst(got, want) -> float:
    """The largest logit error of any position, as a share of that
    position's largest logit."""
    import jax.numpy as jnp
    return float((jnp.abs(got - want).max(-1)
                  / jnp.abs(want).max(-1)).max())


def test_num_params_are_the_issue_s_counts(parts):
    """The published model's counts, layer class by layer class, and the
    test preset's against its own tree."""
    import jax
    from ray_tpu.models import get_config
    full = get_config("granite-4.0-h-micro")
    assert full.layer_params("mamba2_mlp") == 76_182_976
    assert full.layer_params("full_attention") == 60_821_504
    assert full.num_params() == 3_191_396_096 == (
        36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2048 + 2048)
    kinds = full.layer_types
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [
        5, 15, 25, 35]
    assert len(full.period) == 10 and full.layers_of("mamba2_mlp") == 36
    cfg, params, _, _ = parts
    assert cfg.num_params() == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    assert len(cfg.period) == 4 and set(cfg.period) == {
        "mamba2_mlp", "full_attention"}
    for name in ("embedding_multiplier", "residual_multiplier",
                 "attention_multiplier", "logits_scaling"):
        assert getattr(cfg, name) not in (None, 1.0)
    assert cfg.attention_multiplier != cfg.head_dim ** -0.5


def test_no_multiplier_is_folded_into_a_stored_weight(parts):
    """The parameter tree is what a seed gives whatever the four scalars
    are: a checkpoint's tensors would load as they are."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT
    cfg, params, _, _ = parts
    plain = dataclasses.replace(
        cfg, embedding_multiplier=1.0, residual_multiplier=1.0,
        attention_multiplier=None, logits_scaling=1.0)
    other = GPT(plain).init(jax.random.PRNGKey(1),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, other)
    assert all(jax.tree.leaves(same))
    assert jax.tree.structure(params) == jax.tree.structure(other)


@pytest.fixture(scope="module")
def forward(parts):
    """``(tokens [2, 29], the model's logits)`` of a whole forward pass
    (the chunked recurrence, plain attention)."""
    import jax
    from ray_tpu.models import GPT
    cfg, params, _, _ = parts
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 29), 0, 256)
    return tokens, GPT(cfg).apply({"params": params}, tokens)


def test_forward_pass_matches_the_reference(parts, forward):
    from chipbench.lib import reference_granite_h as ref
    _, _, weights, published = parts
    tokens, got = forward
    for row in range(2):
        assert _worst(got[row], ref.logits(weights, tokens[row],
                                           published)) < TOL


@pytest.mark.parametrize("fault", ["embedding_one", "residual_one",
                                   "softmax_one", "softmax_sqrt",
                                   "logits_unscaled"])
def test_a_reference_wrong_in_one_scalar_fails_the_comparison(
        parts, forward, fault):
    """Each of the four multipliers set to 1 in the reference, and the
    softmax scale set to ``head_dim^-1/2``: the model's logits must NOT
    pass for it, by a wide margin, so the comparison holds each."""
    from chipbench.lib import reference_granite_h as ref
    _, _, weights, published = parts
    tokens, got = forward
    wrong = ref.logits(weights, tokens[0], published, wrong=ref.WRONG[fault])
    assert _worst(got[0], wrong) > 50 * TOL


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw = {"num_slots": 2, "page_size": 4, "max_seq_len": 64,
          "max_prompt_len": 32, "block_size": 4, "min_prefill_bucket": 8,
          **kw}
    return LLMEngine(cfg, params, **kw)


def _wave(eng, seqs, n_prompt, bucket, entries, first_page=1):
    """One prefill wave of the engine's model: ``seqs[r][:n_prompt[r]]``
    right-padded to ``bucket``, row r on pages ``first_page + 8 r ..``
    and state entry ``entries[r]`` -> ``(last real position's logits,
    cache, tables)``."""
    import jax.numpy as jnp
    import numpy as np
    wave = len(seqs)
    tokens = np.zeros((wave, bucket), np.int32)
    tables = np.zeros((wave, eng.max_pages), np.int32)
    for r, (seq, n) in enumerate(zip(seqs, n_prompt)):
        tokens[r, :min(n, len(seq))] = seq[:n]
        tables[r, :8] = first_page + 8 * r + np.arange(8)
    logits, cache = eng._last_logits(
        eng.model, eng.params, eng._cache, jnp.asarray(tokens),
        jnp.broadcast_to(jnp.arange(bucket), (wave, bucket)),
        jnp.asarray(n_prompt, jnp.int32), jnp.asarray(tables),
        jnp.asarray(entries, jnp.int32))
    return logits, cache, tables


@pytest.mark.parametrize("dtype,tol,one_row", [
    ("float32", TOL, False), ("bfloat16", 4e-2, False),
    ("float32", TOL, True)], ids=["float32", "bfloat16", "float32-one-row"])
def test_paged_prefill_and_decode_match_the_reference(parts, dtype, tol,
                                                      one_row):
    """Two prompts of different lengths in ONE prefill wave at a padded
    bucket (13 and 21 tokens at 32), then decode steps through the state
    entries and the pages, a dead row between the two, against the
    reference's full forward on each whole sequence: logits, as a share
    of the row's largest; what the engine's entries hold afterwards
    against the reference's state and convolution tail after the same
    tokens; and, in float32, each of the five wrong references failing
    the same comparison.  bfloat16: weights, activations and the tail in
    bfloat16, state in float32.  ``one_row``: each prompt in a wave of
    its own, where the state's write is ONE update and no loop over
    rows (the form whose layout the mixer pins)."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_granite_h as ref
    cfg, params, weights, published = parts
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    eng = _engine(cfg, params)
    if dtype == "bfloat16":      # the reference reads the served weights
        weights = ref.from_program_params(eng.params)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 256, n) for n in (13 + 8, 21 + 8)]
    n_prompt = (13, 21)
    if one_row:
        logits, tables = [], []
        for r, entry in enumerate((2, 5)):
            one, eng._cache, table = _wave(eng, seqs[r:r + 1],
                                           n_prompt[r:r + 1], 32, (entry,),
                                           first_page=1 + 8 * r)
            logits.append(one[0])
            tables.append(table[0])
        cache = eng._cache
    else:
        logits, cache, tables = _wave(eng, seqs, n_prompt, 32, (2, 5))
    got = [[logits[r]] for r in range(2)]
    # decode in the engine's shape: 3 rows, row 1 dead
    rows = eng._rows
    tabs = np.zeros((rows, eng.max_pages), np.int32)
    tabs[0], tabs[2] = tables[0], tables[1]
    ents = jnp.asarray([2, 0, 5], jnp.int32)
    scratch = cache["ssm_state"][:, 0]
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
            got[r].append(out[row, 0])
    for r, n in enumerate(n_prompt):
        mine = jnp.stack(got[r])
        want = ref.logits(weights, seqs[r], published)[n - 1:]
        assert _worst(mine, want) < tol
        if dtype == "float32":
            for fault, wrong in ref.WRONG.items():
                other = ref.logits(weights, seqs[r], published,
                                   wrong=wrong)[n - 1:]
                assert _worst(mine, other) > 50 * tol, fault
    # the dead row moved nothing
    assert bool((cache["ssm_state"][:, 0] == scratch).all())
    for r, entry in enumerate((2, 5)):
        _, left = ref.hidden(weights, seqs[r], published, states=True)
        assert len(left) == cfg.layers_of("mamba2_mlp") == 6
        for layer, (state, tail) in enumerate(left):
            mine = ref.from_program_state(
                cache["ssm_state"][layer, entry],
                cache["ssm_conv"][layer, entry], cfg.mamba_heads,
                cfg.mamba_conv_kernel)
            for a, b in zip(mine, (state, tail)):
                err = float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                            / jnp.linalg.norm(b))
                assert err < (1e-4 if dtype == "float32" else 5e-2)
    eng.close()


def test_a_padded_prompt_in_a_wave_leaves_what_the_unpadded_one_does(parts):
    """A prompt of 13 tokens right-padded to a bucket of 32 in a wave
    beside a longer one, and the same prompt alone at a bucket of 16
    beside nothing: state and tail of every Mamba-2 layer agree, and so
    does the first token's logits; a model NOT told the length absorbs
    the pad (the reading means something)."""
    import jax.numpy as jnp
    import numpy as np
    cfg, params, _, _ = parts
    eng = _engine(cfg, params)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(1, 256, n) for n in (13, 27)]
    logits, cache, _ = _wave(eng, seqs, (13, 27), 32, (2, 5))
    wide = [np.asarray(cache[k][:, 2]) for k in ("ssm_state", "ssm_conv")]
    alone, cache, _ = _wave(eng, seqs[:1], (13,), 16, (4,))
    for a, k in zip(wide, ("ssm_state", "ssm_conv")):
        np.testing.assert_allclose(np.asarray(cache[k][:, 4]), a,
                                   atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(alone[0], logits[0], atol=1e-5)
    # told the pad is real, the recurrence runs on through it
    absorbed, cache, _ = _wave(eng, seqs[:1], (16,), 16, (6,))
    assert float(jnp.abs(cache["ssm_state"][:, 6]
                         - wide[0]).max()) > 1e-3
    eng.close()


def _greedy(weights, published, prompt, n):
    """The reference's own greedy continuation, one forward a token."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_granite_h as ref
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref.logits(
            weights, np.asarray(seq), published)[-1])))
    return seq[len(prompt):]


def test_a_burst_through_the_engine_and_its_high_water_marks(parts):
    """Through ``submit``: admission, prefill waves, install, decode
    blocks; six requests at once on three slots, so rows and entries are
    reused and requests wait prefilled for a slot.  The tokens are the
    reference's own greedy ones; ``live_rows_max`` reaches the slots and
    ``state_entries_max`` passes them (requests prefilled ahead of a
    slot hold an entry too), both inside ``state_entries``, and
    ``reset_peaks`` starts them again."""
    import numpy as np
    cfg, params, weights, published = parts
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (13, 21, 9, 17, 5, 11)]
    want = [_greedy(weights, published, p, 6) for p in prompts]
    eng = _engine(cfg, params, num_slots=3)
    try:
        st = eng.stats.snapshot(3)
        assert st["live_rows_max"] == st["state_entries_max"] == 0

        async def burst():
            return await asyncio.gather(*[
                eng.submit(p, max_new_tokens=6) for p in prompts])
        got = [r.tokens for r in asyncio.run(burst())]
        assert got == want
        snap = eng.load_snapshot()
        assert snap["state_entries_in_use"] == 0
        assert snap["free_pages"] == snap["pool_pages"] - 1
        st = eng.stats.snapshot(3)
        assert st["live_rows_max"] == 3
        assert 3 < st["state_entries_max"] <= eng.state_entries - 1
        # the state-row counters count the new recurrent class too
        assert st["gdn_layer_steps"] == st["steps"] * 6   # 6 Mamba layers
        assert 0 < st["gdn_state_rows"] <= 3 * st["gdn_layer_steps"]
        assert st["gdn_state_rows"] / st["gdn_layer_steps"] \
            <= st["live_rows_max"]
        eng.stats.reset_peaks()
        assert eng.submit(prompts[0], max_new_tokens=6).tokens == want[0]
        st = eng.stats.snapshot(3)
        assert st["live_rows_max"] == st["state_entries_max"] == 1
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "export", "generator"])
def test_what_has_no_recurrent_state_refuses_the_model(parts, what):
    """The prefix cache, the prefill handoff and ``Generator`` refuse
    the new block class as they refuse the other recurrent ones, each by
    the missing mechanism's name."""
    import jax.numpy as jnp
    from ray_tpu.models.generate import Generator
    cfg, params, _, _ = parts
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="snapshot of the recurrent"):
            _engine(cfg, params, prefix_cache_pages=8)
        return
    if what == "generator":
        with pytest.raises(ValueError, match="no dense-cache decode"):
            Generator(cfg, params).generate(jnp.ones((1, 4), jnp.int32),
                                            max_new_tokens=2)
        return
    eng = _engine(cfg, params)
    try:
        with pytest.raises(ValueError, match="carries KV pages only"):
            eng.export_prefill([1, 2, 3], max_new_tokens=4)
    finally:
        eng.close()


@pytest.mark.parametrize("preset", OTHER_TINY)
def test_the_new_fields_leave_every_other_preset_as_it_was(preset):
    """The four fields are at their defaults in every other preset, and
    at their defaults they are not in the program: parameters and logits
    are bit for bit those of the same preset with each scalar STATED at
    its neutral value (1, 1, ``head_dim^-1/2``, 1), which takes the new
    code's other branch."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import GPT, get_config
    cfg = get_config(preset)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
                1.0, 1.0, None, 1.0)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, 256)
    params = GPT(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    want = GPT(cfg).apply({"params": params}, tokens)
    if cfg.kv_lora_rank:        # latent attention states its own scale
        return
    stated = dataclasses.replace(cfg,
                                 attention_multiplier=cfg.head_dim ** -0.5)
    other = GPT(stated).init(jax.random.PRNGKey(0), tokens)["params"]
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), params, other)))
    np.testing.assert_array_equal(
        np.asarray(GPT(stated).apply({"params": params}, tokens)),
        np.asarray(want))
    # and a scalar that is not neutral does move them
    moved = dataclasses.replace(cfg, logits_scaling=2.0)
    assert float(jnp.abs(GPT(moved).apply({"params": params}, tokens)
                         - want).max()) > 1e-4
