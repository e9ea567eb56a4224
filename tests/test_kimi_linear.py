"""Kimi Linear's blocks at test size on the CPU against the plain
reference ``chipbench/lib/reference_kimi_linear.py`` (ISSUE 55): layers
of Kimi Delta Attention (a gated delta rule whose decay is a vector over
a head's key channels) beside latent-attention layers that do not
rotate, sigmoid-routed experts behind both, in the published SHAPE of
pattern: a dense first layer in an unrolled head run of one period, two
scanned periods, an unrolled partial period (15 layers; the model's 27
are 4 + 5 x 4 + 3).  Seeded random weights; numbers are compared, not
tokens, but where greedy tokens are the only thing the engine hands
out."""

import asyncio
import dataclasses

import pytest

PRESET = "tiny-kimi-linear"


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference reads."""
    kinds = cfg.layer_types[:cfg.n_layers]
    return {
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": 10000.0,
        "linear_attn_config": {
            "kda_layers": [i + 1 for i, k in enumerate(kinds) if k == "kda"],
            "full_attn_layers": [i + 1 for i, k in enumerate(kinds)
                                 if k != "kda"],
            "num_heads": cfg.linear_key_heads,
            "head_dim": cfg.linear_key_head_dim,
            "short_conv_kernel_size": cfg.linear_conv_kernel},
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "num_experts_per_token": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_route_scale,
        "num_experts": cfg.experts_here,
        "experts_held_first": cfg.moe_held_first,
        "first_k_dense_replace": cfg.first_dense_layers}


@pytest.fixture(scope="module")
def reference():
    from chipbench.lib import reference_kimi_linear as ref
    pad, block = ref.PAD, ref.Q_BLOCK
    ref.PAD = ref.Q_BLOCK = 16
    yield ref
    ref.PAD, ref.Q_BLOCK = pad, block


@pytest.fixture(scope="module")
def parts(reference):
    """``(cfg, params, the reference's weights, its config)``: the
    share of one chip in four (experts 4-7 of 16)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT, get_config
    cfg = get_config(PRESET, moe_experts_held=4, moe_held_first=4)
    # one compiled program: run op by op the 15 layers' initialisers
    # take 40 s in every worker
    params = jax.jit(lambda key: GPT(cfg).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(1))
    return (cfg, params, reference.from_program_params(params),
            _published(cfg))


def _kda_inputs(key, b, s, h=3, dk=8, dv=16, g_min=3e-4, g_max=11.0):
    """Unit keys, scaled unit queries, write strengths in (0, 1) and
    decays drawn log-uniform over the published range's BOTH ends: a
    channel keeps nearly everything or loses e^-11 a step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    ks = jax.random.split(key, 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, dk))),
            jax.random.normal(ks[2], (b, s, h, dv)),
            -jnp.exp(jax.random.uniform(
                ks[3], (b, s, h, dk), minval=np.log(g_min),
                maxval=np.log(g_max))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))))


def test_num_params_pins_the_held_and_the_published_count():
    """ISSUE 55's arithmetic: one of sixteen chips' share with depth and
    vocabulary whole, and the published model."""
    from ray_tpu.models import GPT, get_config
    import jax
    import jax.numpy as jnp
    full = get_config("kimi-linear-48b-a3b")
    held = get_config("kimi-linear-48b-a3b", moe_experts_held=16)
    assert held.num_params() == 4_956_660_608
    assert full.num_params() == held.num_params() + 26 * 240 * 7_077_888
    assert held._kda_params() == 39_514_272
    assert held._attn_params() == 29_114_880
    assert held.layer_params("kda") == 103_219_872        # layer 0, dense
    assert (full.layers_of("kda"), full.layers_of("full_attention")) \
        == (20, 7)
    assert full.runs == (4, 5, 3) and full.period == ("kda",) * 3 + (
        "full_attention",)
    cfg = get_config(PRESET, moe_experts_held=4)
    assert cfg.runs == (4, 2, 3)
    shapes = jax.eval_shape(
        lambda: GPT(cfg).init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.num_params()
    assert set(shapes) == {"embed", "head", "blocks", "tail", "final_norm",
                           "lm_head"}
    assert "mlp" in shapes["head"]["layer_0"]             # the dense layer
    assert "moe" in shapes["head"]["layer_1"]


@pytest.mark.parametrize("length", [1, 15, 16, 17, 63, 64, 65, 200, 450])
def test_chunked_form_matches_the_one_step_form(length, monkeypatch):
    """Decays at both ends of the published range, where the factored
    chunk form ``(k_i G_i) . (k_j / G_j)`` leaves float32; in one
    segment, and (from 200 tokens on) in segments of two chunks a row."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import gated_delta as gd
    monkeypatch.setattr(gd, "SEGMENT_TOKENS", 256)
    q, k, v, g, beta = _kda_inputs(jax.random.PRNGKey(length), 2, length)
    state0 = jax.random.normal(jax.random.PRNGKey(7), (2, 3, 8, 16))
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, state0)
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, state0=state0)
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - want_o).max()) < 1e-4 * float(
        jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) < 1e-4 * float(
        jnp.abs(want_s).max())


def test_the_factored_chunk_form_would_overflow_on_these_decays():
    """What ``_channelwise_products`` is for: 64 steps at e^-11 are
    e^-704, whose reciprocal float32 cannot hold."""
    import jax
    import jax.numpy as jnp
    _, _, _, g, _ = _kda_inputs(jax.random.PRNGKey(0), 1, 64, g_min=10.0)
    assert not bool(jnp.isfinite(jnp.exp(-jnp.cumsum(g, axis=1))).all())


def test_rows_of_different_real_lengths_in_one_padded_batch():
    """Right-pad past a row's real length leaves its state as it was."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import gated_delta as gd
    q, k, v, g, beta = _kda_inputs(jax.random.PRNGKey(3), 3, 96)
    lengths = jnp.asarray([96, 41, 70])
    o, s = gd.gated_delta_chunked(q, k, v, g, beta, lengths)
    for row, n in enumerate((96, 41, 70)):
        cut = lambda a: a[row:row + 1, :n]                   # noqa: E731
        want_o, want_s = gd.gated_delta_recurrent(
            cut(q), cut(k), cut(v), cut(g), cut(beta))
        assert float(jnp.abs(o[row, :n] - want_o[0]).max()) < 1e-5
        assert float(jnp.abs(s[row] - want_s[0]).max()) < 1e-5


@pytest.mark.parametrize("form", ["recurrent", "chunked", "decode_xla",
                                  "decode_kernel"])
def test_a_decay_equal_in_every_channel_is_the_scalar_decay(form):
    """The tie between Gated DeltaNet's rule and this one: every form,
    handed a vector decay whose channels are equal, returns what it
    returns for that scalar."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import gated_delta as gd
    q, k, v, g, beta = _kda_inputs(jax.random.PRNGKey(5), 2, 80, h=4,
                                   dv=32, g_max=1.5)
    scalar = g[..., 0]
    vector = jnp.broadcast_to(scalar[..., None], g.shape)
    if form in ("recurrent", "chunked"):
        fn = (gd.gated_delta_recurrent if form == "recurrent"
              else gd.gated_delta_chunked)
        got, want = fn(q, k, v, vector, beta), fn(q, k, v, scalar, beta)
    else:
        state = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 8, 4 * 32))
        entries = jnp.asarray([3, 1])
        live = jnp.asarray([True, True])
        fn = (gd.gdn_decode_xla if form == "decode_xla" else
              lambda *a, **kw: gd.gdn_decode_tpu(*a, interpret=True, **kw))
        at = lambda a: a[:, 0]                               # noqa: E731
        got = fn(at(q), at(k), at(v), at(vector), at(beta), state, entries,
                 live, layer=1)
        want = fn(at(q), at(k), at(v), at(scalar), at(beta), state, entries,
                  live, layer=1)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_decode_kernel_in_the_interpreter_leaves_dead_rows_alone():
    """The kernel under a vector decay against the one-step form, on a
    stacked leaf, two rows in five dead: their entries keep their bits
    and every other layer's too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import gated_delta as gd
    q, k, v, g, beta = (a[:, 0] for a in _kda_inputs(
        jax.random.PRNGKey(2), 5, 1, h=4, dk=16, dv=128))
    state = jax.random.normal(jax.random.PRNGKey(4), (3, 8, 16, 4 * 128))
    entries = jnp.asarray([3, 1, 5, 2, 7])
    live = jnp.asarray([True, False, True, True, False])
    want_o, want_s = gd.gdn_decode_xla(q, k, v, g, beta, state, entries,
                                       live, layer=1)
    o, s = gd.gdn_decode_tpu(q, k, v, g, beta, state, entries, live,
                             layer=1, interpret=True)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(s[1, jnp.asarray([1, 7])],
                                  state[1, jnp.asarray([1, 7])])
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[2], state[2])
    assert not bool(jnp.any(o[jnp.asarray([1, 4])] != 0))
    assert float(jnp.abs(s[1, 3] - state[1, 3]).max()) > 0.1


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_forward_pass_matches_the_reference(parts, reference, scan):
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import GPT
    cfg, params, weights, published = parts
    seq = np.random.default_rng(0).integers(1, 256, 37)
    want = reference.logits(weights, seq, published)
    if not scan:
        # the same weights, a period a subtree
        cfg = dataclasses.replace(cfg, scan_layers=False)
        import flax.linen as nn
        import jax
        p = dict(nn.unbox(params))
        blocks = p.pop("blocks")
        for i in range(cfg.runs[1]):
            p[f"block_{i}"] = jax.tree.map(lambda a: a[i], blocks)
        params = p
    got = GPT(cfg).apply({"params": params}, jnp.asarray(seq)[None])[0]
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("fault", [
    "scalar_decay", "rotated", "silu_gate", "beta_range", "bias_in_gates",
    "no_route_scale", "no_shared_expert", "absent_experts_added"])
def test_each_fault_of_the_reference_moves_the_hidden_states(
        parts, reference, fault):
    """A reference made wrong that read like the right one would prove
    nothing about a program held to it."""
    import jax.numpy as jnp
    import numpy as np
    _, _, weights, published = parts
    seq = np.random.default_rng(1).integers(1, 256, 40)
    right = reference.hidden(weights, seq, published)
    wrong = reference.hidden(weights, seq, published, fault=fault)
    assert float(jnp.mean(reference._row_err(wrong, right))) > 0.05


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Sixteen chips each hold one expert of the tiny layer's sixteen:
    their routed parts, and the shared expert ONCE, are the uncut
    layer's output."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import get_config
    from ray_tpu.models.gpt import Block
    cfg = get_config(PRESET)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, cfg.d_model))
    whole = Block(cfg, kda=True)
    import flax.linen as nn
    p = nn.unbox(whole.init(jax.random.PRNGKey(1), x, None, None)["params"])

    def ffn(cfg, params, with_shared=True):
        """The block's output less its residual stream before the
        feed-forward: ``FFN(Norm(h))``."""
        def without(tree):
            tree = dict(tree)
            zero = jax.tree.map(jnp.zeros_like, tree["shared_mlp"])
            return dict(tree, shared_mlp=zero)
        full = Block(cfg, kda=True).apply({"params": params}, x, None, None)
        if with_shared:
            return full
        return Block(cfg, kda=True).apply(
            {"params": without(params)}, x, None, None)
    uncut = ffn(cfg, p)
    # h (the stream before the feed-forward): the block with nothing
    # routed and no shared expert adds nothing to it
    nothing = dict(p, moe=dict(p["moe"], w_down=jnp.zeros_like(
        p["moe"]["w_down"])))
    h = ffn(cfg, nothing, with_shared=False)
    total = ffn(cfg, nothing) - h                          # shared, once
    for first in range(16):
        share = dataclasses.replace(cfg, moe_experts_held=1,
                                    moe_held_first=first)
        held = dict(p, moe={k: (v[first:first + 1] if k.startswith("w_")
                                else v) for k, v in p["moe"].items()})
        total = total + ffn(share, held, with_shared=False) - h
    np.testing.assert_allclose(total + h, uncut, rtol=2e-4, atol=2e-5)


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw.setdefault("num_slots", 2)
    return LLMEngine(cfg, params, page_size=4, max_seq_len=64,
                     max_prompt_len=32, min_prefill_bucket=8, block_size=4,
                     **kw)


def test_a_latent_pool_of_the_pool_layers_and_entries_of_the_state_layers(
        parts):
    """``layer_types`` beside ``kv_lora_rank``: a latent pool of the 4
    pool layers alone (not 15), state leaves of the 11 KDA layers, and
    the engine's three counts by what a layer HOLDS."""
    cfg, params, _, _ = parts
    eng = _engine(cfg, params)
    try:
        assert (eng._pool_layers, eng._state_layers, eng._latent_layers) \
            == (4, 11, 4)
        cache = eng._cache
        assert cache["kv_pages"].shape == (4, eng.kv_pool_pages, 1, 4, 128)
        assert cache["gdn_state"].shape == (11, eng.state_entries, 8, 4 * 32)
        assert cache["gdn_state"].dtype.name == "float32"
        assert cache["gdn_conv"].shape[:2] == (11, eng.state_entries)
    finally:
        eng.close()


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", None)])
def test_paged_prefill_and_decode_match_the_reference(parts, reference,
                                                      dtype, tol):
    """Two prompts of different lengths in ONE prefill wave at a padded
    bucket (13 and 21 tokens at 32), then decode steps through the state
    entries and the latent pages, against the reference's full forward
    on each whole sequence: logits, as a share of the row's largest, and
    what entry and pages hold: pool and state indices run through head
    run, scan and tail (a layer that read another's leaf would not
    agree).  bfloat16: held to four times the distance of the reference
    with its products' operands rounded to bfloat16, as
    tests/test_olmo_hybrid.py holds its model."""
    import jax.numpy as jnp
    import numpy as np
    cfg, params, weights, published = parts
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    eng = _engine(cfg, params)
    if dtype == "bfloat16":      # the reference reads the served weights
        weights = reference.from_program_params(eng.params)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 256, n) for n in (13 + 8, 21 + 8)]
    n_prompt, bucket, wave = (13, 21), 32, 2
    tokens = np.zeros((wave, bucket), np.int32)
    tables = np.zeros((wave, eng.max_pages), np.int32)
    for r, (seq, n) in enumerate(zip(seqs, n_prompt)):
        tokens[r, :n] = seq[:n]
        tables[r, :8] = 1 + 8 * r + np.arange(8)
    entries = jnp.asarray([2, 5], jnp.int32)
    # (a share of the experts is held: the expert layers' pair rows too)
    logits, cache, pair_rows = eng._last_logits(
        eng.model, eng.params, eng._cache, jnp.asarray(tokens),
        jnp.broadcast_to(jnp.arange(bucket), (wave, bucket)),
        jnp.asarray(n_prompt, jnp.int32), jnp.asarray(tables), entries)
    assert pair_rows.shape == (2,)
    want = [reference.logits(weights, seq, published) for seq in seqs]
    noise = [jnp.abs(reference.logits(weights, seq, published, bits=7)
                     - w).max(-1)
             if dtype == "bfloat16" else jnp.zeros(len(seq))
             for seq, w in zip(seqs, want)]

    def close(got, row, pos):
        scale = float(jnp.abs(want[row][pos]).max())
        assert float(jnp.abs(got - want[row][pos]).max()) < max(
            (tol or 0.0) * scale, 4 * float(noise[row][pos]))
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
    if dtype == "float32":
        # what the entries hold after the last step, layer by layer
        _, more = reference.hidden(weights, seqs[0], published, more=True)
        for layer in range(11):
            state, tail = reference.from_program_state(
                cache["gdn_state"][layer, 2], cache["gdn_conv"][layer, 2],
                cfg.linear_value_heads, cfg.linear_conv_kernel)
            np.testing.assert_allclose(state, more["states"][layer, 1],
                                       rtol=1e-3, atol=1e-4)
            np.testing.assert_allclose(tail, more["tails"][layer, 1],
                                       rtol=1e-3, atol=1e-4)
    eng.close()


def _greedy(reference, weights, published, prompt, n):
    """The reference's own greedy continuation, one forward a token."""
    import jax.numpy as jnp
    import numpy as np
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(reference.logits(
            weights, np.asarray(seq), published)[-1])))
    return seq[len(prompt):]


def test_the_engine_s_greedy_tokens_are_the_reference_s(parts, reference):
    """Through ``submit``: admission, prefill wave, install, decode
    blocks; four requests on two slots, so rows and entries are reused.
    And the counters by what a layer holds: 11 recurrent layers and 4
    latent ones a step, not 15 and 15."""
    import numpy as np
    cfg, params, weights, published = parts
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (13, 21, 9, 17)]
    want = [_greedy(reference, weights, published, p, 6) for p in prompts]
    eng = _engine(cfg, params)
    try:
        async def burst():
            return await asyncio.gather(*[
                eng.submit(p, max_new_tokens=6) for p in prompts])
        got = [r.tokens for r in asyncio.run(burst())]
        assert got == want
        for p, w in zip(prompts, want):
            assert eng.submit(p, max_new_tokens=6).tokens == w
        snap = eng.load_snapshot()
        assert snap["state_entries_in_use"] == 0
        assert snap["free_pages"] == snap["pool_pages"] - 1
        st = eng.stats.snapshot(2)
        assert st["gdn_layer_steps"] == st["steps"] * 11
        assert st["mla_layer_steps"] == st["steps"] * 4
        assert st["pool_layer_steps"] == st["steps"] * 4
        assert 0 < st["gdn_state_rows"] <= 2 * st["gdn_layer_steps"]
        assert 0 < st["mla_context_tokens"]
        # an expert layer's step counts where a live row chose an
        # expert HELD here (4 of 16)
        assert 0 < st["moe_layer_steps"] <= st["steps"] * 14
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "export", "generator"])
def test_what_has_nowhere_to_keep_a_state_refuses_the_model(parts, what):
    """The prefix cache and the prefill handoff carry pages only, and
    ``Generator`` a dense cache: each says which mechanism is missing."""
    import jax.numpy as jnp
    cfg, params, _, _ = parts
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="snapshot of the recurrent"):
            _engine(cfg, params, prefix_cache_pages=8)
    elif what == "generator":
        from ray_tpu.models.generate import Generator
        with pytest.raises(ValueError, match="no dense-cache decode"):
            Generator(cfg, params).generate(jnp.ones((1, 4), jnp.int32),
                                            max_new_tokens=2)
    else:
        eng = _engine(cfg, params)
        try:
            with pytest.raises(ValueError, match="carries KV pages only"):
                eng.export_prefill([1, 2, 3], max_new_tokens=4)
        finally:
            eng.close()
