"""A model whose layers are ONE mixer each, of three classes, at test
size on the CPU against the plain reference
``chipbench/lib/reference_nemotron_h.py`` (ISSUE 44): the pattern
``MEMEMEMEM*E`` (five Mamba-2 layers of 8 heads of 16 in 2 groups, state
16, a convolution of 4 taps with a bias; five LatentMoE layers of 16
squared-ReLU experts of 32 in a latent of 32, top-5 by sigmoid score +
bias, one shared expert of 48; one attention layer of 4 query and 2 KV
heads, no rotation), pages of 4.  Seeded random weights; numbers are
compared, not tokens, but where greedy tokens are the only thing the
engine hands out."""

import asyncio

import pytest

PRESET = "tiny-nemotron-h"
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference reads."""
    letters = {"mamba2": "M", "latent_moe": "E", "attention_only": "*"}
    return {"num_hidden_layers": cfg.n_layers,
            "layer_norm_epsilon": cfg.norm_eps,
            "hybrid_override_pattern": "".join(
                letters[k] for k in cfg.layer_types),
            "mamba_num_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim,
            "ssm_state_size": cfg.ssm_state_size,
            "n_groups": cfg.mamba_groups, "conv_kernel": cfg.mamba_conv_kernel,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_experts_per_tok": cfg.moe_top_k,
            "routed_scaling_factor": cfg.moe_route_scale,
            "n_routed_experts": cfg.experts_here,
            "experts_held_first": cfg.moe_held_first}


@pytest.fixture(scope="module")
def parts():
    """``(cfg, scanned params, the reference's weights, its config)``."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_nemotron_h as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(PRESET)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, params, ref.from_program_params(params), _published(cfg)


def test_num_params_are_the_issue_s_counts(parts):
    """The published model's counts, layer class by layer class, the
    one-period cut's, and the test preset's against its own tree."""
    import dataclasses
    import jax
    from ray_tpu.models import get_config
    full = get_config("nemotron-3-super-120b-a12b")
    assert full.layer_params("mamba2") == 109_640_064
    assert full.layer_params("latent_moe") == 54_530_560 + 512 * 5_505_024
    assert full.layer_params("attention_only") == 35_655_680
    assert full.num_params() == (
        40 * 109_640_064 + 40 * (54_530_560 + 512 * 5_505_024)
        + 8 * 35_655_680 + 2 * 131_072 * 4096 + 4096)
    assert 120.6e9 < full.num_params() < 120.8e9
    cut = dataclasses.replace(
        full, n_layers=11, layer_types=full.layer_types[27:38],
        moe_experts_held=128, vocab_size=32768)
    assert cut.num_params() == 4_379_724_160 + 268_435_456 + 4096
    cfg, params, _, _ = parts
    assert cfg.num_params() == sum(
        leaf.size for leaf in jax.tree.leaves(params))


def test_the_cut_pattern_has_the_published_one_s_classes_and_ratio():
    from ray_tpu.models import get_config
    from ray_tpu.models.configs import pattern_layer_types
    full = get_config("nemotron-3-super-120b-a12b")
    assert full.layer_types == pattern_layer_types(PUBLISHED_PATTERN)
    cut = pattern_layer_types(PUBLISHED_PATTERN[27:38])
    assert "".join(PUBLISHED_PATTERN[27:38]) == "MEMEMEMEM*E"
    assert cut == get_config(PRESET).layer_types
    count = lambda kinds: [kinds.count(k) for k in (      # noqa: E731
        "mamba2", "latent_moe", "attention_only")]
    assert count(full.layer_types) == [40, 40, 8]
    assert count(cut) == [5, 5, 1]            # 40 : 40 : 8 is 5 : 5 : 1
    assert set(cut) == set(full.layer_types)
    # not periodic: the whole pattern is one run; the cut is one period
    assert len(full.period) == 88 and get_config(PRESET).period == cut


def test_forward_pass_matches_the_reference(parts):
    """A whole forward (the chunked recurrence, plain attention, the
    sorted expert products) against the reference's token-by-token one:
    logits."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_nemotron_h as ref
    from ray_tpu.models import GPT
    cfg, params, weights, published = parts
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 29), 0, 256)
    got = GPT(cfg).apply({"params": params}, tokens)
    for row in range(2):
        want = ref.logits(weights, tokens[row], published)
        assert float(jnp.abs(got[row] - want).max()) < 2e-4 * float(
            jnp.abs(want).max())


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw = {"num_slots": 2, "page_size": 4, "max_seq_len": 64,
          "max_prompt_len": 32, "block_size": 4, "min_prefill_bucket": 8,
          **kw}
    return LLMEngine(cfg, params, **kw)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", 2e-2)])
def test_paged_prefill_and_decode_match_the_reference(parts, dtype, tol):
    """Two prompts of different lengths in ONE prefill wave at a padded
    bucket (13 and 21 tokens at 32), then decode steps through the state
    entries and the pages, a dead row between the two, against the
    reference's full forward on each whole sequence: logits, as a share
    of the row's largest; and what the engine's entries hold afterwards
    against the reference's state and convolution tail after the same
    tokens.  bfloat16 (weights, activations and the tail in bfloat16,
    state in float32): at this test's width a rounding can flip the
    router's 5th expert of 16, a jump of a third of the row that the
    REFERENCE with its products' operands rounded to bfloat16 makes too
    (at positions of its own), so the median position is held to 8% and
    four positions in five to four times that reference's distance at
    the same position."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_nemotron_h as ref
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

    errs, inside = [], []

    def close(got, row, pos):
        scale = float(jnp.abs(want[row][pos]).max())
        err = float(jnp.abs(got - want[row][pos]).max())
        errs.append(err / scale)
        inside.append(err < max(tol * scale, 4 * float(noise[row][pos])))
    for r, n in enumerate(n_prompt):
        close(logits[r], r, n - 1)
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
            close(out[row, 0], r, n_prompt[r] + step)
    if dtype == "float32":
        assert all(inside), errs
    else:
        assert np.median(errs) < 8e-2 and np.mean(inside) >= 0.8, errs
    # the dead row moved nothing
    assert bool((cache["ssm_state"][:, 0] == scratch).all())
    for r, entry in enumerate((2, 5)):
        _, left = ref.hidden(weights, seqs[r], published, states=True)
        for layer, (state, tail) in enumerate(left):
            got = ref.from_program_state(
                cache["ssm_state"][layer, entry],
                cache["ssm_conv"][layer, entry], cfg.mamba_heads,
                cfg.mamba_conv_kernel)
            for a, b in zip(got, (state, tail)):
                err = float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                            / jnp.linalg.norm(b))
                # bfloat16: the first layer reads rounded inputs only;
                # the later ones also what flipped above them (a third
                # to a half of a row at this width): not held
                if dtype == "float32" or layer == 0:
                    assert err < (1e-4 if dtype == "float32" else 3e-2)
    eng.close()


def _greedy(weights, published, prompt, n):
    """The reference's own greedy continuation, one forward a token."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_nemotron_h as ref
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(ref.logits(
            weights, np.asarray(seq), published)[-1])))
    return seq[len(prompt):]


def test_the_engine_s_greedy_tokens_are_the_reference_s(parts):
    """Through ``submit``: admission, prefill wave, install, decode
    blocks; four requests on two slots, so rows and entries are reused
    and requests wait prefilled for a slot; and the counters of the
    state-space and expert layers."""
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
        for p, w in zip(prompts, want):
            assert eng.submit(p, max_new_tokens=6).tokens == w
        snap = eng.load_snapshot()
        assert snap["state_entries_in_use"] == 0
        assert snap["free_pages"] == snap["pool_pages"] - 1
        st = eng.stats.snapshot(2)
        # the state-row counters count every recurrent layer class
        assert st["gdn_layer_steps"] == st["steps"] * 5   # 5 Mamba layers
        assert 0 < st["gdn_state_rows"] <= 2 * st["gdn_layer_steps"]
        assert 0 < st["moe_layer_steps"] <= st["steps"] * 5
        assert st["moe_experts_touched"] <= 10 * st["moe_layer_steps"]
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["prefix_cache", "export", "import",
                                  "generator"])
def test_what_has_no_recurrent_state_refuses_the_model(parts, what):
    """The prefix cache, the prefill handoff and ``Generator`` go on
    refusing a model with recurrent layers, each by the missing
    mechanism's name."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import Generator
    from ray_tpu.serve.llm_engine import PrefillHandoff
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
            if what == "export":
                eng.export_prefill([1, 2, 3], max_new_tokens=4)
            else:
                eng.import_prefill(PrefillHandoff(
                    kv=np.zeros((1, 1, 2, 4, 32), np.float32), page_size=4,
                    npages=1, prompt_len=3, first_token=1,
                    max_new_tokens=4, temperature=0.0, eos_id=None))
    finally:
        eng.close()


def _pairs_loop(x, gates, experts, w_gate, w_up, w_down, fn, live=None):
    """``dropless_experts`` as a loop over pairs, in numpy float64."""
    import numpy as np
    x, gates, w_up, w_down = (np.asarray(a, np.float64)
                              for a in (x, gates, w_up, w_down))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        if live is not None and not live[t]:
            continue
        for j, e in enumerate(np.asarray(experts)[t]):
            if e >= w_up.shape[0]:
                continue                    # some other chip's expert
            h = fn(x[t] @ w_up[e])
            if w_gate is not None:
                h = fn(x[t] @ np.asarray(w_gate, np.float64)[e]) * (
                    x[t] @ w_up[e])
            out[t] += gates[t, j] * (h @ w_down[e])
    return out


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("sorted_pairs", [False, True])
def test_gated_and_ungated_experts_in_both_formulations(monkeypatch, gated,
                                                        sorted_pairs):
    """``dropless_experts`` with and without ``w_gate``, as the
    all-experts product and as the sort + ``ragged_dot``, against a loop
    over pairs; in both a row that is not live comes out zero, and a
    pair whose expert is held elsewhere adds nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import moe
    n, d, f, e, k = 12, 16, 24, 6, 3
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (n, d))
    w_gate = jax.random.normal(ks[1], (e, d, f)) / 4 if gated else None
    w_up = jax.random.normal(ks[2], (e, d, f)) / 4
    w_down = jax.random.normal(ks[3], (e, f, d)) / 4
    gates = jax.random.uniform(ks[4], (n, k))
    # ids up to e: e is "held elsewhere"
    experts = jax.random.randint(ks[5], (n, k), 0, e + 1)
    gates = jnp.where(experts < e, gates, 0.0)
    live = np.arange(n) % 4 != 1
    if sorted_pairs:
        monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", 8)
    act = "silu" if gated else "relu2"
    fn = (lambda h: h / (1 + np.exp(-h))) if gated else (
        lambda h: np.maximum(h, 0) ** 2)
    for rows in (None, live):
        got, _ = moe.dropless_experts(
            x, gates, experts, w_gate, w_up, w_down, act=act, partial=True,
            live=None if rows is None else jnp.asarray(rows))
        want = _pairs_loop(x, gates, experts, w_gate, w_up, w_down, fn, rows)
        np.testing.assert_allclose(got, want, atol=2e-4)
        if rows is not None:
            assert not np.asarray(got)[~rows].any()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One of four chips holds a quarter of the routed experts and the
    router, the projections and the shared expert whole: the four
    shares' routed parts, plus the shared expert counted once, are the
    uncut ``LatentMoE`` layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.moe import LatentMoE
    kw = dict(d_model=32, latent=16, n_experts=16, d_ff=24, shared_d_ff=40,
              top_k=5, dtype=jnp.float32, route_scale=5.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32))
    whole = LatentMoE(**kw)
    import flax.linen as nn
    params = nn.unbox(whole.init(jax.random.PRNGKey(1), x)["params"])
    want = whole.apply({"params": params}, x)
    shared = jnp.square(jax.nn.relu(
        x @ params["shared_up"]["kernel"])) @ params["shared_down"]["kernel"]
    total = shared
    for share in range(4):
        lo = 4 * share
        mine = jax.tree.map(lambda a: a, params)
        mine["moe"] = dict(mine["moe"],
                           w_up=params["moe"]["w_up"][lo:lo + 4],
                           w_down=params["moe"]["w_down"][lo:lo + 4])
        out = LatentMoE(**kw, held=4, held_first=lo).apply(
            {"params": mine}, x)
        total = total + out - shared
        # a share alone is NOT the layer: the sum means something
        assert float(jnp.abs(out - want).max()) > 1e-3
    np.testing.assert_allclose(total, want, atol=1e-4)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_expert_layer_sows_what_its_router_read(dtype):
    """``LatentMoE`` sows ``router_in``, its input behind the barrier
    (the one buffer the router, the latent projection and the shared
    expert read), and the router's logits are the float32 products of
    exactly that array, whatever the compute dtype: what
    ``chipbench/lib/replica_ssm.py`` holds the router to on the chip."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.moe import LatentMoE
    layer = LatentMoE(d_model=32, latent=16, n_experts=16, d_ff=24,
                      shared_d_ff=40, top_k=5, dtype=jnp.dtype(dtype),
                      param_dtype=jnp.dtype(dtype), route_scale=5.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32)).astype(dtype)
    params = nn.unbox(layer.init(jax.random.PRNGKey(1), x)["params"])
    out, mut = jax.jit(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["intermediates"],
        capture_intermediates=lambda m, name: name == "router_logits"))(
            params, x)
    sown = mut["intermediates"]
    (z,) = sown["router_in"]
    (r,) = sown["moe"]["router_logits"]
    assert z.dtype == x.dtype and r.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(z, np.float32),
                                  np.asarray(x, np.float32))
    want = np.asarray(z, np.float64) @ np.asarray(
        params["moe"]["router"]["kernel"], np.float64)
    np.testing.assert_allclose(r, want, rtol=0, atol=2e-6 * np.abs(want).max())
    # a caller that does not ask for them pays nothing
    assert layer.apply({"params": params}, x).shape == out.shape
