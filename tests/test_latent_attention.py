"""Latent attention, sigmoid-routed experts held in part, shared experts
and a leading dense layer (ISSUE 37), at test size on the CPU against
the plain reference ``chipbench/lib/reference_kanana2.py``: the preset
``tiny-kanana`` (4 heads of 16 + 8, latent 32, values 16; one dense
layer, then three of 8 experts top-3 with two shared), pages of 4.
Seeded random weights; numbers are compared, not tokens, but where
greedy tokens are the only thing the engine hands out."""

import asyncio
import dataclasses
import functools

import pytest

PRESET = "tiny-kanana"


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference reads."""
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "num_experts_per_tok": cfg.moe_top_k,
            "routed_scaling_factor": cfg.moe_route_scale,
            "n_routed_experts": cfg.experts_here,
            "experts_held_first": cfg.moe_held_first,
            "first_k_dense_replace": cfg.first_dense_layers}


def _parts(**overrides):
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_kanana2 as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(PRESET, **overrides)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    stacked = params
    if not cfg.scan_layers:        # the reference reads stacked layers
        import flax.linen as nn
        p = nn.unbox(params)
        n = cfg.n_layers - cfg.first_dense_layers
        stacked = dict(
            p, dense_blocks=jax.tree.map(lambda a: a[None],
                                         p["dense_block_0"]),
            blocks=jax.tree.map(lambda *a: jnp.stack(a),
                                *[p[f"block_{i}"] for i in range(n)]))
    return cfg, params, ref.from_program_params(stacked), _published(cfg)


@pytest.fixture(scope="module")
def parts():
    """``(cfg, scanned params, the reference's weights, its config)``."""
    return _parts()


def test_the_preset_is_of_the_published_shape():
    from ray_tpu.models import get_config
    cfg = get_config("kanana-2-30b-a3b")
    assert round(cfg.num_params() / 1e9, 2) == 30.67
    assert (cfg.cache_row_width, cfg.cache_kv_heads, cfg.rope_dim) == (
        640, 1, 64)
    # one of eight chips' share at 16 layers: ISSUE 37's 4.53 GB of bf16
    cut = get_config("kanana-2-30b-a3b", n_layers=16, moe_experts_held=16)
    assert cut.experts_here == 16
    assert round(cut.num_params() * 2 / 1e9, 2) == 4.53
    # a token's cache row against keys and values of every head
    assert 2 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) == 1152
    # a model without these fields counts and caches what it always did
    tiny = get_config("tiny")
    assert tiny.num_params() == 115_008
    assert (tiny.cache_row_width, tiny.cache_kv_heads) == (32, 4)


@pytest.mark.parametrize("held", [None, (3, 2)], ids=["all", "held-3-from-2"])
@pytest.mark.parametrize("pairs_max", [1 << 20, 0],
                         ids=["all-experts", "grouped"])
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_forward_pass_matches_the_reference(monkeypatch, scan, pairs_max,
                                            held):
    """``GPT`` (dense prefix stack + scanned expert stack, or unrolled)
    against the reference's logits, both expert formulations; with every
    expert held, and with 3 of 8 from the third on (what the others
    would add left out on both sides)."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_kanana2 as ref
    from ray_tpu.models import GPT
    from ray_tpu.ops import moe
    over = {"scan_layers": scan}
    if held:
        over.update(moe_experts_held=held[0], moe_held_first=held[1])
    cfg, params, weights, published = _parts(**over)
    monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", pairs_max)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 33))
    got = GPT(cfg).apply({"params": params}, jnp.asarray(tokens))
    for row in range(2):
        want = ref.logits(weights, tokens[row], published)
        np.testing.assert_allclose(got[row], want, atol=2e-4, rtol=1e-4)
    assert np.abs(np.asarray(want)).max() > 0.1


def test_the_router_scores_choose_with_the_bias_and_gate_without_it():
    """A hand-worked case: four experts, two a token.  Scores sigmoid(0,
    ln 3, -ln 3, ln 1.5) = 0.5, 0.75, 0.25, 0.6; the bias lifts expert 2
    past expert 3 and 0; the gates are the chosen SCORES renormalised,
    times 2.448: the bias chose and is gone."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.moe import route_top_k
    logits = jnp.log(jnp.asarray([[1.0, 3.0, 1 / 3, 1.5]]))
    bias = jnp.asarray([0.0, 0.0, 0.4, 0.0])
    gates, idx = route_top_k(logits, 2, scoring="sigmoid", bias=bias,
                             scale=2.448)
    assert idx.tolist() == [[1, 2]]              # 0.75, 0.25 + 0.4
    np.testing.assert_allclose(
        gates, [[0.75 / 1.0 * 2.448, 0.25 / 1.0 * 2.448]], rtol=1e-6)
    # without the bias: experts 1 and 3
    gates, idx = route_top_k(logits, 2, scoring="sigmoid", scale=2.448)
    assert idx.tolist() == [[1, 3]]
    np.testing.assert_allclose(
        gates, [[0.75 / 1.35 * 2.448, 0.6 / 1.35 * 2.448]], rtol=1e-6)
    # the softmax router is what it was
    gates, idx = route_top_k(logits, 2)
    np.testing.assert_allclose(gates, [[3 / 4.5, 1.5 / 4.5]], rtol=1e-6)


@pytest.mark.parametrize("tokens", [5, 200], ids=["small-pairs", "grouped"])
@pytest.mark.parametrize("preset, layer", [(PRESET, None),
                                           ("tiny-k-exaone", 1)],
                         ids=["kanana", "k-exaone"])
def test_the_eight_shares_add_up_to_the_uncut_layer(tokens, preset, layer):
    """Expert parallelism as the chips see it: 8 chips hold one expert
    each of a layer's 8.  Each routes over all 8 and computes its own
    pairs; the routed parts of the 8 shares, plus the attention and the
    shared expert counted ONCE, are the uncut layer's output.  (The
    attention and the shared expert are in every share's block; ``base``
    is the block with the routed sum's down-projections zeroed.)  For
    Kanana's block (latent attention, two shared experts) and K-EXAONE's
    (GQA under a window, one shared; its blocks are told their layer)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import get_config
    from ray_tpu.models.gpt import Block
    from ray_tpu.ops.layers import rope_frequencies
    cfg = get_config(preset, scan_layers=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, tokens // 2 + 1, 64))
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq_len, cfg.rope_theta)
    at = {"layer": layer}
    params = nn.unbox(Block(cfg).init(jax.random.PRNGKey(1), x, cos, sin,
                                      **at)["params"])
    uncut = Block(cfg).apply({"params": params}, x, cos, sin, **at)
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if "['moe']['w_down']" in
        jax.tree_util.keystr(path) else a, params)
    base = Block(cfg).apply({"params": zeroed}, x, cos, sin, **at)
    routed, counted = 0.0, 0
    for chip in range(8):
        share = dataclasses.replace(cfg, moe_experts_held=1,
                                    moe_held_first=chip)
        mine = jax.tree_util.tree_map_with_path(
            lambda path, a: a[chip:chip + 1] if "['moe']['w_" in
            jax.tree_util.keystr(path) else a, params)
        out, mut = Block(share).apply({"params": mine}, x, cos, sin,
                                      mutable=["intermediates"], **at)
        routed = routed + (out - base)
        idx = np.asarray(mut["intermediates"]["moe"]["expert_idx"][0])
        counted += int((idx == 0).sum())       # pairs computed here
    assert counted == x.shape[0] * x.shape[1] * cfg.moe_top_k
    np.testing.assert_allclose(base + routed, uncut, atol=2e-5, rtol=1e-5)
    assert float(jnp.abs(uncut - base).max()) > 1e-2


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw = {"num_slots": 2, "page_size": 4, "max_seq_len": 64,
          "max_prompt_len": 32, "block_size": 4, "min_prefill_bucket": 8,
          **kw}
    return LLMEngine(cfg, params, **kw)


@pytest.mark.parametrize("n_prompt", [(13, 21), (32, 16)],
                         ids=["padded", "fills-its-bucket"])
def test_paged_prefill_and_decode_match_the_full_forward(parts, n_prompt):
    """Two prompts in ONE prefill wave at bucket 32 (EXPANDED attention,
    latent rows written to the pool), then decode steps ABSORBED through
    the pool in the engine's shape (3 rows, row 1 dead), against the
    reference's full forward on each whole sequence: logits."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_kanana2 as ref
    cfg, params, weights, published = parts
    eng = _engine(cfg, params)
    assert eng._pool_tail == (1, 4, 128)     # one KV head, 40 -> 128 wide
    rng = np.random.default_rng(0)
    seqs = [rng.integers(1, 256, n + 8) for n in n_prompt]
    bucket, wave = 32, 2
    tokens = np.zeros((wave, bucket), np.int32)
    tables = np.zeros((wave, eng.max_pages), np.int32)
    for r, (seq, n) in enumerate(zip(seqs, n_prompt)):
        tokens[r, :n] = seq[:n]
        tables[r, :10] = 1 + 10 * r + np.arange(10)
    logits, cache = eng._last_logits(
        eng.model, eng.params, eng._cache, jnp.asarray(tokens),
        jnp.broadcast_to(jnp.arange(bucket), (wave, bucket)),
        jnp.asarray(n_prompt, jnp.int32), jnp.asarray(tables))
    want = [ref.logits(weights, seq, published) for seq in seqs]
    for r, n in enumerate(n_prompt):
        np.testing.assert_allclose(logits[r], want[r][n - 1], atol=2e-4,
                                   rtol=1e-4)
    rows = eng._rows
    tabs = np.zeros((rows, eng.max_pages), np.int32)
    tabs[0], tabs[2] = tables[0], tables[1]
    for step in range(8):
        toks = np.zeros((rows, 1), np.int32)
        poss = np.zeros((rows, 1), np.int32)
        for row, r in ((0, 0), (2, 1)):
            toks[row, 0] = seqs[r][n_prompt[r] + step]
            poss[row, 0] = n_prompt[r] + step
        out, mut = eng.model.apply(
            {"params": eng.params, "cache": cache}, jnp.asarray(toks),
            jnp.asarray(poss), block_tables=jnp.asarray(tabs),
            mutable=["cache"])
        cache = mut["cache"]
        for row, r in ((0, 0), (2, 1)):
            np.testing.assert_allclose(
                out[row, 0], want[r][n_prompt[r] + step], atol=2e-4,
                rtol=1e-4)
    eng.close()


def test_absorbed_attention_is_expanded_attention():
    """``LatentAttention`` alone: position 20's output by the plain
    (expanded) path over the whole sequence, and by one ABSORBED decode
    step over the latent rows that a paged prefill of the first 20
    positions left in the pool: the same numbers, and the pool's rows
    are ``[c | k_rope | zeros]``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import get_config
    from ray_tpu.models.gpt import LatentAttention
    from ray_tpu.ops.layers import rope_frequencies
    cfg = get_config(PRESET)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 21, 64))
    cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq_len, cfg.rope_theta)
    plain = LatentAttention(cfg)
    params = nn.unbox(plain.init(jax.random.PRNGKey(1), x, cos, sin)
                      ["params"])
    want = plain.apply({"params": params}, x, cos, sin)
    paged = LatentAttention(cfg, decode=True)
    pool = jnp.zeros((2, 9, 1, 4, cfg.cache_row_width))
    table = jnp.arange(1, 7)[None]
    pos = jnp.arange(21)[None]
    out, pool = paged.apply({"params": params}, x[:, :20], cos, sin,
                            pos[:, :20], table, pool, 1)
    np.testing.assert_allclose(out, want[:, :20], atol=1e-5)
    out, pool = paged.apply({"params": params}, x[:, 20:], cos, sin,
                            pos[:, 20:], table, pool, 1)
    np.testing.assert_allclose(out, want[:, 20:], atol=1e-5)
    rows = np.asarray(pool[1, 1:7, 0]).reshape(24, -1)
    assert np.abs(rows[:21, :40]).min() > 0 and not rows[:, 40:].any()
    assert not rows[21:].any() and not np.asarray(pool[0]).any()


def _latent_case(rs, dtype):
    import jax.numpy as jnp
    pool = jnp.asarray(rs.randn(3, 13, 1, 16, 256), dtype)
    q = jnp.asarray(rs.randn(4, 8, 256), dtype)
    tables = jnp.asarray(rs.permutation(12).reshape(3, 4)[[0, 1, 2, 0]] + 1,
                         jnp.int32)
    return pool, q, tables, jnp.asarray([5, 33, 64, 49], jnp.int32)


def test_the_latent_kernel_in_the_interpreter_is_the_xla_oracle(monkeypatch):
    """``paged_attention_decode`` on latent pages (one KV head, 8 query
    heads, scores over the whole 256-wide row, values its first 128)
    under jax's TPU interpreter against ``paged_attention_xla``; a dead
    row comes back zero and its pages are not read (they hold NaN)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.paged_attention import (paged_attention_tpu,
                                             paged_attention_xla)
    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=pltpu.InterpretParams()))
    pool, q, tables, lengths = _latent_case(np.random.RandomState(3),
                                            jnp.bfloat16)
    live = jnp.asarray([True, True, False, True])
    # row 2's pages: nothing may be read from them
    pool = pool.at[1, tables[2]].set(jnp.nan)
    tables = tables.at[3].set(tables[1])
    kw = dict(layer=jnp.int32(1), live=live, sm_scale=0.1, v_width=128)
    got = paged_attention_tpu(q, pool, tables, lengths, **kw)
    want = paged_attention_xla(q, pool, tables, lengths, **kw)
    assert got.shape == (4, 8, 128)
    assert not np.asarray(got[2], np.float32).any()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    assert np.abs(np.asarray(want[0], np.float32)).max() > 0.1


def test_the_xla_oracle_on_latent_rows_is_plain_attention():
    """``paged_attention_xla(v_width=)``: keys the whole row, values
    its first ``v_width``, against softmax attention written out."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import paged_attention_xla
    pool, q, tables, lengths = _latent_case(np.random.RandomState(4),
                                            jnp.float32)
    got = paged_attention_xla(q, pool, tables, lengths, layer=2,
                              sm_scale=0.1, v_width=128)
    for row in range(4):
        n = int(lengths[row])
        rows = np.asarray(pool[2, tables[row], 0]).reshape(64, 256)[:n]
        p = jax.nn.softmax(np.asarray(q[row]) @ rows.T * 0.1, -1)
        np.testing.assert_allclose(got[row], p @ rows[:, :128], atol=1e-4)


@pytest.mark.parametrize("t", [1, 8, 6], ids=["decode", "pages", "gcd-2"])
def test_write_kv_pages_on_a_latent_row(t):
    """The writer is indifferent to what a row is: ``[rows, T, 1, 40]``
    latent rows land at their pages' offsets of their layer, nothing
    else moves."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import gather_kv_pages, write_kv_pages
    rs = np.random.RandomState(t)
    pool = jnp.asarray(rs.randn(2, 9, 1, 4, 40), jnp.float32)
    tables = jnp.asarray([[3, 1, 7, 5], [2, 8, 4, 6]], jnp.int32)
    start = np.asarray([5 if t == 1 else 4, 2 if t != 8 else 8])
    positions = jnp.asarray(start[:, None] + np.arange(t))
    new = jnp.asarray(rs.randn(2, t, 1, 40), jnp.float32)
    out = write_kv_pages(pool, new, tables, positions, layer=1)
    np.testing.assert_array_equal(out[0], pool[0])
    span = np.asarray(gather_kv_pages(out, tables, layer=1))
    before = np.asarray(gather_kv_pages(pool, tables, layer=1))
    for r in range(2):
        lo = int(start[r])
        np.testing.assert_array_equal(span[r, lo:lo + t], new[r])
        np.testing.assert_array_equal(span[r, :lo], before[r, :lo])
        np.testing.assert_array_equal(span[r, lo + t:], before[r, lo + t:])


@pytest.mark.parametrize("blocks", [2, 4, 8])
def test_a_row_s_scores_past_the_budget_run_in_query_blocks(monkeypatch,
                                                            blocks):
    """``models/gpt.py _prefill_attend`` where ONE row's float32 scores
    pass ``_PREFILL_SCORE_BYTES`` (32 heads at 8,192 tokens are 8.6 GB):
    blocks of the row's queries, each against the keys up to its causal
    edge, give the unblocked result; keys and values of different
    widths, as expanded latent attention has them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import gpt
    from ray_tpu.ops.attention import xla_attention
    rows, t, h = 3, 32, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(key, (rows, t, h, 12)) for key in ks[:2])
    v = jax.random.normal(ks[2], (rows, t, h, 8))
    whole = xla_attention(q, k, v, causal=True, sm_scale=0.3)
    np.testing.assert_array_equal(
        gpt._prefill_attend(q, k, v, sm_scale=0.3), whole)
    # room for 1/blocks of a row's scores and not a byte more
    monkeypatch.setattr(gpt, "_PREFILL_SCORE_BYTES", 4 * h * t * t // blocks)
    fn = jax.jit(functools.partial(gpt._prefill_attend, sm_scale=0.3))
    lowered = fn.lower(q, k, v).as_text()
    bq = t // blocks
    # the first block sees bq keys, the last all of them; no [t, t] scores
    assert f"{h}x{bq}x{bq}x" in lowered and f"{h}x{bq}x{t}x" in lowered
    assert f"{h}x{t}x{t}x" not in lowered
    np.testing.assert_allclose(fn(q, k, v), whole, rtol=1e-6, atol=1e-6)


def test_on_the_tpu_such_a_row_runs_the_flash_kernel(monkeypatch):
    """Where the backend answers "tpu", a row past the budget makes no
    scores at all: the flash kernel (interpreted here), values narrower
    than the keys zero-padded to their width and cut back."""
    import jax
    import numpy as np
    import importlib
    from ray_tpu.models import gpt
    # (the package re-exports the function ``attention`` under this name)
    attn = importlib.import_module("ray_tpu.ops.attention")
    xla_attention = attn.xla_attention
    rows, t, h = 2, 256, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(key, (rows, t, h, 24)) for key in ks[:2])
    v = jax.random.normal(ks[2], (rows, t, h, 16))
    whole = xla_attention(q, k, v, causal=True, sm_scale=0.2)
    monkeypatch.setattr(attn, "resolve_impl", lambda impl: "flash")
    monkeypatch.setattr(gpt, "_PREFILL_SCORE_BYTES", 4 * h * t * t - 1)
    lowered = jax.jit(lambda *a: gpt._prefill_attend(*a, sm_scale=0.2)
                      ).lower(q, k, v).as_text()
    assert f"{h}x{t}x{t}x" not in lowered             # no [h, t, t] scores
    got = gpt._prefill_attend(q, k, v, sm_scale=0.2)
    assert got.shape == whole.shape
    np.testing.assert_allclose(got, whole, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths", [(23,), (13, 40), (64, 5)],
                         ids=["one-row", "two-rows", "fills-its-bucket"])
def test_a_wave_of_several_chunks_is_the_one_pass_on_real_positions(
        lengths, prefill_chunk):
    """Latent attention with 3 of 8 experts held, a dense layer first: a
    wave at bucket 64 in chunks of 16 (``Block._chunked``: the sections
    before and after the attention a chunk at a time, as many as the
    longest prompt needs) against the same wave in one pass."""
    from conftest import assert_chunked_wave_is_the_whole_wave
    cfg, params, _, _ = _parts(moe_experts_held=3, moe_held_first=2)
    eng = _engine(cfg, params, max_seq_len=128, max_prompt_len=64)
    try:
        assert_chunked_wave_is_the_whole_wave(eng, lengths, 64, 16,
                                              prefill_chunk)
    finally:
        eng.close()


def test_a_wave_of_one_chunk_is_the_program_it_was(parts, prefill_chunk):
    """Told the real lengths or not, a wave of one chunk or less traces
    to the same program, equation for equation (the cells whose prompts
    fit a chunk run what they ran); a wave of several chunks does not:
    it loops over them."""
    from conftest import assert_only_several_chunks_loop
    cfg, params, _, _ = parts
    eng = _engine(cfg, params)
    try:
        assert_only_several_chunks_loop(eng, prefill_chunk)
    finally:
        eng.close()


def test_the_engine_counts_the_chunks_a_wave_ran(parts, prefill_chunk):
    """Through ``submit`` with chunks of 8: a prompt of 17 runs at bucket
    32 and computes 24 positions, one of 30 all 32, one of 7 its bucket
    of 8 (one chunk: as ever); ``prefill_padded_tokens`` counts what was
    computed, never less than the real tokens, and the greedy tokens
    are lone generation's: decode reads pages whose rows past the
    prompt were never computed."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import Generator
    cfg, params, _, _ = parts
    prefill_chunk(8)
    eng = _engine(cfg, params)
    lone = Generator(cfg, params)
    rng = np.random.default_rng(5)
    try:
        padded = prompt = 0
        for n, computed in ((17, 24), (30, 32), (7, 8)):
            p = [int(t) for t in rng.integers(1, 256, n)]
            want = lone.generate(jnp.asarray([p], jnp.int32),
                                 max_new_tokens=6, temperature=0.0)[0]
            assert eng.submit(p, max_new_tokens=6).tokens == [
                int(t) for t in want]
            padded, prompt = padded + computed, prompt + n
            st = eng.stats.snapshot(2)
            assert st["prefill_padded_tokens"] == padded
            assert st["prefill_prompt_tokens"] == prompt
    finally:
        eng.close()


def test_the_engine_s_greedy_tokens_are_lone_generation_s(parts):
    """Through ``submit``: admission, prefill wave, install, decode
    blocks; four requests on two slots against ``Generator`` (a dense
    cache of latent rows, every one expanded a step) one prompt at a
    time; and the counters of the latent kernel's work."""
    import jax.numpy as jnp
    from ray_tpu.models.generate import Generator
    cfg, params, _, _ = parts
    import numpy as np
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(1, 256, n)]
               for n in (13, 21, 9, 17)]
    lone = Generator(dataclasses.replace(cfg, max_seq_len=64), params)
    want = [lone.generate(jnp.asarray([p]), max_new_tokens=6,
                          temperature=0.0)[0].tolist() for p in prompts]
    eng = _engine(cfg, params)
    try:
        async def burst():
            return await asyncio.gather(*[
                eng.submit(p, max_new_tokens=6) for p in prompts])
        assert [r.tokens for r in asyncio.run(burst())] == want
        st = eng.stats.snapshot(2)
        assert st["mla_layer_steps"] == st["steps"] * 4      # 4 layers
        # request of prompt n: 5 decode steps reading n+1 .. n+5 rows
        assert st["mla_context_tokens"] == 4 * sum(
            5 * n + 15 for n in (13, 21, 9, 17))
        # the load counts the experts HELD (all 8 here), 3 expert layers
        assert st["moe_layer_steps"] > 0
        assert st["moe_experts_touched"] <= 8 * st["moe_layer_steps"]
        snap = eng.load_snapshot()
        assert snap["free_pages"] == snap["pool_pages"] - 1
    finally:
        eng.close()


def test_the_engine_serves_a_share_of_the_experts():
    """3 of 8 experts held from the third on: the engine's greedy tokens
    are the reference's for that share, and a layer step cannot touch
    more experts than are held."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_kanana2 as ref
    cfg, params, weights, published = _parts(moe_experts_held=3,
                                             moe_held_first=2)
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 11)]
    seq = list(prompt)
    for _ in range(5):
        seq.append(int(jnp.argmax(ref.logits(weights, np.asarray(seq),
                                             published)[-1])))
    eng = _engine(cfg, params)
    try:
        assert eng.submit(prompt, max_new_tokens=5).tokens == seq[11:]
        st = eng.stats.snapshot(2)
        assert 0 < st["moe_experts_touched"] <= 3 * st["moe_layer_steps"]
    finally:
        eng.close()


def test_a_prefill_handoff_carries_latent_pages(parts):
    """Pages are pages: ``export_prefill`` on one engine, ``import_prefill``
    on another, and the answer is the one a single engine gives."""
    cfg, params, _, _ = parts
    prompt = list(range(3, 20))
    a, b = _engine(cfg, params), _engine(cfg, params)
    try:
        want = a.submit(prompt, max_new_tokens=6).tokens
        handoff = a.export_prefill(prompt, max_new_tokens=6)
        assert handoff.kv.shape == (4, 5, 1, 4, 128)     # 17 tokens
        assert b.import_prefill(handoff).tokens == want
    finally:
        a.close()
        b.close()


def test_the_prefix_cache_refuses_a_latent_pool(parts):
    """No silent wrong answer: a hit's suffix prefill would attend over
    its own keys only; the refusal names the path that is missing."""
    cfg, params, _, _ = parts
    with pytest.raises(ValueError, match="gather the cached prefix's "
                                         "latent rows"):
        _engine(cfg, params, prefix_cache_pages=8)


def test_a_wave_carries_no_more_tokens_than_it_may():
    """``prefill_wave_tokens``: five prompts of bucket 16 in one burst
    under a cap of 32 tokens go as waves of 2, 2 and 1 (a wave's
    activations are its tokens'), and answer as they do uncapped."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT, get_config
    cfg = get_config("tiny")
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [[3 + i] * (9 + i) for i in range(5)]
    answers = []
    for cap in (None, 32):
        eng = _engine(cfg, params, num_slots=1, prefill_wave_tokens=cap)
        try:
            assert [eng._widest_wave(b) for b in (8, 16, 32, 64)] == (
                [32] * 4 if cap is None else [4, 2, 1, 1])

            async def burst():
                return await asyncio.gather(*[
                    eng.submit(p, max_new_tokens=3) for p in prompts])
            answers.append([r.tokens for r in asyncio.run(burst())])
            waves = {k for k in eng._prefill_jit}
            assert all(b * w <= (cap or 1 << 30) for b, w in waves), waves
        finally:
            eng.close()
    assert answers[0] == answers[1]
