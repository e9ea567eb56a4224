"""A block whose layers differ in kind, at test size on the CPU against
the plain reference ``chipbench/lib/reference_smallthinker.py`` (ISSUE 26):
``heads * head_dim != d_model``, 8 dropless ReGLU experts top-3 routed
from the attention's input, a period of one global layer without
positions and three rotating layers with a window of 8, over 8 layers,
pages of 4.  Seeded random float32 weights; logits are compared, not
tokens."""

import functools

import pytest

PRESET = "tiny-smallthinker"


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference and the benchmark's adapter read."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "vocab_size": cfg.vocab_size,
            "max_position_embeddings": cfg.max_seq_len,
            "moe_ffn_hidden_size": cfg.moe_d_ff,
            "moe_num_primary_experts": cfg.moe_experts,
            "moe_num_active_primary_experts": cfg.moe_top_k,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "rope_layout": list(cfg.rope_layout),
            "sliding_window_layout": list(cfg.window_layout),
            "sliding_window_size": cfg.sliding_window,
            "tie_word_embeddings": cfg.tie_embeddings}


@pytest.fixture(scope="module", params=[True, False],
                ids=["scanned", "unrolled"])
def parts(request):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_smallthinker as ref
    from ray_tpu.models import GPT, get_config

    cfg = get_config(PRESET, scan_layers=request.param)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 33))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    if request.param:
        weights = ref.from_program_params(params)
    else:       # the adapter knows the scanned tree: stack the unrolled one
        import flax.linen as nn
        p = nn.unbox(params)
        stacked = dict(p, blocks=jax.tree.map(
            lambda *a: jnp.stack(a),
            *[p[f"block_{i}"] for i in range(cfg.n_layers)]))
        weights = ref.from_program_params(stacked)
    return cfg, params, weights, _published(cfg), tokens


def test_the_preset_is_of_the_published_shape():
    from ray_tpu.models import get_config
    tiny, real = get_config(PRESET), get_config("smallthinker-21b-a3b")
    for cfg in (tiny, real):
        assert cfg.n_heads * cfg.head_dim != cfg.d_model
        assert cfg.moe_dropless and cfg.moe_router_pre_attn
        assert cfg.moe_act == "relu" and cfg.layers_differ
        assert cfg.rope_layout[:4] == cfg.window_layout[:4] == (0, 1, 1, 1)
    # the published model: 21.5B parameters; a token runs 6 of 64 experts
    # a layer, 2.9B without the embedding and the head (the name's A3B)
    assert round(real.num_params() / 1e9, 1) == 21.5
    layer = real._attn_params() + 64 * 3 * 2560 * 768 + 2560 * 64 + 2 * 2560
    assert real.num_params() == 52 * layer + 2 * 151936 * 2560 + 2560
    active = real.num_params() - 52 * 58 * 3 * 2560 * 768
    assert round((active - 2 * 151936 * 2560) / 1e9, 1) == 2.9


@pytest.mark.parametrize("pairs_max", [1 << 20, 0],
                         ids=["all-experts", "grouped"])
def test_forward_pass_matches_the_reference(parts, monkeypatch, pairs_max):
    """(a) ``GPT.__call__`` on 33 tokens (4 windows long), under both
    formulations of the dropless expert sum."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_smallthinker as ref
    from ray_tpu.models import GPT
    from ray_tpu.ops import moe

    cfg, params, weights, published, tokens = parts
    monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", pairs_max)
    got = GPT(cfg).apply({"params": params}, jnp.asarray(tokens))
    for row in range(2):
        want = ref.logits(weights, tokens[row], published)
        np.testing.assert_allclose(got[row], want, atol=3e-5, rtol=1e-5)
    # the check is sharp: each part of the block left out is far away
    for wrong in ({"no_window": True}, {"top_k": cfg.moe_top_k - 1},
                  {"router_dtype": "bfloat16"}):
        off = ref.logits(weights, tokens[0], published, **wrong)
        assert np.abs(np.asarray(off) - np.asarray(got[0])).max() > 0.1


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    return LLMEngine(cfg, params, num_slots=2, page_size=4,
                     max_seq_len=64, max_prompt_len=32, block_size=4,
                     min_prefill_bucket=4, **kw)


@pytest.mark.parametrize("lengths", [(21,), (30, 9)],
                         ids=["one-row", "two-rows"])
def test_a_wave_of_several_chunks_is_the_one_pass_on_real_positions(
        parts, lengths, prefill_chunk):
    """A wave at bucket 32 in chunks of 8 (``Block._chunked``), where the
    router reads the ATTENTION's input (its logits cross the attention
    beside the projections) and the layers differ in rotation and
    window (a span of 32 is four windows long): against one pass."""
    from conftest import assert_chunked_wave_is_the_whole_wave
    cfg, params = parts[:2]
    eng = _engine(cfg, params)
    try:
        assert_chunked_wave_is_the_whole_wave(eng, lengths, 32, 8,
                                              prefill_chunk)
    finally:
        eng.close()


def test_paged_prefill_and_decode_match_the_reference(parts):
    """(b) through the engine's own model, cache and page tables: a
    prompt of 13 tokens (longer than the window, not a whole number of
    pages) prefilled by the T > 1 path, then 24 teacher-forced decode
    steps across six page boundaries, every step's logits against the
    reference's full forward pass."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_smallthinker as ref

    cfg, params, weights, published, tokens = parts
    eng = _engine(cfg, params)
    try:
        seq = [int(t) for t in tokens[0]] + [int(t) for t in tokens[1, :4]]
        want = np.asarray(ref.logits(weights, seq, published))
        n = 13
        table = jnp.asarray([[3, 7, 1, 9, 2, 8, 4, 6, 5, 10, 11, 12,
                              13, 14, 15, 16]], jnp.int32)
        cache = eng._cache
        logits, mut = eng.model.apply(
            {"params": eng.params, "cache": cache},
            jnp.asarray([seq[:16]]), jnp.arange(16)[None],
            block_tables=table, mutable=["cache"])
        np.testing.assert_allclose(logits[0, :n], want[:n], atol=3e-5,
                                   rtol=1e-5)
        cache = mut["cache"]
        for p in range(n, len(seq)):       # position p overwrites the pad
            logits, mut = eng.model.apply(
                {"params": eng.params, "cache": cache},
                jnp.asarray([[seq[p]]]), jnp.asarray([[p]]),
                block_tables=table, mutable=["cache"])
            cache = mut["cache"]
            np.testing.assert_allclose(logits[0, 0], want[p], atol=3e-5,
                                       rtol=1e-5, err_msg=f"position {p}")
    finally:
        eng.close()


def test_the_engine_s_greedy_tokens_are_the_reference_s(parts):
    """(b) the loop itself: two requests prefilled and decoded past the
    window by ``LLMEngine``; every token it returned is the
    reference's largest logit at its position, and the engine counted
    the expert load and the pages its window layers left unread."""
    from chipbench.lib import reference_smallthinker as ref

    cfg, params, weights, published, tokens = parts
    eng = _engine(cfg, params)
    try:
        for row, plen in ((0, 13), (1, 6)):
            prompt = [int(t) for t in tokens[row, :plen]]
            out = eng.submit(prompt, max_new_tokens=20, temperature=0.0)
            assert plen + 20 > cfg.sliding_window + 8
            rows = ref.logits(weights, (prompt + out.tokens)[:-1],
                              published)[plen - 1:]
            assert ref.token_agreement(rows, out.tokens) == 1.0
        st = eng.stats.snapshot(2)
    finally:
        eng.close()
    assert st["moe_layer_steps"] > 0
    assert 1 <= st["moe_experts_touched"] / st["moe_layer_steps"] <= 6
    # 6 of 8 layers have the window; by hand for the first request: the
    # step that makes token k reads 13 + k positions
    ps, w = 4, 8
    read = skipped = 0
    for plen in (13, 6):
        for length in range(plen + 1, plen + 20):
            skip = max(0, length - w) // ps
            skipped += 6 * skip
            read += 6 * (-(-length // ps) - skip)
    assert (st["window_pages_read"], st["window_pages_skipped"]) == (
        read, skipped)
    assert skipped > 0


def _interpret_pallas(monkeypatch):
    """Run every ``pallas_call`` under jax's TPU interpreter."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=pltpu.InterpretParams()))


@pytest.mark.parametrize("window", [16, 40, 1 << 30],
                         ids=["page", "part-pages", "none"])
def test_pallas_decode_kernel_honours_the_window_in_tpu_interpreter(
        monkeypatch, window):
    """(c) the kernel with a window against ``paged_attention_xla`` with
    the same window, and far from the answer without one."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.paged_attention import (paged_attention_tpu,
                                             paged_attention_xla)

    _interpret_pallas(monkeypatch)
    rs = np.random.RandomState(window % 97)
    pool = jnp.asarray(rs.randn(3, 13, 2, 16, 128), jnp.bfloat16)
    q = jnp.asarray(rs.randn(4, 4, 64), jnp.bfloat16)
    tables = jnp.asarray(rs.permutation(12).reshape(3, 4)[[0, 1, 2, 0]] + 1,
                         jnp.int32)
    lengths = jnp.asarray([5, 33, 64, 49], jnp.int32)
    got = paged_attention_tpu(q, pool, tables, lengths, layer=jnp.int32(1),
                              window=jnp.int32(window))
    want = paged_attention_xla(q, pool, tables, lengths, layer=1,
                               window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    plain = paged_attention_xla(q, pool, tables, lengths, layer=1)
    far = np.abs(np.asarray(got, np.float32)
                 - np.asarray(plain, np.float32)).max()
    assert (far < 2e-2) if window > 64 else (far > 0.1)


@pytest.mark.parametrize("pairs_max", [1 << 20, 0],
                         ids=["all-experts", "grouped"])
def test_nothing_is_dropped_under_total_imbalance(parts, monkeypatch,
                                                  pairs_max):
    """(d) a router whose weights send every token of every layer to the
    same three experts: 99 pairs on 3 of 8 experts (the capacity dispatch
    would keep 15 a row), every one computed, result equal to the
    reference's.  How: every embedding row gets one large coordinate, so
    the normalised input has it large and positive for every token, and
    the router's row for that coordinate favours experts 1, 4 and 6."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_smallthinker as ref
    from ray_tpu.models import GPT
    from ray_tpu.ops import moe

    cfg, params, _, published, tokens = parts
    monkeypatch.setattr(moe, "DENSE_PAIRS_MAX", pairs_max)
    favour = jnp.zeros((cfg.moe_experts,)).at[jnp.asarray([1, 4, 6])].set(
        jnp.asarray([3.0, 2.0, 1.0]))

    def skew(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:        # [(L,) d, E]: row 0 of every layer
            return leaf.at[..., 0, :].set(favour)
        if name == "['embed']":
            return leaf.at[:, 0].set(50.0)
        return leaf
    skewed = jax.tree_util.tree_map_with_path(skew, nn.unbox(params))
    got, mut = GPT(cfg).apply({"params": skewed}, jnp.asarray(tokens[:1]),
                              mutable=["intermediates"])
    idx = np.concatenate([np.asarray(leaf).reshape(-1, cfg.moe_top_k)
                          for path, leaf in
                          jax.tree_util.tree_leaves_with_path(mut)
                          if "expert_idx" in jax.tree_util.keystr(path)])
    # total imbalance: every token of every layer chose the same three
    assert idx.shape[0] == cfg.n_layers * 33
    assert {tuple(r) for r in idx} == {(1, 4, 6)}
    stacked = skewed if cfg.scan_layers else dict(
        skewed, blocks=jax.tree.map(
            lambda *a: jnp.stack(a),
            *[skewed[f"block_{i}"] for i in range(cfg.n_layers)]))
    want = ref.logits(ref.from_program_params(stacked), tokens[0],
                      published)
    np.testing.assert_allclose(got[0], want, atol=3e-5, rtol=1e-5)
    assert np.abs(np.asarray(want)).max() > 0.1


# ---- the touched-experts decode kernel (ISSUE 27) ----

def _interpreted_expert_kernel(monkeypatch):
    """Make ``ops/moe.py`` take its TPU branch, with the kernel under
    jax's TPU interpreter (the paged kernel keeps its XLA branch).
    Returns the list that collects the shape of the expert weights each
    kernel is built over."""
    from ray_tpu.ops import moe

    built, kernel = [], moe._experts_kernel

    def spy(x, combine, ids, count, layer, w_gate, *rest):
        built.append(w_gate.shape)
        return kernel(x, combine, ids, count, layer, w_gate, *rest)
    monkeypatch.setattr(moe, "_experts_kernel", spy)
    monkeypatch.setattr(moe, "backend_platform", lambda: "tpu")
    _interpret_pallas(monkeypatch)
    return built


def _routing(case, rows, e, k, rs):
    """``(experts [rows, k], live [rows])`` of a named routing."""
    import numpy as np
    experts = np.stack([rs.permutation(e)[:k] for _ in range(rows)])
    live = np.ones(rows, bool)
    if case == "one-live-row":
        live[:] = False
        live[3] = True
    elif case == "total-imbalance":
        experts[:] = [1, 4, 6]
    elif case == "all-touched":
        experts[:4] = np.arange(4 * k).reshape(4, k) % e
    elif case == "dead-rows-elsewhere":     # live rows on 0-3, dead on 4-7
        live[1::2] = False
        experts[live] = np.stack([rs.permutation(4)[:k] for _ in
                                  range(live.sum())])
        experts[~live] = 4 + np.stack([rs.permutation(4)[:k] for _ in
                                       range((~live).sum())])
    return experts, live


@pytest.mark.parametrize("layers,vmem", [(3, None), (1, None), (3, 1)],
                         ids=["stacked", "one-layer", "width-tiled"])
@pytest.mark.parametrize("case", ["one-live-row", "total-imbalance",
                                  "all-touched", "dead-rows-elsewhere",
                                  "no-mask"])
def test_expert_kernel_reads_what_live_rows_chose(monkeypatch, case,
                                                  layers, vmem):
    """The ``moe_experts_decode`` kernel (TPU interpreter, lane-aligned
    small widths, bf16) against the all-experts formulation; the id list
    it walks holds exactly the experts the live rows chose, which is what
    ``LLMEngine._expert_load`` counts; dead rows come out zero.
    ``width-tiled``: an expert too wide for the kernel's VMEM budget is
    walked in slices of 128 of its width (a second grid axis)."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import moe
    from ray_tpu.serve.llm_engine import LLMEngine

    e, d, f, rows, k = 8, 128, 256, 9, 3
    rs = np.random.RandomState(len(case))
    experts, live = _routing(case, rows, e, k, rs)
    mask = None if case == "no-mask" else jnp.asarray(live)
    x = jnp.asarray(rs.randn(rows, d), jnp.bfloat16)
    gates = jnp.asarray(rs.dirichlet(np.ones(k), rows), jnp.float32)
    w = [jnp.asarray(rs.randn(layers, e, a, b) / a ** 0.5, jnp.bfloat16)
         for a, b in ((d, f), (d, f), (f, d))]
    layer = jnp.int32(layers - 1)
    args = (x, gates, jnp.asarray(experts, jnp.int32), *w)
    run = jax.jit(lambda *a: moe.dropless_experts(
        *a, act="relu", live=mask, layer=layer)[0])
    want = np.asarray(run(*args), np.float32)        # every expert read
    built = _interpreted_expert_kernel(monkeypatch)
    if vmem:
        monkeypatch.setattr(moe, "_KERNEL_WEIGHTS_VMEM", vmem)
        assert moe._width_tile(d, f, 2) == 128
    run = jax.jit(lambda *a: moe.dropless_experts(
        *a, act="relu", live=mask, layer=layer)[0])
    got = np.asarray(run(*args), np.float32)
    assert built == [(layers, e, d, f)]
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert np.abs(want[live]).max() > 0.3
    if mask is not None:
        assert (got[~live] == 0).all() and (want[~live] == 0).all()

    ids, count = moe.touched_experts(jnp.asarray(experts), mask, e)
    chosen = sorted(set(experts[live].reshape(-1)))
    assert ids.shape == (min(e, rows * k),)
    assert list(np.asarray(ids)[:int(count)]) == chosen
    assert (np.asarray(ids)[int(count):] == chosen[-1]).all()
    assert {"one-live-row": k, "total-imbalance": 3, "all-touched": e,
            "dead-rows-elsewhere": 4}.get(case, len(chosen)) == len(chosen)
    steps, touched = LLMEngine._expert_load(
        types.SimpleNamespace(cfg=types.SimpleNamespace(experts_here=e),
                              _rows=rows),
        {"expert_idx": jnp.asarray(experts)[None, :, None]},
        jnp.asarray(live))
    assert (int(steps), int(touched)) == (1, int(count))


def test_no_live_row_reads_no_expert(monkeypatch):
    """An engine with nothing in flight: the kernel's id list is empty,
    every slot's arithmetic is skipped and the layer adds zero."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import moe

    _interpreted_expert_kernel(monkeypatch)
    rs = np.random.RandomState(0)
    w = [jnp.asarray(rs.randn(1, 4, 128, 128), jnp.bfloat16)
         for _ in range(3)]
    experts = jnp.asarray(rs.randint(0, 4, (5, 2)), jnp.int32)
    dead = jnp.zeros((5,), bool)
    _, count = moe.touched_experts(experts, dead, 4)
    assert int(count) == 0
    out, _ = moe.dropless_experts(
        jnp.asarray(rs.randn(5, 128), jnp.bfloat16),
        jnp.full((5, 2), 0.5), experts, *w, live=dead, layer=0)
    assert (np.asarray(out, np.float32) == 0).all()


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_decode_hands_the_kernel_the_stacked_experts(monkeypatch, scan):
    """Through the engine's own model at lane-aligned widths, rows 0 and
    2 holding a request and row 1 on the scratch page: the decode
    step with the kernel gives the live rows the logits the all-experts
    formulation gives them.  Scanned layers hand the kernel the STACKED
    expert leaves (its operands hold the layer dimension); unrolled
    layers have no stack and keep the all-experts product."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import GPT, get_config

    cfg = get_config(PRESET, n_layers=4, d_model=128, moe_d_ff=128,
                     scan_layers=scan)
    params = GPT(cfg).init(jax.random.PRNGKey(2),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    eng = _engine(cfg, params)
    try:
        tables = jnp.zeros((eng._rows, eng.max_pages), jnp.int32)
        tables = tables.at[0, :2].set(jnp.asarray([3, 1]))
        tables = tables.at[2, :2].set(jnp.asarray([2, 5]))
        args = ({"params": eng.params, "cache": eng._cache},
                jnp.asarray([[7], [0], [9]]),
                jnp.asarray([[5], [0], [4]]))

        def step(variables, tokens, positions):
            return eng.model.apply(variables, tokens, positions,
                                   block_tables=tables,
                                   mutable=["cache"])[0]
        want = jax.jit(step)(*args)
        built = _interpreted_expert_kernel(monkeypatch)
        # (another function object: jit's cache is keyed by it)
        got = jax.jit(lambda *a: step(*a))(*args)
    finally:
        eng.close()
    # [L, E, d, f] whole, not a layer's [E, d, f] (the scan may trace
    # its body more than once)
    assert set(built) == ({(4, 8, 128, 128)} if scan else set())
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=1e-5)
    assert np.abs(np.asarray(want)[live]).max() > 0.1


# sha256 of the lowered (StableHLO) text of ``GPT(cfg).apply`` on a
# [2, 24] batch, taken at the parent commit (d342a83)
PARENT_LOWERING = {
    "tiny":
    "bb9e6ee22a4ba0b2f8643bad95dec5674ebd66a5cc1001aef79a873f1b80752f",
    "tiny-moe":
    "f931cf6d365d73470a1d3bdc7a9469c5b2c8fba01653a74a56494d02decfd152"}


def _lowering_sha(preset):
    import hashlib

    import jax
    import jax.numpy as jnp
    from ray_tpu.models import GPT, get_config

    cfg = get_config(preset)
    tokens = jnp.zeros((2, 24), jnp.int32)
    net = GPT(cfg)
    params = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(5),
                                             tokens)["params"])
    text = jax.jit(lambda p, t: net.apply({"params": p}, t)).lower(
        params, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("preset", sorted(PARENT_LOWERING))
def test_existing_presets_logits_are_unchanged_bit_for_bit(preset):
    """(e) the fields ISSUE 26 added default to the old program: what
    ``GPT.__call__`` lowers to for the presets that were there is, to the
    letter, what the parent commit lowered (so the logits are the
    parent's bit for bit on any backend)."""
    assert _lowering_sha(preset) == PARENT_LOWERING[preset]
