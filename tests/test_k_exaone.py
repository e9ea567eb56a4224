"""K-EXAONE's blocks and its self-drafting decode step (ISSUE 46), at test
size on the CPU against the plain reference
``chipbench/lib/reference_k_exaone.py``: the preset ``tiny-k-exaone`` (8
query / 2 KV heads of 16 with a per-head QK norm, three rotating layers
with a window of 8 to one global layer without positions, one dense
layer then five of 8 sigmoid-routed experts top-3 with one shared, one
multi-token-prediction module), pages of 4.  Seeded random weights;
numbers are compared, not tokens, but where tokens are the only thing
the engine hands out."""

import dataclasses
import functools
import importlib
import threading

import pytest

PRESET = "tiny-k-exaone"


def _published(cfg) -> dict:
    """The preset in the published ``config.json`` key names, which the
    reference and the adapter read."""
    kinds = ["sliding_attention" if v else "full_attention"
             for v in cfg.window_layout]
    n, dense = cfg.n_layers, cfg.first_dense_layers
    return {"vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
            "num_hidden_layers": n, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff,
            "moe_intermediate_size": cfg.moe_d_ff,
            "max_position_embeddings": cfg.max_seq_len,
            "rms_norm_eps": cfg.norm_eps, "hidden_act": "silu",
            "rope_parameters": {"rope_theta": cfg.rope_theta,
                                "rope_type": "default"},
            "sliding_window": cfg.sliding_window, "layer_types": kinds,
            "mlp_layer_types": ["dense"] * dense + ["sparse"] * (
                len(kinds) - dense),
            "num_experts_per_tok": cfg.moe_top_k,
            "routed_scaling_factor": cfg.moe_route_scale,
            "scoring_func": "sigmoid", "norm_topk_prob": True,
            "n_group": 1, "topk_group": 1,
            "num_experts": cfg.experts_here,
            "published": {"num_experts": cfg.moe_experts},
            "experts_held_first": cfg.moe_held_first,
            "num_shared_experts": cfg.moe_shared_experts,
            "first_k_dense_replace": dense,
            "num_nextn_predict_layers": cfg.mtp_layers,
            "mtp_layer_types": ["full_attention"],
            "tie_word_embeddings": False}


def _parts(**overrides):
    import jax
    import jax.numpy as jnp
    from chipbench.lib import reference_k_exaone as ref
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


def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm_engine import LLMEngine
    kw = {"num_slots": 3, "page_size": 4, "max_seq_len": 96,
          "max_prompt_len": 32, "block_size": 4, "min_prefill_bucket": 8,
          **kw}
    return LLMEngine(cfg, params, **kw)


def _without_module(cfg, params):
    """The same model served WITHOUT drafting: no module."""
    import flax.linen as nn
    return (dataclasses.replace(cfg, mtp_layers=0),
            {k: v for k, v in nn.unbox(params).items() if k != "mtp"})


def _serve(eng, prompts, **kw):
    """Every prompt at once through ``eng.submit``; the results."""
    out = [None] * len(prompts)

    def go(i):
        out[i] = eng.submit(prompts[i], **{
            k: v[i] if isinstance(v, list) else v for k, v in kw.items()})
    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def test_the_preset_is_of_the_published_shape():
    """The preset against the configuration file the cell runs (the
    published keys, the cut ones under ``published``), through the
    adapter; 236.6B parameters and the module's 5.1B."""
    from chipbench.lib import arch_k_exaone, configs
    from ray_tpu.models import get_config
    file = configs.load_json("chipbench/configs/k-exaone-236b-a23b.json")
    uncut = dict(file, **file["published"])
    uncut["published"] = {"num_experts": uncut["num_experts"]}
    over = arch_k_exaone.model_overrides(uncut)
    assert over.pop("moe_experts_held") == 128
    cfg = get_config("k-exaone-236b-a23b")
    for key, value in over.items():
        want = getattr(cfg, key)
        want = want[:len(value)] if key.endswith("_layout") else want
        assert want == value, key
    module = 2 * 6144 * 6144 + 3 * 6144 + cfg.layer_params() \
        - 3 * 6144 * 18432 + 129 * 3 * 6144 * 2048 + 6144 * 128 + 128
    assert cfg.num_params() - module == pytest.approx(236.6e9, rel=2e-3)
    held = get_config("k-exaone-236b-a23b", **arch_k_exaone.model_overrides(
        file))
    assert held.num_params() * 2 == pytest.approx(10.6e9, rel=5e-3)
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]


@pytest.mark.parametrize("held", [None, (3, 2)], ids=["all", "held-3-from-2"])
@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
def test_forward_pass_matches_the_reference(scan, held):
    """The whole forward, and the module's over ``(h_i, t_{i+1})``:
    logits against the reference's, with every expert held and with a
    share of them."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    from ray_tpu.models import GPT
    over = {} if held is None else {"moe_experts_held": held[0],
                                    "moe_held_first": held[1]}
    cfg, params, weights, published = _parts(scan_layers=scan, **over)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 29))
    model = GPT(cfg)
    hidden, pre = model.apply({"params": params}, jnp.asarray(tokens),
                              return_hidden=True, return_prenorm=True)
    got = model.apply({"params": params}, jnp.asarray(tokens))
    nxt = np.concatenate([tokens[:, 1:], np.full((2, 1), 7)], 1)
    got_mtp = model.apply({"params": params}, jnp.asarray(nxt),
                          mtp_hidden=pre)
    for b in range(2):
        want, want_mtp = ref.logits(weights, tokens[b], published, mtp=True,
                                    last_next=7)
        np.testing.assert_allclose(got[b], want, atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(got_mtp[b], want_mtp, atol=3e-4,
                                   rtol=1e-3)
    # each fault moves the reference: none of them is a no-op here
    right = ref.hidden(weights, tokens[0], published, mtp=True)
    for fault in ref.FAULTS:
        if fault == "absent_experts_added" and held is None:
            continue                    # no expert is absent
        wrong = ref.hidden(weights, tokens[0], published, mtp=True,
                           fault=fault)
        pick = 1 if fault.startswith("mtp_") else 0
        assert float(jnp.abs(wrong[pick] - right[pick]).max()) > 1e-3, fault


@pytest.mark.parametrize("n_prompt", [13, 16, 30],
                         ids=["odd", "fills-its-bucket", "two-buckets"])
def test_paged_prefill_and_verify_steps_match_the_reference(parts, n_prompt):
    """A prompt through the paged prefill and the module over it, then
    verify steps of two positions a row through the pool in the engine's
    shape (``lib/replica_mtp.py program_hidden``: what the chip's check
    runs), against the reference's ONE forward over the whole sequence:
    the stack's hidden states, the module's, the routers' logits."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    from chipbench.lib.replica_mtp import program_hidden
    cfg, params, weights, published = parts
    eng = _engine(cfg, params)
    assert eng._cache["kv_pages"].shape[0] == cfg.n_layers + 1
    seq = np.random.default_rng(n_prompt).integers(1, 256, n_prompt + 37)
    got = program_hidden(eng, seq, n_prompt, last_next=5)
    want, want_mtp = ref.hidden(weights, seq, published, mtp=True,
                                last_next=5)
    np.testing.assert_allclose(got["hidden"], want, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(got["mtp_hidden"], want_mtp, atol=2e-4,
                               rtol=1e-3)
    check = ref.router_check(weights, got["router_in"], got["router_out"])
    assert check["router_rel_err"] < 1e-5 < check["router_bf16_control"]
    # (every expert is held here: none is absent)
    faults = [f for f in ref.FAULTS if f != "absent_experts_added"]
    report = ref.hidden_check(weights, jnp.asarray(seq), got["hidden"],
                              got["mtp_hidden"], published, faults=faults,
                              last_next=5)
    assert report["hidden_rel_err"] < 1e-4 < report["fp8_control"]
    assert report["mtp_hidden_rel_err"] < 1e-4 < report["mtp_fp8_control"]
    for fault in faults:
        assert abs(report[f"{fault}_projection"]) < 0.05, fault
        assert report[f"{fault}_control"] > 1e-3, fault


@pytest.mark.parametrize("block_size", [1, 4])
def test_greedy_with_drafting_is_greedy_without(parts, block_size):
    """Float32, temperature 0: token for token what the same model gives
    served without its module, whatever the drafts were (with seeded
    random weights nearly all of them fall: every rejected draft's K/V
    row is overwritten before anything reads it)."""
    import numpy as np
    cfg, params, _, _ = parts
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 256, n).tolist() for n in (5, 17, 30, 9, 26)]
    asked = [20, 21, 7, 30, 1]
    on = _engine(cfg, params, block_size=block_size)
    off = _engine(*_without_module(cfg, params), block_size=block_size)
    try:
        drafted = _serve(on, prompts, max_new_tokens=asked)
        plain = _serve(off, prompts, max_new_tokens=asked)
    finally:
        on.close()
        off.close()
    for a, b, n in zip(drafted, plain, asked):
        assert a.tokens == b.tokens and len(a.tokens) == n
        assert a.finish_reason == b.finish_reason == "length"
    st = on.stats
    assert st.drafts_proposed > 0
    assert st.step_tokens == st.drafts_proposed + st.drafts_accepted


def test_sampled_requests_emit_one_or_two_tokens_a_step(parts):
    """Temperature 1.0: both branches run; every request gets the
    tokens it asked for, odd and even counts alike; the counters say
    what happened (tokens = steps + accepted drafts, two rows written a
    pool layer a delivered step) and the decode-time account still sums
    to the time between first and last token."""
    import numpy as np
    cfg, params, _, _ = parts
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 256, n).tolist() for n in (5, 17, 30, 9, 26, 12)]
    asked = [40, 41, 42, 43, 2, 3]
    eng = _engine(cfg, params, seed=3)
    try:
        results = _serve(eng, prompts, max_new_tokens=asked, temperature=1.0)
    finally:
        eng.close()
    st = eng.stats
    for r, n in zip(results, asked):
        assert len(r.tokens) == n and r.finish_reason == "length"
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)
        assert r.stepping_s + r.prefill_stall_s + r.block_tail_s == \
            pytest.approx(r.latency_s - r.time_to_first_token_s, abs=1e-6)
    assert 0 < st.drafts_accepted < st.drafts_proposed
    assert st.step_tokens == sum(asked) - len(asked)
    # a request may end at the first of an accepted pair
    assert 0 <= st.drafts_proposed + st.drafts_accepted - st.step_tokens \
        <= len(asked)
    assert st.decode_rows_written == 2 * st.drafts_proposed * (
        cfg.n_layers + 1)
    assert st.pool_layer_steps == st.steps * (cfg.n_layers + 1)
    snap = st.snapshot(eng.num_slots)
    assert snap["drafts_accepted"] == st.drafts_accepted
    assert snap["window_pages_skipped"] > 0


def _faulty_rule(fault):
    """``models/generate.py verify_draft`` with a fault put in."""
    import jax.numpy as jnp
    real = importlib.import_module("ray_tpu.models.generate").verify_draft

    def rule(rng, logits1, logits2, q_logits, draft, **kw):
        n, first, second = real(rng, logits1, logits2, q_logits, draft, **kw)
        if fault == "every-draft-stands":
            return jnp.full_like(n, 2), draft.astype(first.dtype), second
        if fault == "no-draft-stands":
            # the draft's own token is handed out, but alone
            return jnp.ones_like(n), first, second
        # an accepted pair handed out second token first
        return n, jnp.where(n == 2, second, first), jnp.where(
            n == 2, first, second)
    return rule


@pytest.mark.parametrize("fault", [
    None, "every-draft-stands", "no-draft-stands", "pair-out-of-order"])
def test_the_replay_holds_the_block_program_to_the_reference(
        parts, monkeypatch, fault):
    """What the chip's check does with the engine's OWN block program
    (``lib/replica_mtp.py block_replay``, ``reference.replay_check``):
    rows of one request, live together among dead rows, stepped by
    ``engine_decode_block`` from several contexts; their decisions
    against the reference's ``sum min(P_1, Q)`` at the positions their
    drafts were for, the logits their next drafts came from against the
    reference's module at each row's own last position, the state's
    bookkeeping.  The program as it is passes; one that lets every draft
    stand, or none, fails the decisions' band, and one that hands a pair
    out in the wrong order computes its next draft from another context
    than the tokens it emitted."""
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    from chipbench.lib.replica_mtp import block_replay, program_hidden
    cfg, params, weights, published = parts
    if fault:      # (the package re-exports a function of the same name)
        monkeypatch.setattr(importlib.import_module(
            "ray_tpu.models.generate"), "verify_draft", _faulty_rule(fault))
    eng = _engine(cfg, params, num_slots=6, block_size=8, kv_pool_pages=128)
    seq = np.random.default_rng(7).integers(1, 256, 41)
    got = program_hidden(eng, seq[:-1], 12, last_next=int(seq[-1]))
    replay = block_replay(eng, seq[:-1], got, 12, 1.0, ref.PAD, seed=1)
    rows = replay.pop("rows")
    assert len(rows) == 6 and len({r["start"] for r in rows}) > 3
    assert all(r["start"] % eng.page_size == 0 for r in rows)
    assert all(len(r["counts"]) == 16 for r in rows)
    assert replay["replay_state_ok"] == 1
    report = ref.replay_check(weights, published, rows, 1.0)
    assert report["replay_drafts"] == 96
    assert report["replay_always_accept_control"] > 6
    assert report["replay_q_other_row_control"] > 0.1
    if fault is None:
        assert abs(report["replay_accept_z"]) < 4
        assert abs(report["replay_loglik_z"]) < 4
        assert report["replay_q_rel_err"] < 1e-4
        assert {1, 2} == {c for r in rows for c in r["counts"]}
    elif fault == "every-draft-stands":
        assert report["replay_accept_z"] > 6
    elif fault == "no-draft-stands":
        assert report["replay_accept_z"] < -6
    else:
        assert report["replay_q_rel_err"] > 0.1


def _delivered(cfg, params, block, *, max_new_tokens, eos_id=None,
               prompt_len=10, max_seq_len=96):
    """One request in slot 0 of an idle engine, handed ``block`` (a
    drafting engine's ``[first, second, count]`` of ``block_size`` 4
    steps) by ``_deliver_block``: ``(its tokens, finish reason or None,
    the stats)``."""
    import numpy as np
    from ray_tpu.serve import llm_engine as le
    eng = _engine(cfg, params, max_seq_len=max_seq_len)
    done = {}
    req = le._Request(list(range(prompt_len)), max_new_tokens, 1.0, eos_id,
                      lambda ok, value: done.update(result=value), None)
    eng._slots[0] = sl = le._Slot(req, prompt_len, 100, [1, 2, 3])
    full = np.zeros((3, eng._rows, 4), np.int64)
    full[:, 0] = block
    eng._deliver_block(full, [(0, req)], le._Ahead(0, [], {}), 4)
    result = done.get("result")
    return (sl.out, result and result.finish_reason, eng.stats)


@pytest.mark.parametrize("asked, tokens, steps, accepted", [
    # ends at the FIRST of a pair (whose draft stood all the same)
    (4, [100, 11, 12, 21], 2, 2),
    (5, [100, 11, 12, 21, 22], 2, 2),   # ends at the second
    (3, [100, 11, 12], 1, 1),
    (9, [100, 11, 12, 21, 22, 31, 41, 42], 4, 3),     # not finished
], ids=["odd-cut", "even", "one-step", "runs-on"])
def test_a_request_may_end_in_the_middle_of_a_pair(parts, asked, tokens,
                                                   steps, accepted):
    """Steps gave (11, 12), (21, 22), (31), (41, 42): the request gets
    its tokens in order up to ``max_new_tokens`` and no further, and the
    counters count the steps that were delivered."""
    cfg, params, _, _ = parts
    block = [[11, 21, 31, 41], [12, 22, 99, 42], [2, 2, 1, 2]]
    out, reason, st = _delivered(cfg, params, block, max_new_tokens=asked)
    assert out == tokens
    assert reason == (None if asked == 9 else "length")
    assert (st.drafts_proposed, st.drafts_accepted) == (steps, accepted)
    assert st.step_tokens == len(tokens) - 1
    assert st.decode_rows_written == 2 * steps * (cfg.n_layers + 1)


def test_eos_as_the_first_of_two_ends_the_request_there(parts):
    cfg, params, _, _ = parts
    block = [[11, 21, 31, 41], [12, 22, 99, 42], [2, 2, 1, 2]]
    out, reason, _ = _delivered(cfg, params, block, max_new_tokens=50,
                                eos_id=21)
    assert (out, reason) == ([100, 11, 12, 21], "eos")
    out, reason, _ = _delivered(cfg, params, block, max_new_tokens=50,
                                eos_id=22)
    assert (out, reason) == ([100, 11, 12, 21, 22], "eos")


def test_max_seq_len_ends_the_request_inside_a_pair(parts):
    """A prompt of 10 in a span of 14: positions 10 .. 13 hold its four
    tokens' rows, and the fourth is the first of the second pair."""
    cfg, params, _, _ = parts
    block = [[11, 21, 31, 41], [12, 22, 99, 42], [2, 2, 1, 2]]
    out, reason, _ = _delivered(cfg, params, block, max_new_tokens=50,
                                max_seq_len=14)
    assert (out, reason) == ([100, 11, 12, 21], "length")


def test_a_drafting_engine_refuses_what_carries_no_draft(parts):
    cfg, params, _, _ = parts
    with pytest.raises(ValueError, match="prediction module"):
        _engine(cfg, params, prefix_cache_pages=8)
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="prediction module"):
        eng.export_prefill([1, 2, 3])


# ---- the acceptance rule (models/generate.py) ----

def _rule_case(seed: int, v: int = 8):
    import numpy as np
    rs = np.random.RandomState(seed)
    soft = lambda z: np.exp(z) / np.exp(z).sum()             # noqa: E731
    return soft(rs.randn(v) * 1.5), soft(rs.randn(v) * 1.5), soft(
        rs.randn(v) * 1.5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_rule_preserves_the_model_s_distribution_exactly(seed):
    """On a vocabulary of 8, by enumerating the acceptance's uniform on a
    grid of 4,096 points for every draft: ``sum_d q(d) [share of u that
    lets d stand, at d; the rest on the residual]`` is ``p1``, to the
    grid's resolution, whatever ``q``; and the second token's
    distribution, given that the first stood, is ``p2``'s."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import accept_draft
    p1, _, q = _rule_case(seed)
    grid = (np.arange(4096) + 0.5) / 4096
    out = np.zeros(8)
    for d in range(8):
        accepted, residual = accept_draft(
            jnp.asarray(np.tile(p1, (4096, 1)), jnp.float32),
            jnp.asarray(np.tile(q, (4096, 1)), jnp.float32),
            jnp.full((4096,), d), jnp.asarray(grid, jnp.float32))
        share = float(np.mean(np.asarray(accepted)))
        assert share == pytest.approx(min(1.0, p1[d] / q[d]), abs=1 / 4096)
        rest = np.asarray(residual[0])
        out[d] += q[d] * share
        if rest.sum() > 0:
            out += q[d] * (1 - share) * rest / rest.sum()
    np.testing.assert_allclose(out, p1, atol=2e-3)


@pytest.mark.parametrize("temperature", [1.0, 0.7])
def test_the_rule_preserves_the_model_s_distribution_in_draws(temperature):
    """200,000 rows through ``verify_draft`` with drafts drawn from the
    module's distribution: the first token emitted is distributed as the
    model's own (chi-square on 7 degrees of freedom under 24.3, its
    99.9th percentile), though 9 drafts in 10 fall; and so is the second
    where the draft stood."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import sample_logits, verify_draft
    n = 200_000
    p1, p2, q = (np.log(p) * temperature for p in _rule_case(5))
    rows = lambda z: jnp.asarray(np.tile(z, (n, 1)), jnp.float32)  # noqa: E731
    temps = jnp.full((n,), temperature, jnp.float32)
    k_d, k_v = jax.random.split(jax.random.PRNGKey(7))
    drafts = sample_logits(k_d, rows(q), temperature=temps)
    count, first, second = (np.asarray(a) for a in verify_draft(
        k_v, rows(p1), rows(p2), rows(q), drafts, temperature=temps))
    assert 0.05 < np.mean(count == 2) < 0.9

    def chi2(tokens, logits):
        want = np.exp(logits / temperature)
        want = want / want.sum() * len(tokens)
        got = np.bincount(tokens, minlength=8)
        return float(((got - want) ** 2 / want).sum())
    assert chi2(first, p1) < 24.3
    assert chi2(second[count == 2], p2) < 24.3
    # and it is not the drafter's
    assert chi2(first, q) > 100


def test_greedy_rows_take_the_draft_iff_it_is_the_argmax():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.generate import verify_draft
    p1, p2, q = (jnp.asarray(np.log(np.tile(p, (8, 1))), jnp.float32)
                 for p in _rule_case(3))
    best, then = int(jnp.argmax(p1[0])), int(jnp.argmax(p2[0]))
    drafts = jnp.arange(8)
    count, first, second = verify_draft(
        jax.random.PRNGKey(0), p1, p2, jnp.where(
            jnp.arange(8)[None] == drafts[:, None], 0.0, -30.0), drafts,
        temperature=jnp.zeros((8,)))
    assert list(np.asarray(count)) == [2 if d == best else 1
                                       for d in range(8)]
    assert set(np.asarray(first).tolist()) == {best}
    assert int(second[best]) == then


def test_the_reference_s_rule_is_the_program_s():
    """``reference.accept`` on ``(P_1, P_2, Q, d, u)`` against
    ``accept_draft`` for the decision, over a grid of ``u``."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    from ray_tpu.models.generate import accept_draft
    p1, p2, q = _rule_case(9)
    for d in range(8):
        for u in (0.01, 0.3, 0.6, 0.99):
            out = ref.accept(p1, p2, q, d, (u, 0.5, 0.5))
            stood, _ = accept_draft(jnp.asarray(p1[None], jnp.float32),
                                    jnp.asarray(q[None], jnp.float32),
                                    jnp.asarray([d]), jnp.asarray([u]))
            assert len(out) == 1 + int(stood[0])
            assert (out[0] == d) == bool(stood[0]) or p1[d] >= q[d]
    ratio = ref.accept_probability(p1[None], q[None], [3])
    assert float(ratio[0]) == pytest.approx(min(1, p1[3] / q[3]), rel=1e-5)


# ---- the paged decode kernel at two queries a row ----

_PS = 32          # two bf16 sublane tiles: a write-back is half a page

# name: (head_dim, each row's length WITH both new tokens, window, live).
# Chunks are two pages
_VERIFY_CASES = {
    "plain": (128, [37, 70, 5], None, None),
    # the first new row is the last of a page, the second opens the next:
    # for 65 and 129 the earlier page is the chunk BEFORE the last
    "straddles-a-page": (128, [33, 65, 129], None, None),
    "straddles-a-group": (128, [17, 49, 81], None, None),
    "window": (128, [37, 150, 100], 40, None),
    "window-edge-inside-a-page": (128, [45, 77, 141], 12, None),
    "dead-rows": (64, [37, 70, 5, 90], None, [1, 0, 1, 0]),
    "two-positions": (128, [2, 2, 3], None, None),
    "padded-query-window": (64, [33, 65, 100], 20, None),
}


@pytest.fixture
def tpu_interpreter(monkeypatch):
    """``tests/test_paged_llm.py``'s: ``pallas_call`` in the TPU
    interpreter, the kernel's chunk cut to two pages."""
    import importlib

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call, interpret=pltpu.InterpretParams()))
    monkeypatch.setattr(
        importlib.import_module("ray_tpu.ops.paged_attention"),
        "_CHUNK_TOKENS", 2 * _PS)


def _verify_case(case):
    import jax.numpy as jnp
    import numpy as np
    hd, lengths, window, live = _VERIFY_CASES[case]
    rows, mp, kvh, heads = len(lengths), 6, 2, 8
    rs = np.random.RandomState(len(case))
    pool = jnp.asarray(rs.randn(3, 1 + rows * mp, kvh, _PS, 2 * hd),
                       jnp.bfloat16)
    q = jnp.asarray(rs.randn(rows, 2, heads, hd), jnp.bfloat16)
    new = jnp.asarray(rs.randn(rows, 2, kvh, 2 * hd), jnp.bfloat16)
    tables = (rs.permutation(rows * mp).reshape(rows, mp) + 1).astype(
        "int32")
    if live is not None:
        live = np.asarray(live, bool)
        tables[1] = 0
    kw = dict(layer=1, window=window,
              live=None if live is None else jnp.asarray(live))
    return (pool, q, new, tables, jnp.asarray(lengths, jnp.int32), live, kw)


@pytest.mark.parametrize("case", list(_VERIFY_CASES))
def test_two_queries_a_row_are_one_query_a_row_twice(case):
    """The XLA form (the kernel's oracle) at ``T = 2`` against itself at
    ``T = 1`` twice, the second call on the pool the first left: outputs
    and pool bit for bit.  So the two positions of a step are causal
    between them and each counts its window from its own position."""
    import numpy as np
    from ray_tpu.ops import paged_attention as pa
    pool, q, new, tables, lengths, _, kw = _verify_case(case)
    got, got_pool = pa.paged_attention_xla(q, pool, tables, lengths,
                                           new_rows=new, **kw)
    one, mid = pa.paged_attention_xla(q[:, 0], pool, tables, lengths - 1,
                                      new_rows=new[:, 0], **kw)
    two, end = pa.paged_attention_xla(q[:, 1], mid, tables, lengths,
                                      new_rows=new[:, 1], **kw)
    np.testing.assert_array_equal(np.asarray(got[:, 0], np.float32),
                                  np.asarray(one, np.float32))
    np.testing.assert_array_equal(np.asarray(got[:, 1], np.float32),
                                  np.asarray(two, np.float32))
    np.testing.assert_array_equal(np.asarray(got_pool, np.float32),
                                  np.asarray(end, np.float32))


@pytest.mark.parametrize("case", list(_VERIFY_CASES))
def test_the_kernel_at_two_queries_in_the_tpu_interpreter(tpu_interpreter,
                                                          case):
    """The Pallas kernel at ``T = 2`` against the XLA form: the output
    within the kernel's tolerance; the returned pool BIT for bit the
    oracle's on the pages of every kept row's two new positions and,
    everywhere else (other layers, other pages, a dead row's; scratch
    page 0 left out), the pool that went in."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import paged_attention as pa
    pool, q, new, tables, lengths, live, kw = _verify_case(case)
    got, got_pool = pa.paged_attention_tpu(q, pool, tables, lengths,
                                           new_rows=new, **kw)
    want, want_pool = pa.paged_attention_xla(q, pool, tables, lengths,
                                             new_rows=new, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)

    def bits(x):
        return np.array(jax.lax.bitcast_convert_type(x, jnp.uint16))

    got_bits, want_bits, expect = bits(got_pool), bits(want_pool), bits(pool)
    kept = np.flatnonzero(np.ones(len(lengths), bool) if live is None
                          else live)
    for r in kept:
        for page in {tables[r, (int(lengths[r]) - back) // _PS]
                     for back in (2, 1)}:
            assert (want_bits[1, page] != expect[1, page]).any()
            expect[1, page] = want_bits[1, page]
    np.testing.assert_array_equal(got_bits[:, 1:], expect[:, 1:])


@pytest.mark.parametrize("window", [None, 12], ids=["global", "window"])
def test_a_rejected_draft_s_row_is_overwritten(window):
    """A step at p wrote rows p and p + 1; its draft fell, so the next
    step stands at p + 1 and writes p + 1 and p + 2.  With the stale row
    at p + 1 POISONED (NaN), that step's outputs and the pool it leaves
    are what a clean pool gives: nothing reads a rejected draft's row
    before it is rewritten."""
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import paged_attention as pa
    rs = np.random.RandomState(4)
    pool = jnp.asarray(rs.randn(2, 9, 2, 8, 32), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    q = jnp.asarray(rs.randn(2, 2, 4, 16), jnp.float32)
    new = jnp.asarray(rs.randn(2, 2, 2, 32), jnp.float32)
    stands_at = np.asarray([13, 23])              # p + 1; 23: a page's last
    lengths = jnp.asarray(stands_at + 2)
    poisoned = pool
    for r, pos in enumerate(stands_at):
        poisoned = poisoned.at[:, tables[r, pos // 8], :, pos % 8].set(
            jnp.nan)
    clean = pa.paged_attention(q, pool, tables, lengths, new_rows=new,
                               layer=1, window=window)
    got = pa.paged_attention(q, poisoned, tables, lengths, new_rows=new,
                             layer=1, window=window)
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(clean[0]))
    np.testing.assert_array_equal(np.asarray(got[1][1]),
                                  np.asarray(clean[1][1]))


def test_the_reference_s_wide_feed_forward_in_blocks_is_the_one_pass(
        monkeypatch):
    """``_swiglu`` over blocks of the hidden units (the published dense
    layer's 18432) against the one pass."""
    import jax
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    z = jax.random.normal(ks[0], (9, 16))
    wg, wu = (jax.random.normal(k, (16, 96)) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (96, 16))
    whole = ref._swiglu(z, wg, wu, wd, None)
    monkeypatch.setattr(ref, "F_BLOCK", 32)
    np.testing.assert_allclose(ref._swiglu(z, wg, wu, wd, None), whole,
                               rtol=1e-5, atol=1e-4)


# ---- one chip's share of an expert layer ----

def test_the_eight_shares_of_the_reference_add_up_to_the_uncut_layer(parts):
    """The reference's ``held_experts`` for each of 8 chips holding one
    expert of a layer's 8, summed, is the uncut layer's routed sum; the
    shared expert is outside it, counted once by whoever adds the two
    (``models/gpt.py Block``: ``tests/test_latent_attention.py`` holds
    the program's shares to the program's uncut block, for this preset
    too)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_k_exaone as ref
    _, _, weights, published = parts
    w = jax.tree.map(lambda a: a[2].astype(jnp.float32), weights["layers"])
    z = jax.random.normal(jax.random.PRNGKey(0), (21, 64))
    c = ref.route(z @ w["router"], w["bias"], 3, 2.5)
    assert (np.asarray(c) > 0).sum() == 21 * 3
    uncut = ref.held_experts(z, c, w, 0, 8)
    shares = sum(ref.held_experts(
        z, c, jax.tree.map(lambda a: a[chip:chip + 1], {
            k: w[k] for k in ("w_gate", "w_up", "w_down")}), chip, 1)
        for chip in range(8))
    np.testing.assert_allclose(shares, uncut, atol=2e-5, rtol=1e-5)
    assert float(jnp.abs(uncut).max()) > 1e-2
