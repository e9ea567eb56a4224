"""The ``serve_hybrid`` runner and what it finds by name, on the CPU at
the tiny size: the configuration file against the catalog row, the
adapter and its refusals, the reference against the program, the numeric
check against programs made wrong on purpose, the 45 s schedule, the byte
functions by hand, each new reader on a hand-made record, and the runner
end to end with a toy configuration and mix passed in directly
(``rehearsal.json`` is not this PR's to edit)."""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmo-hybrid-7b.serve-assist"

TOY = {
    "source": "none: a toy of the Olmo-Hybrid blocks for CPU tests only",
    "model_type": "olmo_hybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "hidden_act": "silu", "max_position_embeddings": 256,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 3,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "program": {"preset": "tiny-olmo-hybrid",
                "adapter": "chipbench.lib.arch_olmo_hybrid",
                "reference": "chipbench.lib.reference_olmo_hybrid"}}

TOY_MIX = {
    "kind": "serve_hybrid", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 17, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 14},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {"short_max_total_tokens": 30, "long_min_context": 40,
                  "limits": {"hidden_rel_err": [None, 1e-4],
                             "state_dropped_projection": [None, 0.01],
                             "padding_absorbed_projection": [None, 0.01],
                             "beta_range_projection": [None, 0.01],
                             "no_decay_projection": [None, 0.01],
                             "no_conv_projection": [None, 0.01],
                             "gdn_kernel_rel_err": [None, 1e-4],
                             "gdn_kernel_f64_rel_err": [None, 1e-4],
                             "dead_rows_untouched": [1, None],
                             "served_token_agree_share": [1.0, None],
                             "handover_rel_err": [None, 1e-4],
                             "handover_state_dropped_projection":
                                 [None, 0.01]},
                  "controls": {
                      "fp8_control": "hidden_rel_err",
                      "gdn_kernel_bf16_state_control": "gdn_kernel_rel_err",
                      "gdn_kernel_bf16_held_control":
                          "gdn_kernel_rel_err"}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def _real_mix():
    with open(os.path.join(HERE, "..", "traffic", "serve-assist.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key,
    ``num_hidden_layers`` alone reduced; ``layer_types`` kept whole."""
    cfg = _real_config()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 12
    assert cfg["published"]["num_hidden_layers"] == 32
    want = {"model_type": "olmo_hybrid", "vocab_size": 100352,
            "hidden_size": 3840, "intermediate_size": 11008,
            "num_attention_heads": 30, "num_key_value_heads": 30,
            "hidden_act": "silu", "max_position_embeddings": 65536,
            "attention_bias": False, "rms_norm_eps": 1e-6,
            "tie_word_embeddings": False,
            "layer_types": (["linear_attention"] * 3
                            + ["full_attention"]) * 8,
            "linear_num_key_heads": 30, "linear_num_value_heads": 30,
            "linear_key_head_dim": 96, "linear_value_head_dim": 192,
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "rope_parameters": {"rope_theta": None}}
    assert {k: cfg[k] for k in want} == want
    assert cfg["assumed"] and cfg["deployment"]


def test_adapter_makes_the_preset_the_configuration_and_refuses():
    from chipbench.lib import arch_olmo_hybrid
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_olmo_hybrid.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    assert (model.n_layers, model.d_model, model.head_dim) == (12, 3840, 128)
    assert model.period == ("linear_attention",) * 3 + ("full_attention",)
    assert model.rope_theta is None and model.qk_norm and model.post_norm
    # three periods and the embeddings: 6.54 GB of bf16 weights
    assert model.num_params() == 9 * 215_570_172 + 3 * 185_809_920 \
        + 2 * 100352 * 3840 + 3840
    assert round(2 * model.num_params() / 1e9, 2) == 6.54
    for wrong in ({"hidden_act": "gelu"}, {"attention_bias": True},
                  {"rope_parameters": {"rope_theta": 10000.0}},
                  {"layer_types": ["sliding_attention"] * 32}):
        with pytest.raises(SystemExit):
            arch_olmo_hybrid.model_overrides(dict(cfg, **wrong))


def test_the_parent_program_is_refused_before_anything_starts():
    """``_refuse_unknown`` on a program that lacks the preset or a field
    (what the parent commit is to this cell)."""
    from chipbench.lib import arch_olmo_hybrid
    from chipbench.runners.serve_arch import _refuse_unknown
    ov = arch_olmo_hybrid.model_overrides(_real_config())
    _refuse_unknown("olmo-hybrid-7b", ov)              # this program: fine
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("olmo-hybrid-7b", dict(ov, no_such_field=1))
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("no-such-preset", ov)


def test_reference_agrees_with_the_program_through_the_adapter():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_olmo_hybrid
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config

    ov = arch_olmo_hybrid.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 70))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    want = GPT(cfg).apply({"params": params}, jnp.asarray(tokens))[0]
    got = ref.logits(ref.from_program_params(params), tokens[0], TOY)
    assert got.dtype == jnp.float32
    # float32 rounding through 8 layers, logits of size ~4
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)


def test_the_reference_s_shared_program_changes_no_number():
    """The check runs every reference of a request at ONE padded length
    through one compiled program a layer kind (the faults are operands,
    not constants).  Token 0 after the sequence's end changes no row
    before it, and the recurrence that starts again at a position is
    two recurrences from zeros."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_olmo_hybrid
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config

    ov = arch_olmo_hybrid.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (37,))
    weights = ref.from_program_params(GPT(cfg).init(
        jax.random.PRNGKey(1), jnp.asarray(tokens[None]))["params"])
    for wrong in ({}, {"reset_at": 20}, {"absorb": (20, 12)},
                  {"no_conv": True}):
        plain = ref.hidden(weights, tokens, TOY, **wrong)
        padded = ref.hidden(weights, tokens, TOY, pad_to=64, **wrong)
        assert plain.shape == padded.shape == (37, 64), wrong
        # float32 sums taken in another order at another length, through
        # 8 layers, on values of size ~2 (a reference made wrong differs
        # from the right one by 0.1 and more of a row's size)
        np.testing.assert_allclose(padded, plain, atol=2e-4, err_msg=wrong)

    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, k = (unit(jax.random.normal(key, (30, 4, 8))) for key in ks[:2])
    v = jax.random.normal(ks[2], (30, 4, 32))
    alpha = jax.random.uniform(ks[3], (30, 4), minval=0.5)
    beta = 2 * jax.random.uniform(ks[4], (30, 4))
    zero = jnp.zeros((4, 8, 32))
    o, _ = ref.recurrence(q, k, v, alpha, beta, zero, reset_at=jnp.int32(11))
    o1, _ = ref.recurrence(q[:11], k[:11], v[:11], alpha[:11], beta[:11],
                           zero)
    o2, _ = ref.recurrence(q[11:], k[11:], v[11:], alpha[11:], beta[11:],
                           zero)
    np.testing.assert_array_equal(o, jnp.concatenate([o1, o2]))
    never, _ = ref.recurrence(q, k, v, alpha, beta, zero,
                              reset_at=jnp.int32(ref.NEVER))
    np.testing.assert_array_equal(
        never, ref.recurrence(q, k, v, alpha, beta, zero)[0])


def test_the_numeric_check_tells_a_wrong_program_from_the_right_one():
    """``lib/replica_hybrid.py``'s readings through a tiny paged engine
    on the CPU in float32: the program as it is reads zeros, every
    control lies far from zero, and a program that forgets the prompt's
    real length, or loses the state between prefill and decode, is
    caught by the reading made for it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_olmo_hybrid, replica_hybrid
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config
    from ray_tpu.serve.llm_engine import LLMEngine

    ov = arch_olmo_hybrid.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 13))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    eng = LLMEngine(cfg, params, num_slots=2, page_size=4, max_seq_len=64,
                    max_prompt_len=32, block_size=4, min_prefill_bucket=4)
    try:
        prompt = [int(t) for t in tokens[0]]
        served = eng.submit(prompt, max_new_tokens=30,
                            temperature=0.0).tokens
        (good,) = replica_hybrid.HybridBenchLLMServer.bench_reference(
            types.SimpleNamespace(engine=eng),
            [{"prompt": prompt, "tokens": served}], TOY)
        assert good["context"] == 43 and good["bucket"] == 16
        assert good["hidden_rel_err"] < 2e-5
        assert good["served_token_agree_share"] == 1.0
        assert good["gdn_kernel_rel_err"] < 1e-5
        assert good["gdn_kernel_bf16_state_control"] > 1e-3
        assert good["dead_rows_untouched"] == 1
        for fault in ("state_dropped", "padding_absorbed", "beta_range",
                      "no_decay", "no_conv"):
            assert abs(good[fault + "_projection"]) < 1e-3, fault
            assert good[fault + "_control"] > 0.02, fault
        assert good["fp8_control"] > 0.02
        # the second witnesses of the kernel reading: float64 in another
        # library (not the same arithmetic: not zero), and the kernel
        # itself with its state held in bfloat16 (the reading moves as
        # far as the reference-side control says)
        assert 0 < good["gdn_kernel_f64_rel_err"] < 1e-5
        assert good["gdn_kernel_bf16_held_control"] > 1e-3
        # the engine's own compiled programs: prefill with the packed
        # entry, install through the block's meta rows, on a dirty entry
        assert good["handover_rel_err"] < 2e-5
        assert abs(good["handover_state_dropped_projection"]) < 1e-3
        assert good["handover_state_dropped_control"] > 0.02
        assert good["handover_entry_was_dirty"] == 1
        assert good["handover_entry"] != 1 and good["handover_row"] != 0
        assert good["handover_token_agree_share"] == 1.0

        weights = ref.from_program_params(eng.params)
        seq = (prompt + served)[:-1]
        real = replica_hybrid.program_hidden(eng, seq, len(prompt))
        # a prefill that is not told the real length runs the padding
        # through the recurrence
        apply = eng.model.apply

        def forgets(variables, *args, lengths=None, **kw):
            return apply(variables, *args, **kw)
        object.__setattr__(eng.model, "apply", forgets)
        try:
            got = replica_hybrid.program_hidden(eng, seq, len(prompt))
        finally:
            object.__delattr__(eng.model, "apply")
        bad = ref.hidden_check(weights, jnp.asarray(seq), got["hidden"],
                               TOY, n_prompt=len(prompt), bucket=16)
        assert bad["padding_absorbed_projection"] > 0.99
        assert bad["hidden_rel_err"] > 10 * good["hidden_rel_err"]
        # a program that loses the state between prefill and install:
        # the prompt's rows as they are, the rest decoded from zeros
        n = len(prompt)
        for name in ("gdn_state", "gdn_conv"):
            eng._cache[name] = jnp.zeros_like(eng._cache[name])
        lost = jnp.concatenate([real["hidden"][:n], _decode_only(
            eng, seq, n)])
        bad = ref.hidden_check(weights, jnp.asarray(seq), lost, TOY,
                               n_prompt=n, bucket=16)
        assert bad["state_dropped_projection"] > 0.9
    finally:
        eng.close()


@pytest.mark.parametrize("fault", ["install_drops_the_entry",
                                   "prefill_ignores_the_entry"])
def test_the_handover_reading_catches_the_engine_s_own_plumbing(fault):
    """``engine_handover`` runs the programs the window times.  An
    engine whose decode block installs a row without its state entry,
    or whose prefill writes the prompt's state somewhere else than the
    packed column says, decodes from a state that is not the prompt's:
    both are caught by ``handover_state_dropped_projection`` (and by
    ``handover_rel_err``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_olmo_hybrid, replica_hybrid
    from chipbench.lib import reference_olmo_hybrid as ref
    from ray_tpu.models import GPT, get_config
    from ray_tpu.serve.llm_engine import LLMEngine

    ov = arch_olmo_hybrid.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 13))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    eng = LLMEngine(cfg, params, num_slots=2, page_size=4, max_seq_len=64,
                    max_prompt_len=32, block_size=4, min_prefill_bucket=4)
    try:
        prompt = [int(t) for t in tokens[0]]
        for _ in range(3):                # entries 1.. used and left dirty
            eng.submit(prompt, max_new_tokens=6, temperature=0.0)
        weights = ref.from_program_params(eng.params)

        def reading():
            own = replica_hybrid.engine_handover(eng, prompt)
            return ref.handover_check(
                weights, jnp.asarray(own["tokens"]), own["hidden"], TOY,
                n_prompt=len(prompt))
        good = reading()
        assert good["handover_rel_err"] < 2e-5
        assert abs(good["handover_state_dropped_projection"]) < 1e-3
        if fault == "install_drops_the_entry":
            block = eng._block_jit
            eng._block_jit = lambda p, c, s, meta, *rest: block(
                p, c, s, meta.at[3].set(0), *rest)
        else:
            prefill = eng._get_prefill_paged

            def elsewhere(bucket, wave):
                fn = prefill(bucket, wave)
                return lambda p, c, packed, *rest: fn(
                    p, c, packed.at[:, bucket + 2].set(1), *rest)
            eng._get_prefill_paged = elsewhere
        bad = reading()
        assert bad["handover_state_dropped_projection"] > 0.5, bad
        assert bad["handover_rel_err"] > 100 * good["handover_rel_err"], bad
    finally:
        eng.close()


def _decode_only(eng, seq, n):
    """Positions ``n..`` of ``seq`` as decode steps on whatever entry 1
    and the pages hold (the KV pages of a prefill just made; the
    recurrent leaves as the caller left them)."""
    import jax.numpy as jnp
    import numpy as np
    tables = np.zeros((eng._rows, eng.max_pages), np.int32)
    used = -(-len(seq) // eng.page_size)
    tables[0, :used] = 1 + np.arange(used)
    entries = np.zeros((eng._rows,), np.int32)
    entries[0] = 1
    out = []
    for pos in range(n, len(seq)):
        toks = np.zeros((eng._rows, 1), np.int32)
        poss = np.zeros((eng._rows, 1), np.int32)
        toks[0, 0], poss[0, 0] = seq[pos], pos
        hid, mut = eng.model.apply(
            {"params": eng.params, "cache": eng._cache}, jnp.asarray(toks),
            jnp.asarray(poss), block_tables=jnp.asarray(tables),
            return_hidden=True, mutable=["cache"],
            state_rows=jnp.asarray(entries))
        eng._cache = mut["cache"]
        out.append(hid[0, 0])
    return jnp.stack(out)


def test_the_45_s_schedule_is_the_mix_s_own():
    """Every ``--seed`` offers the same requests at the same times (the
    schedule is the mix's); only the token ids differ.  No request can
    outgrow the server, and the long reference sample's prompt is
    padded (so its ``padding_absorbed`` reading exists)."""
    from chipbench.runners.serve_arch import cell_schedule, reference_samples
    from chipbench.runners.serve_hybrid import _padded
    mix, cfg = _real_mix(), _real_config()
    a, b = (cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"])
            for r in a] == [(r["due_s"], len(r["prompt"]),
                             r["max_new_tokens"]) for r in b]
    assert a[0]["prompt"] != b[0]["prompt"]
    server = mix["server"]
    assert all(len(r["prompt"]) <= server["max_prompt_len"]
               and len(r["prompt"]) + r["max_new_tokens"]
               <= server["max_seq_len"] for r in a)
    # what the runner would pick, had every request run to its length
    recs = [{"due": r["due_s"], "prompt_len": len(r["prompt"]), "done": 1,
             "tokens": [1] * r["max_new_tokens"]} for r in a
            if _padded(len(r["prompt"]))]
    picked = {s["which"]: s for s in reference_samples(
        recs, a, mix["reference"])}
    assert set(picked) == {"short", "long"}
    assert not _padded(2048) and _padded(2047) and _padded(65)
    long = picked["long"]
    assert len(long["prompt"]) + len(long["tokens"]) \
        >= mix["reference"]["long_min_context"]


def test_state_bytes_by_hand():
    from chipbench.lib import gdn_bytes
    cfg = _real_config()
    # 30 heads x 96 x 192 float32
    assert gdn_bytes.state_bytes(cfg) == 2_211_840
    # state both ways, 3 x 11,520 bf16 tail both ways, q k v gates out
    assert gdn_bytes.decode_row_bytes(cfg) == 2 * 2_211_840 + 2 * 69_120 \
        + 4 * (2 * 2880 + 5760 + 60 + 5760)
    # 9 layers: 20.5 MB of state and tail a request
    assert round(9 * (2_211_840 + 69_120) / 1e6, 1) == 20.5
    assert gdn_bytes.decode_flops(cfg) == 7 * 552_960
    assert gdn_bytes.chunked_flops_per_token(cfg) > 0


def _record(**serve):
    return {"config": _real_config(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_hybrid_readers_on_a_hand_made_record():
    from chipbench.metrics import (gdn_roofline_share, gdn_time_share,
                                   hybrid_step_device_ms, live_rows_mean)
    whole = _record(stats0={"gdn_layer_steps": 288, "gdn_state_rows": 500},
                    stats1={"gdn_layer_steps": 29_088,
                            "gdn_state_rows": 1_152_500})
    assert live_rows_mean.read(whole) == 40.0
    run = _record(stats0={}, stats1={})
    assert live_rows_mean.read(run) is None                   # the parent
    run["traced"] = {
        "stats0": {"gdn_layer_steps": 900, "gdn_state_rows": 30_000},
        "stats1": {"gdn_layer_steps": 3_600, "gdn_state_rows": 138_000}}
    run["trace"] = {"busy_s": 4.0, "self_s": {
        "%gdn_decode.3 = (f32[65,1,5760], f32[9,73,96,5760]) custom-call":
            0.9, "%fusion.1 = bf16[65,3840] fusion": 3.1}}
    run["spans"] = {"modules": {"engine_decode_block": {
        "count": 12, "total_s": 3.3}}}
    run["gdn_trace"] = {"gdn_decode": {"runs": 2700.0, "seconds": 0.9}}
    assert gdn_time_share.read(run) == pytest.approx(0.225)
    # 2700 runs / 9 linear layers = 300 steps in 3.3 s
    assert hybrid_step_device_ms.read(run) == pytest.approx(11.0)
    # 40 rows x 2700 layer steps x 4,631,280 B at 819 GB/s = 0.6107 s
    assert gdn_roofline_share.read(run) == pytest.approx(0.6786, rel=1e-3)
    # a parent without the counters, a trace without the kernel
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["spans"], bare["gdn_trace"] = {"busy_s": 1.0}, {}, {}
    for reader in (gdn_time_share, hybrid_step_device_ms,
                   gdn_roofline_share, live_rows_mean):
        assert reader.read(bare) is None
        assert reader.read({"device": {"platform": "cpu"}}) is None


def test_benchmark_json_lists_the_cell_where_it_reports():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "serve-assist", 1)
    assert [c["file"] for c in bench["configs"]
            if c["name"] == cell["config"]] == [
        "chipbench/configs/olmo-hybrid-7b.json"]
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert lists == {
        "serve_tpot_mean_ms", "engine_ttft_p95_ms", "tpot_p95_ms",
        "paged_time_share", "prefill_time_share", "slot_wait_mean_ms",
        "engine_queue_wait_p95_ms", "engine_host_share",
        "hybrid_step_device_ms", "gdn_time_share", "gdn_roofline_share",
        "live_rows_mean"}
    # decode_step_device_ms counts steps by the paged kernel over ALL
    # layers: not for a model where 3 layers in 12 run it
    assert "decode_step_device_ms" not in lists


def test_the_warm_up_covers_the_programs_the_window_was_seen_to_run():
    """What a run of the committed mix warms (``rehearse`` of the
    committed schedule under the committed ``warm_horizon_s``, and the
    mix's ``warm_pairs``) names every prefill program that the cell's
    windows were SEEN to run on the chip (``traffic/serve-assist.
    pairs-seen.json``: the ``prefill_pairs_used`` of my chip runs, PR
    40) and the next wave size up in each bucket that formed a wave of
    two, so an edit of the mix cannot quietly drop a program the window
    needs."""
    from chipbench.runners.serve_arch import _WAVES, cell_schedule, rehearse
    from chipbench.runners.serve_hybrid import warmed_pairs
    mix, cfg = _real_mix(), _real_config()
    with open(os.path.join(HERE, "..", "traffic",
                           "serve-assist.pairs-seen.json")) as f:
        seen = json.load(f)
    assert seen["seconds"] == 45 and len(seen["runs"]) >= 3
    schedule = cell_schedule(mix, 7, seen["seconds"], cfg["vocab_size"])
    warmed = set(warmed_pairs(schedule, mix))
    assert set(rehearse(schedule, mix)) <= warmed
    used = {(b, w) for run in seen["runs"] for b, w, _ in run["pairs"]}
    assert used and used <= warmed, sorted(used - warmed)
    for b in {b for b, _ in used}:
        widest = max(w for c, w in used if c == b)
        # one wave size of margin in every bucket that formed a wave of
        # two or more (64 and 2048 formed none: ISSUE 40's exception)
        if widest > 1:
            assert (b, _WAVES[_WAVES.index(widest) + 1]) in warmed, b
    assert mix["warm_horizon_s"] >= 1.0        # two rounds of the loop


@pytest.mark.parametrize("replica", [
    "chipbench.lib.replica.BenchLLMServer",
    "chipbench.lib.replica_hybrid.HybridBenchLLMServer"])
def test_the_replica_traces_without_the_python_call_tracer(monkeypatch,
                                                           replica):
    """``bench_trace('start')`` of every replica turns the profiler's
    Python call tracer off and nothing else (PERF.md section 6, PRs 33
    and 40): one ``lib/trace.py start_trace`` for them and the trainer's
    worker."""
    import importlib

    import jax
    module, name = replica.rsplit(".", 1)
    cls = getattr(importlib.import_module(module), name)
    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, profiler_options=None: seen.update(
            dir=log_dir, options=profiler_options))
    at = cls.bench_trace(object(), "start", "/some/dir")
    assert at > 0 and seen["dir"] == "/some/dir"
    default = jax.profiler.ProfileOptions()
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == default.host_tracer_level


def test_runner_end_to_end_on_the_cpu():
    """``serve_hybrid.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window, reference on a short and a long
    request, the record every serve reader reads."""
    from chipbench.metrics import live_rows_mean, serve_tpot_mean_ms
    from chipbench.runners import serve_hybrid

    lines = []
    record = serve_hybrid.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "gdn_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), checks
    done = dict(lines)["serve_done"]
    # the prefill programs the window ran were warmed, and every wave
    # the engine counted is a call of one of them
    warmed = {tuple(p) for p in dict(lines)["replica"]["pairs"]}
    used = done["prefill_pairs_used"]
    assert used and {(b, w) for b, w, _ in used} <= warmed
    assert sum(n for _, _, n in used) == (
        done["stats1"]["prefill_waves"] - done["stats0"]["prefill_waves"])
    assert set(record["phases"]) == {
        "cluster_s", "replica_s", "warm_s", "window_s", "drained_s",
        "reference_s"}
    assert {m["which"] for m in done["reference"]} == {"short", "long"}
    long = next(m for m in done["reference"] if m["which"] == "long")
    assert set(TOY_MIX["reference"]["limits"]) <= set(long)
    assert record["compared"]["long.hidden_rel_err"] == {
        "value": long["hidden_rel_err"], "limit": [None, 1e-4]}
    assert record["compared"]["long.dead_rows_untouched"]["value"] == 1
    # a control is judged against the far side of its reading's limit
    assert record["compared"]["control.short.fp8_control"] == {
        "value": next(m for m in done["reference"]
                      if m["which"] == "short")["fp8_control"],
        "limit": [1e-4, None]}
    assert record["compared"]["control.long.gdn_kernel_bf16_held_control"][
        "limit"] == [1e-4, None]
    assert long["gdn_kernel_bf16_held_control"] > 1e-3
    assert long["handover_rel_err"] < 2e-5
    assert long["hidden_rel_err"] < 2e-5
    assert done["stats1"]["gdn_layer_steps"] > 0
    assert done["load_end"]["state_entries"] == 4 + 1 + 8
    assert done["load_end"]["state_entries_in_use"] == 0
    assert serve_tpot_mean_ms.read(record) > 0
    assert 0 < live_rows_mean.read(record) <= 4
