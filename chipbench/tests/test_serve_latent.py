"""The ``serve_latent`` runner and what it finds by name, on the CPU at
the tiny size: the configuration file against the catalog, the adapter,
the reference's wrong-on-purpose variants each moving its own reading
and no other, the runner end to end with a toy configuration and mix
passed in directly (``rehearsal.json`` is not theirs to edit), the byte
function against hand-worked numbers, and each new reader on a hand-made
record."""

import dataclasses
import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

TOY = {
    "source": "none: a toy of the Kanana-2 blocks for CPU tests only",
    "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_head_dim": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 32, "n_routed_experts": 3,
    "experts_held_first": 2, "published": {"n_routed_experts": 8},
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "routed_scaling_factor": 2.448,
    "program": {"preset": "tiny-kanana",
                "adapter": "chipbench.lib.arch_kanana2",
                "reference": "chipbench.lib.reference_kanana2"}}

TOY_MIX = {
    "kind": "serve_latent", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 10, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 14},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0, "prefill_wave_tokens": 64},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {
        "max_new_tokens": 8, "faults_in": "first",
        "limits": {"hidden_rel_err": [None, 1e-4],
                   "served_token_agree_share": [1.0, None],
                   "router_rel_err": [None, 1e-5],
                   "latent_kernel_rel_err": [None, 1e-4],
                   "latent_kernel_dead_rows_zero": [1, None],
                   "bias_in_gates_projection": [None, 0.01],
                   "absent_experts_added_projection": [None, 0.01]},
        "controls": {"fp8_control": "hidden_rel_err",
                     "latent_kernel_control": "latent_kernel_rel_err"}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key;
    the depth and the experts held alone reduced, with the published
    counts and the deployment stated."""
    cfg = _real_config()
    catalog = os.environ.get(
        "MODEL_CATALOG",
        "/opt/skills/guides/model-configs/architectures.jsonl")
    want = {"attention_bias": False, "first_k_dense_replace": 1,
            "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "kv_lora_rank": 512,
            "max_position_embeddings": 32768, "model_type": "deepseek_v3",
            "moe_intermediate_size": 768, "moe_layer_freq": 1,
            "n_group": 1, "n_shared_experts": 2, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_experts_per_tok": 6,
            "num_key_value_heads": 32, "q_lora_rank": None,
            "qk_head_dim": 192, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
            "rope_interleave": True, "rope_scaling": None,
            "rope_theta": 1000000, "routed_scaling_factor": 2.448,
            "scoring_func": "sigmoid", "tie_word_embeddings": False,
            "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
            "vocab_size": 128256}
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        assert {k: v for k, v in row["config"].items()
                if k not in cfg["reduced"]} == want
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (16, 16)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128}
    assert cfg["experts_held_first"] == 0
    assert cfg["deployment"].startswith("8 chips share each layer")
    assert len(cfg["assumed"]) >= 5


def test_adapter_makes_the_preset_the_configuration():
    from chipbench.lib import arch_kanana2
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_kanana2.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    assert (model.n_layers, model.d_model, model.head_dim) == (16, 2048, 192)
    assert (model.moe_experts, model.experts_here, model.moe_top_k) == (
        128, 16, 6)
    assert model.cache_row_width == 640 and model.first_dense_layers == 1
    # ISSUE 37's table: attention 26.35 M, an expert layer 111.6 M here
    assert model._attn_params() == 26_345_984
    assert round(2 * model.num_params() / 1e9, 2) == 4.53
    for wrong in ({"scoring_func": "softmax"}, {"q_lora_rank": 1536},
                  {"n_group": 8}, {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(SystemExit):
            arch_kanana2.model_overrides(dict(cfg, **wrong))


def test_a_program_without_the_fields_is_refused_at_once(monkeypatch):
    """The parent commit: ``TransformerConfig`` lacks what the adapter
    sets.  The refusal comes from ``run`` before a schedule is drawn or
    a cluster started, as a clean exit that names the fields."""
    import ray_tpu.models.configs as configs
    from chipbench.runners import serve_latent

    @dataclasses.dataclass
    class Older:
        vocab_size: int = 0
        moe_experts: int = 0
    monkeypatch.setattr(configs, "TransformerConfig", Older)
    with pytest.raises(SystemExit, match="kv_lora_rank"):
        serve_latent.run({"cell": {}, "config": TOY, "mix": TOY_MIX})
    monkeypatch.undo()
    with pytest.raises(SystemExit, match="preset 'nope' missing"):
        serve_latent.run({"cell": {}, "mix": TOY_MIX, "config": dict(
            TOY, program=dict(TOY["program"], preset="nope"))})


@pytest.fixture(scope="module")
def toy():
    """``(cfg, params, tokens)``: the toy through the adapter."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_kanana2
    from ray_tpu.models import GPT, get_config
    ov = arch_kanana2.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 40))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    return cfg, params, tokens


def test_reference_agrees_with_the_program_through_the_adapter(toy):
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import reference_kanana2 as ref
    from ray_tpu.models import GPT
    cfg, params, tokens = toy
    assert (cfg.moe_experts, cfg.experts_here, cfg.moe_held_first) == (
        8, 3, 2)
    want = GPT(cfg).apply({"params": params}, jnp.asarray(tokens))[0]
    got = ref.logits(ref.from_program_params(params), tokens[0], TOY)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_each_fault_moves_its_own_reading_and_no_other(toy):
    """``hidden_check`` with a WRONG reference standing in for the
    program: the projection onto that fault reads 1 and onto no other
    fault does (a large fault casts a shadow on a small one's step, a
    projection being a quotient by that step's size: ``no_route_scale``
    reads 1.25 on ``bias_in_gates``, whose control is 0.07; but only a
    fault's OWN reading is 1); the right reference reads 0 on all; every
    control is well away from 0 (a fault that moved nothing would prove
    nothing)."""
    import jax.numpy as jnp
    from chipbench.lib import reference_kanana2 as ref
    cfg, params, tokens = toy
    weights = ref.from_program_params(params)
    seq = jnp.asarray(tokens[0])
    right = ref.hidden_check(weights, seq, ref.hidden(weights, seq, TOY),
                             TOY)
    assert right["hidden_rel_err"] < 1e-6
    assert right["fp8_control"] > 0.03
    for fault in ref.FAULTS:
        assert abs(right[f"{fault}_projection"]) < 1e-4
        assert right[f"{fault}_control"] > 0.01, fault
    for fault in ref.FAULTS:
        wrong = ref.hidden_check(
            weights, seq, ref.hidden(weights, seq, TOY, fault=fault), TOY)
        assert wrong[f"{fault}_projection"] == pytest.approx(1.0, abs=1e-4)
        assert wrong["hidden_rel_err"] > 0.01
        for other in ref.FAULTS:
            if other != fault:
                assert abs(wrong[f"{other}_projection"] - 1) > 0.2, (
                    fault, other)


def test_the_numeric_check_tells_a_wrong_program_from_the_right_one(toy):
    """``lib/replica_latent.py``'s readings through a tiny paged engine
    on the CPU in float32, where the arithmetic is exact: the program as
    it is reads zeros; a program with another routing scale, without the
    shared experts, or with its router rounded to bfloat16 is each caught
    by the reading made for it."""
    import flax.linen as nn
    import jax.numpy as jnp
    import ray_tpu.ops.moe as moe
    from chipbench.lib import reference_kanana2 as ref
    from chipbench.lib import replica_latent
    from ray_tpu.models import GPT
    from ray_tpu.serve.llm_engine import LLMEngine
    cfg, params, tokens = toy
    eng = LLMEngine(cfg, params, num_slots=2, page_size=4, max_seq_len=64,
                    max_prompt_len=32, block_size=4, min_prefill_bucket=4)
    try:
        prompt = [int(t) for t in tokens[0][:13]]
        served = eng.submit(prompt, max_new_tokens=20,
                            temperature=0.0).tokens
        (good,) = replica_latent.LatentBenchLLMServer.bench_reference(
            types.SimpleNamespace(engine=eng),
            [{"prompt": prompt, "tokens": served}], TOY)
        assert good["context"] == 33 and good["bucket"] == 16
        assert good["hidden_rel_err"] < 1e-5
        assert good["served_token_agree_share"] == 1.0
        assert good["router_rel_err"] < 1e-6
        assert good["router_rows"] == 3 * 32          # 3 expert layers
        assert good["latent_kernel_rel_err"] < 1e-5
        assert good["latent_kernel_control"] > 0.01
        assert good["latent_kernel_dead_rows_zero"] == 1
        for fault in ref.FAULTS:
            assert abs(good[f"{fault}_projection"]) < 1e-3, fault
        assert good["fp8_control"] > 0.03

        weights = ref.from_program_params(eng.params)
        seq = (prompt + served)[:-1]

        def wrong_program(**replaced):
            model = GPT(dataclasses.replace(cfg, **replaced), decode=True,
                        paged_pages=eng.kv_pool_pages,
                        page_size=eng.page_size)
            got = replica_latent.program_hidden(eng, seq, len(prompt), model)
            return got, ref.hidden_check(weights, jnp.asarray(seq),
                                         got["hidden"], TOY)
        _, bad = wrong_program(moe_route_scale=1.0)
        assert bad["no_route_scale_projection"] > 0.99
        assert bad["hidden_rel_err"] > 0.01
        real = moe.DroplessMoE.router_logits

        def router_logits(self, h):          # rounds what it returns
            return self.router(h.astype(jnp.float32)).astype(
                jnp.bfloat16).astype(jnp.float32)
        moe.DroplessMoE.router_logits = nn.module.wrap_method_once(
            router_logits)
        try:
            got, _ = wrong_program()
        finally:
            moe.DroplessMoE.router_logits = real
        assert ref.router_check(weights, got["router_in"],
                                got["router_out"])["router_rel_err"] > 1e-3
    finally:
        eng.close()


def test_mla_bytes_by_hand():
    from chipbench.lib import mla_bytes
    from chipbench.lib.peaks import peaks_for
    cfg = _real_config()
    assert mla_bytes.row_bytes(cfg) == 1152                  # (512 + 64) x 2
    # a head: 576 for the score, 512 for the value, two operations each
    assert mla_bytes.decode_token_flops(cfg) == 32 * (576 + 512) * 2 == 69_632
    peaks = peaks_for("TPU v5 lite")
    # 24 rows of 5,000 cached tokens: 138 MB, 0.169 ms at 819 GB/s; the
    # products at the peak would take a quarter of that: the bytes bound
    assert mla_bytes.decode_seconds(cfg, 24 * 5000, peaks) == pytest.approx(
        138_240_000 / 819e9)
    assert 69_632 / 197e12 < 1152 / 819e9


def _record(**serve):
    return {"config": _real_config(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_mla_readers_on_a_hand_made_record():
    from chipbench.metrics import (latent_prefill_attend_share,
                                   mla_context_tokens_mean,
                                   mla_roofline_share)
    # the counters over the WINDOW: 100,000 cached positions a layer step
    whole = _record(
        stats0={"mla_layer_steps": 16, "mla_context_tokens": 1_000},
        stats1={"mla_layer_steps": 1_616,
                "mla_context_tokens": 160_001_000})
    assert mla_context_tokens_mean.read(whole) == 100_000
    run = _record(stats0={}, stats1={})
    assert mla_context_tokens_mean.read(run) is None          # the parent
    # the counters over the TRACED interval, as the runner snapshots them
    run["traced"] = {
        "stats0": {"mla_layer_steps": 160, "mla_context_tokens": 0},
        "stats1": {"mla_layer_steps": 1760,
                   "mla_context_tokens": 160_000_000}}
    run["trace"] = {"busy_s": 4.0}
    run["mla_trace"] = {"kernel": {"runs": 2000.0, "seconds": 0.5},
                        "prefill_attend_s": 1.0}
    # 2000 layer steps x 100,000 rows x 1,152 B at 819 GB/s = 0.2813 s
    assert mla_roofline_share.read(run) == pytest.approx(0.5626, rel=1e-3)
    assert latent_prefill_attend_share.read(run) == 0.25
    # a parent without the counters, a trace without the operations
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["mla_trace"] = {"busy_s": 1.0}, {}
    assert mla_roofline_share.read(bare) is None
    assert latent_prefill_attend_share.read(bare) is None
    assert mla_roofline_share.read({"device": {"platform": "cpu"}}) is None


def test_mla_trace_recognises_the_scores_and_nothing_else():
    from chipbench.lib import mla_trace
    rx = mla_trace.scores_pattern(_real_config())
    scores = ("%fusion.9 = f32[1,32,1024,8192]{3,2,1,0} fusion(bf16[1,1024,"
              "32,192] %q, bf16[1,8192,32,192] %k), kind=kOutput")
    value = ("%fusion.7 = bf16[1,1024,32,128]{3,2,1,0} fusion(bf16[1,32,"
             "1024,8192] %p, bf16[1,8192,32,128] %v), kind=kOutput")
    project = ("%fusion.3 = bf16[2,8192,32,192]{3,2,1,0} fusion(bf16[2,8192,"
               "2048] %y, bf16[15,2048,32,192] %wq), kind=kOutput")
    decode = ("%fusion.5 = bf16[33,32,640]{2,1,0} fusion(bf16[33,32,128] %q, "
              "bf16[512,32,256] %w), kind=kOutput")
    alone = ("%fusion.2 = f32[32,1024,7168]{2,1,0} fusion(bf16[1,1024,32,192]"
             " %q, bf16[1,7168,32,192] %k), kind=kOutput")
    assert rx.search(scores) and rx.search(value) and rx.search(alone)
    assert not rx.search(project) and not rx.search(decode)
    assert mla_trace.of({}) == {} and mla_trace.of(
        {"trace_dir": "/nonexistent", "config": _real_config()}) == {}


def test_the_mix_names_weights_contents_and_a_wave_s_tokens():
    from chipbench.runners import serve_arch, serve_latent
    with open(os.path.join(HERE, "..", "traffic", "serve-docqa.json")) as f:
        mix = json.load(f)
    cfg = _real_config()
    a, b = (serve_arch.server_args(cfg, mix, seed31) for seed31 in (7, 11))
    assert a == b and a["seed"] == mix["weights_seed"]
    assert (a["num_slots"], a["max_seq_len"], a["max_prompt_len"]) == (
        32, 10240, 8192)
    assert a["config_overrides"]["moe_experts_held"] == 16
    a, b = (serve_arch.cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert a == b
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 2304 and max(lens) <= 8192
    assert {serve_latent._bucket_of(mix)(n) for n in lens} == {4096, 8192}
    # the rehearsal's waves, cut to what one wave may carry
    pairs = serve_latent._fits(mix, serve_arch.rehearse(a, mix))
    assert pairs and all(w == 1 or b * w <= 16384 for b, w in pairs)
    assert (8192, 2) in pairs and (8192, 4) not in pairs
    # every limit's reading is one the check makes, every control too
    from chipbench.lib import reference_kanana2 as ref
    made = {"hidden_rel_err", "served_token_agree_share", "router_rel_err",
            "latent_kernel_rel_err", "latent_kernel_dead_rows_zero",
            "fp8_control", "latent_kernel_control"} | {
        f"{f}_projection" for f in ref.FAULTS}
    assert set(mix["reference"]["limits"]) <= made
    assert set(mix["reference"]["controls"]) <= made
    assert set(mix["reference"]["controls"].values()) <= set(
        mix["reference"]["limits"])


def test_runner_end_to_end_on_the_cpu():
    """``serve_latent.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window, reference on one request of each
    bucket, the record every serve reader reads."""
    from chipbench.metrics import (mla_context_tokens_mean,
                                   moe_experts_touched_mean,
                                   serve_tpot_mean_ms)
    from chipbench.runners import serve_latent

    lines = []
    record = serve_latent.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "moe_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), checks
    said = dict(lines)
    assert all(w == 1 or b * w <= 64 for b, w in said["replica"]["pairs"])
    done = said["serve_done"]
    assert {m["which"] for m in done["reference"]} == {"b16", "b32"}
    first, second = done["reference"]
    assert set(TOY_MIX["reference"]["limits"]) <= set(first)
    # the second sample runs the readings that need no wrong reference
    assert "bias_in_gates_projection" not in second
    assert second["hidden_rel_err"] < 1e-5 and "fp8_control" not in second
    assert record["compared"]["b16.hidden_rel_err"] == {
        "value": first["hidden_rel_err"], "limit": [None, 1e-4]}
    # a control is held to the far side of its reading's limit
    assert record["compared"]["control.b16.fp8_control"]["limit"] == [
        1e-4, None]
    from chipbench.run import compared
    line = compared(record)
    assert line["no_failed_request"] == {"value": 1, "limit": [1, None]}
    assert line["b32.router_rel_err"]["limit"] == [None, 1e-5]
    assert done["stats1"]["mla_layer_steps"] > 0
    assert mla_context_tokens_mean.read(record) > 0
    # held experts touched of 3
    assert 0 < moe_experts_touched_mean.read(record) <= 3
    assert serve_tpot_mean_ms.read(record) > 0
