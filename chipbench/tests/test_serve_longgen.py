"""The ``serve_kda`` runner and what it finds by name, on the CPU at the
tiny size: the configuration file against the catalog row, the adapter
and its refusals, the reference against the program through the adapter,
the 45 s schedule, the byte function by hand, each new reader and each
accepted reader the cell is listed under on a hand-made record (that
``mla_roofline_share`` takes the 7 pool layers' steps and not 27), that
``BENCHMARK.json`` lists the cell under every metric it reports, and the
runner end to end with a toy configuration and mix passed in directly
(``rehearsal.json`` is not this PR's to edit).  Run it on its own
(``tests/conftest.py`` forces eight host devices, and the runner then
finds 8 where the toy cell asks for 1)."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi-linear-48b-a3b.serve-longgen"

TOY = {
    "source": "none: a toy of the Kimi Linear layers for CPU tests only",
    "model_type": "kimi_linear", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 15,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "model_max_length": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "rope_scaling": None, "hidden_act": "silu", "mla_use_nope": True,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 15], "head_dim": 8,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14],
        "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "num_experts": 4, "published": {"num_experts": 16},
    "experts_held_first": 4, "num_experts_per_token": 3,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
    "program": {"preset": "tiny-kimi-linear",
                "adapter": "chipbench.lib.arch_kimi_linear",
                "reference": "chipbench.lib.reference_kimi_linear"}}

FAULTS = ("scalar_decay", "rotated", "silu_gate", "beta_range",
          "bias_in_gates", "no_route_scale", "no_shared_expert",
          "absent_experts_added")

TOY_MIX = {
    "kind": "serve_kda", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 9, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 14},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0, "prefill_wave_tokens": 64},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_pairs": [[32, 2], [16, 2]],
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {
        "short_max_total_tokens": 30, "long_min_prompt": 20,
        "long_max_answer": 8,
        "limits": {
            "hidden_rel_err": [None, 1e-3], "logits_rel_err": [None, 1e-3],
            "state_rel_err": [None, 1e-3], "tail_rel_err": [None, 1e-3],
            "router_rel_err": [None, 1e-4],
            "kda_kernel_rel_err": [None, 1e-4],
            "kda_chunk_rel_err": [None, 1e-4],
            "kda_chunk_state_finite": [1, None],
            "dead_rows_untouched": [1, None],
            "latent_kernel_rel_err": [None, 1e-3],
            "latent_kernel_dead_rows_zero": [1, None],
            "handover_state_rel_err": [None, 1e-3],
            "handover_tail_rel_err": [None, 1e-3],
            "served_token_agree_share": [1.0, None],
            **{f + "_projection": [None, 0.02] for f in FAULTS}},
        "controls": {
            "fp8_control": "hidden_rel_err",
            "logits_fp8_control": "logits_rel_err",
            "state_control": "state_rel_err",
            "tail_control": "tail_rel_err",
            "router_bf16_control": "router_rel_err",
            "kda_kernel_bf16_state_control": "kda_kernel_rel_err",
            "kda_kernel_scalar_decay_control": "kda_kernel_rel_err",
            "kda_chunk_bf16_state_control": "kda_chunk_rel_err",
            "kda_chunk_scalar_decay_control": "kda_chunk_rel_err",
            "latent_kernel_control": "latent_kernel_rel_err",
            "handover_state_control": "handover_state_rel_err",
            "handover_tail_control": "handover_tail_rel_err"}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def _real_mix():
    with open(os.path.join(HERE, "..", "traffic",
                           "serve-longgen.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key_but_the_share():
    """The catalog row's ``config`` (model-configs guide) key for key;
    ``num_experts`` alone reduced, to one of sixteen chips' share."""
    cfg = _real_config()
    assert cfg["reduced"] == ["num_experts"]
    assert cfg["num_experts"] == 16 and cfg["published"] == {
        "num_experts": 256} and cfg["experts_held_first"] == 0
    want = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    assert {k: cfg[k] for k in want} == want
    lin = cfg["linear_attn_config"]
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(lin["kda_layers"]) == 20 and (
        lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]
    ) == (128, 32, 4)
    assert len(cfg["assumed"]) >= 5 and "v5e-16" in cfg["deployment"]
    assert cfg["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")


def test_adapter_makes_the_preset_the_configuration_and_refuses():
    import dataclasses
    from chipbench.lib import arch_kimi_linear
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_kimi_linear.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    # the preset IS the configuration: the overrides change the share
    # and the weights' dtype and nothing else
    preset = get_config(cfg["program"]["preset"])
    assert {f.name for f in dataclasses.fields(model)
            if getattr(model, f.name) != getattr(preset, f.name)} == {
        "moe_experts_held", "param_dtype"}
    assert model.num_params() == 4_956_660_608
    assert model.runs == (4, 5, 3) and model.rope_theta is None
    assert [i for i, k in enumerate(model.layer_types)
            if k == "full_attention"] == [3, 7, 11, 15, 19, 23, 26]
    for wrong in ({"mla_use_nope": False}, {"q_lora_rank": 1536},
                  {"num_expert_group": 8},
                  {"moe_router_activation_func": "softmax"},
                  {"num_nextn_predict_layers": 1}):
        with pytest.raises(SystemExit, match="cannot express"):
            arch_kimi_linear.model_overrides(dict(cfg, **wrong))
    lin = dict(cfg["linear_attn_config"], kda_layers=[1, 2, 3])
    with pytest.raises(SystemExit, match="every layer once"):
        arch_kimi_linear.model_overrides(dict(cfg, linear_attn_config=lin))


def test_the_parent_program_is_refused_before_anything_starts():
    """``_refuse_unknown`` on a program that lacks the preset or a field
    (what the parent commit is to this cell: it has neither the preset
    nor ``layer_period`` / ``linear_gate_rank``); the adapter itself
    imports nothing from the program."""
    from chipbench.lib import arch_kimi_linear
    from chipbench.runners.serve_arch import _refuse_unknown
    ov = arch_kimi_linear.model_overrides(_real_config())
    _refuse_unknown("kimi-linear-48b-a3b", ov)      # this program: fine
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("kimi-linear-48b-a3b", dict(ov, no_such_field=1))
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("no-such-preset", ov)
    with open(arch_kimi_linear.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_reference_agrees_with_the_program_through_the_adapter():
    """The toy's published keys -> the adapter -> the program's forward,
    against the reference reading the same keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_kimi_linear
    from chipbench.lib import reference_kimi_linear as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(TOY["program"]["preset"], **arch_kimi_linear
                     .model_overrides(TOY, {"dtype": "float32"}))
    assert cfg.runs == (4, 2, 3) and cfg.experts_here == 4
    params = GPT(cfg).init(jax.random.PRNGKey(2),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    seq = np.random.default_rng(0).integers(1, 256, 29)
    want = ref.logits(ref.from_program_params(params), seq, TOY)
    got = GPT(cfg).apply({"params": params}, jnp.asarray(seq)[None])[0]
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_the_45_s_schedule_is_the_mix_s_own():
    """Poisson arrivals at the mix's rate, prompts and answers inside
    their clips, the same schedule for every ``--seed`` (draw_seed), six
    prefill buckets, and a document now and then."""
    from chipbench.lib import traffic
    from chipbench.runners.serve import _buckets
    mix = _real_mix()
    a = traffic.serve_schedule(mix, mix["contents_seed"], 45.0, 163840)
    b = traffic.serve_schedule(mix, mix["contents_seed"], 45.0, 163840)
    assert [(r["due_s"], r["prompt"], r["max_new_tokens"]) for r in a] == [
        (r["due_s"], r["prompt"], r["max_new_tokens"]) for r in b]
    assert len(a) == round(mix["rate_per_s"] * 45)
    assert all(256 <= len(r["prompt"]) <= 8192 for r in a)
    assert all(512 <= r["max_new_tokens"] <= 4096 for r in a)
    assert all(len(r["prompt"]) + r["max_new_tokens"]
               <= mix["server"]["max_seq_len"] for r in a)
    assert _buckets(256, 8192) == [256, 512, 1024, 2048, 4096, 8192]
    server = mix["server"]
    assert (server["num_slots"], server["page_size"],
            server["kv_pool_pages"], server["prefill_wave_tokens"]) == (
        32, 64, 2048, 8192)
    assert server["prefix_cache_pages"] == 0
    assert any(len(r["prompt"]) >= mix["reference"]["long_min_prompt"]
               for r in a)


def test_bytes_by_hand():
    from chipbench.lib import kda_bytes
    cfg = _real_config()
    # 32 heads x 128 x 128 float32
    assert kda_bytes.state_bytes(cfg) == 2_097_152
    # state both ways, 3 x 12,288 bf16 tail both ways, q, k, v, the decay
    # and o over 4096 lanes float32, beta a head
    assert kda_bytes.decode_row_bytes(cfg) == 2 * 2_097_152 + 2 * 73_728 \
        + 4 * (5 * 4096 + 32) == 4_423_808
    assert kda_bytes.kda_layers(cfg) == 20
    # 20 layers: 41.9 MB of state a request, read and written every step
    assert round(20 * 2_097_152 / 1e6, 1) == 41.9


def _record(**serve):
    return {"config": _real_config(), "mix": _real_mix(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_the_chunk_form_s_tensors_are_told_from_the_rest():
    from chipbench.lib import kda_trace
    rx = kda_trace.chunk_pattern(_real_config())
    hit = lambda line: bool(rx.search(line))                  # noqa: E731
    assert hit("%fusion.1 = f32[2,32,16,64,64] fusion(...)")
    assert hit("%fusion.2 = f32[8,32,4,64,128] fusion(...)")
    assert hit("%fusion.3 = f32[2,32,16,4,16,16] fusion(...)")
    assert hit("%fusion.4 = f32[2,32,16,4,64,128] fusion(...)")
    assert hit("%fusion.5 = f32[2,32,64,128] fusion(...)")     # scan step
    assert hit("%fusion.6 = f32[2,32,128,128] fusion(...)")    # the state
    # the latent layers' scores, the projections, the head, the kernel
    assert not hit("%fusion.7 = f32[2,32,512,512] fusion(...)")
    assert not hit("%fusion.8 = bf16[2,1024,32,128] fusion(bf16[2304,32,"
                   "128] %wq)")
    assert not hit("%fusion.9 = f32[33,163840] fusion(...)")
    assert not hit("%fusion.10 = bf16[33,32,192] fusion(...)")


def test_readers_on_a_hand_made_record():
    """The four new readers, and the accepted ones that have to read
    this cell right unedited: their counters count by what a layer
    holds (20 and 7 of 27)."""
    from chipbench.metrics import (kda_prefill_chunk_share,
                                   kda_roofline_share, kda_step_device_ms,
                                   kda_time_share, kv_rows_written_mean,
                                   live_rows_mean, mla_context_tokens_mean,
                                   mla_roofline_share,
                                   moe_experts_touched_mean)
    steps = 1_000
    run = _record(
        stats0={k: 0 for k in (
            "gdn_layer_steps", "gdn_state_rows", "mla_layer_steps",
            "mla_context_tokens", "pool_layer_steps", "decode_rows_written",
            "moe_layer_steps", "moe_experts_touched")},
        stats1={"gdn_layer_steps": 20 * steps,
                "gdn_state_rows": 20 * steps * 14,
                "mla_layer_steps": 7 * steps,
                "mla_context_tokens": 7 * steps * 14 * 3000,
                "pool_layer_steps": 7 * steps,
                "decode_rows_written": 7 * steps * 14,
                "moe_layer_steps": 26 * steps,
                "moe_experts_touched": 26 * steps * 4})
    assert live_rows_mean.read(run) == 14.0
    assert mla_context_tokens_mean.read(run) == 14 * 3000
    assert kv_rows_written_mean.read(run) == 14.0
    assert moe_experts_touched_mean.read(run) == 4.0
    run["traced"] = {
        "stats0": {k: v // 10 for k, v in run["serve"]["stats1"].items()},
        "stats1": {k: v // 5 for k, v in run["serve"]["stats1"].items()}}
    run["trace"] = {"busy_s": 2.0, "self_s": {"kda_decode.3": 0.4,
                                              "fusion.9": 1.0}}
    run["spans"] = {"modules": {"engine_decode_block": {
        "count": 5, "total_s": 1.2}}}
    run["kda_trace"] = {"kernel": {"runs": 2000.0, "seconds": 0.4},
                        "prefill_s": 0.5, "prefill_chunk_s": 0.2}
    # the absorbed kernel ran 7 times a step: 700 runs in 100 steps
    run["mla_trace"] = {"kernel": {"runs": 700.0, "seconds": 0.05}}
    # 2000 runs / 20 KDA layers = 100 steps in 1.2 s
    assert kda_step_device_ms.read(run) == pytest.approx(12.0)
    assert kda_time_share.read(run) == pytest.approx(0.2)
    assert kda_prefill_chunk_share.read(run) == pytest.approx(0.4)
    # 14 rows x 2000 layer steps x 4,423,808 B at 819 GB/s = 0.15124 s
    assert kda_roofline_share.read(run) == pytest.approx(0.15124 / 0.4,
                                                         rel=1e-3)
    # 42,000 cached rows a layer step x 700 layer steps x 1,152 B at
    # 819 GB/s = 0.04135 s: by the SEVEN pool layers' steps; read over
    # 27 layers' the same trace would give 3.9 times as much
    assert mla_roofline_share.read(run) == pytest.approx(0.04135 / 0.05,
                                                         rel=1e-3)
    # a parent without the kernel, a trace without it
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["spans"], bare["kda_trace"] = (
        {"busy_s": 1.0}, {}, {})
    for reader in (kda_step_device_ms, kda_time_share, kda_roofline_share,
                   kda_prefill_chunk_share):
        assert reader.read(bare) is None
        assert reader.read({"device": {"platform": "cpu"}}) is None


def test_benchmark_json_lists_the_cell_under_every_metric_it_reports():
    """Written as "is a subset of": a later PR's metric may list the cell
    too."""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "serve-longgen", 1)
    assert len(cell["why"]) <= 200
    assert [(c["file"], c["reduced"], c["source"]) for c in bench["configs"]
            if c["name"] == cell["config"]] == [
        ("chipbench/configs/kimi-linear-48b-a3b.json", ["num_experts"],
         _real_config()["source"])]
    assert all(len(c["why"]) <= 200 for c in bench["configs"])
    assert len(bench["workloads"]) >= 9 and len(bench["configs"]) >= 8
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {
        "serve_tpot_mean_ms", "kda_step_device_ms", "kda_time_share",
        "kda_roofline_share", "kda_prefill_chunk_share", "live_rows_mean",
        "mla_roofline_share", "mla_context_tokens_mean",
        "moe_experts_touched_mean", "paged_time_share",
        "prefill_time_share", "kv_rows_written_mean",
        "block_steps_run_share", "sampler_draw_share",
        "engine_host_share"} <= lists
    # steps counted by ALL layers or by another model's kernels, bytes
    # of another layer, readers that index another family's keys
    assert not lists & {"decode_step_device_ms", "hybrid_step_device_ms",
                        "gdn_time_share", "gdn_roofline_share",
                        "moe_time_share", "moe_roofline_share",
                        "latent_prefill_attend_share",
                        "window_pages_skipped_share"}
    for m in bench["per_layer"]:
        if m["name"].startswith("kda_"):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tpot_mean_ms"
            assert os.path.exists(os.path.join(
                HERE, "..", "metrics", m["name"] + ".py"))


def test_runner_end_to_end_on_the_cpu():
    """``serve_kda.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window, reference on a short and a long
    request, the record every serve reader reads."""
    from chipbench.metrics import (live_rows_mean, mla_context_tokens_mean,
                                   serve_tpot_mean_ms)
    from chipbench.runners import serve_kda

    lines = []
    record = serve_kda.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "kda_decode_is_pallas", "experts_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), (checks, record["compared"])
    done = dict(lines)["serve_done"]
    warmed = {tuple(p) for p in dict(lines)["replica"]["pairs"]}
    assert (32, 2) in warmed
    used = done["prefill_pairs_used"]
    assert used and {(b, w) for b, w, _ in used} <= warmed
    assert {m["which"] for m in done["reference"]} == {"short", "long"}
    short = next(m for m in done["reference"] if m["which"] == "short")
    long = next(m for m in done["reference"] if m["which"] == "long")
    assert set(TOY_MIX["reference"]["limits"]) <= set(short) | set(long)
    assert set(TOY_MIX["reference"]["controls"]) <= set(short) | set(long)
    # the long one went through the engine's own programs alone
    assert "hidden_rel_err" not in long and "handover_wave" in long
    assert short["kda_layers"] == 11 and short["latent_pool_layers"] == 4
    assert record["compared"]["short.hidden_rel_err"] == {
        "value": short["hidden_rel_err"], "limit": [None, 1e-3]}
    for m in (short,):
        assert m["hidden_rel_err"] < 1e-4 and m["logits_rel_err"] < 1e-4
        assert m["state_rel_err"] < 2e-4 and m["tail_rel_err"] < 1e-4
        assert m["state_control"] > 0.5 and m["tail_control"] > 0.5
        assert m["router_rel_err"] < 1e-5
        assert m["latent_kernel_control"] > 1e-2 > m["latent_kernel_rel_err"]
    for fault in FAULTS:
        assert short[fault + "_control"] > 2e-2 > abs(
            short[fault + "_projection"]), fault
    assert short["kda_kernel_bf16_state_control"] > 1e-3 > short[
        "kda_kernel_rel_err"]
    assert short["kda_kernel_scalar_decay_control"] > 1e-2
    assert short["kda_chunk_bf16_state_control"] > 1e-3 > short[
        "kda_chunk_rel_err"]
    assert short["dead_rows_untouched"] == 1
    assert long["handover_state_control"] > 0.5 > 1e-3 > long[
        "handover_state_rel_err"]
    assert long["handover_tail_control"] > 0.5 > 1e-3 > long[
        "handover_tail_rel_err"]
    assert long["handover_token_agree_share"] == 1.0   # float32: no flips
    # a control is judged against the far side of its reading's limit
    assert record["compared"]["control.short.state_control"] == {
        "value": short["state_control"], "limit": [1e-3, None]}
    # the accepted readers of rows and cached positions read this cell
    assert done["stats1"]["gdn_layer_steps"] == 11 * done["stats1"]["steps"]
    assert done["stats1"]["mla_layer_steps"] == 4 * done["stats1"]["steps"]
    assert 0 < live_rows_mean.read(record) <= 4
    assert mla_context_tokens_mean.read(record) > 0
    assert done["load_end"]["state_entries_in_use"] == 0
    assert serve_tpot_mean_ms.read(record) > 0
