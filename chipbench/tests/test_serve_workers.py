"""The ``serve_ssm`` runner and what it finds by name, on the CPU at the
tiny size: the configuration file against the catalog row, the adapter
and its refusals, the reference against the program, the 45 s schedule,
the byte functions by hand, each new reader on a hand-made record, that
``BENCHMARK.json`` lists the cell under every metric it reports, and the
runner end to end with a toy configuration and mix passed in directly
(``rehearsal.json`` is not this PR's to edit)."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "nemotron-3-super-120b-a12b.serve-workers"

TOY = {
    "source": "none: a toy of the Nemotron-H layers for CPU tests only",
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 11,
    "hybrid_override_pattern": "MEMEMEMEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "n_routed_experts": 8, "experts_held_first": 4,
    "published": {"n_routed_experts": 16},
    "num_experts_per_tok": 5, "moe_intermediate_size": 32,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
    "routed_scaling_factor": 5.0,
    "program": {"preset": "tiny-nemotron-h",
                "adapter": "chipbench.lib.arch_nemotron_h",
                "reference": "chipbench.lib.reference_nemotron_h"}}

TOY_MIX = {
    "kind": "serve_ssm", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 17, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 14},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0, "prefill_wave_tokens": 64},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {"short_max_total_tokens": 30, "long_min_context": 40,
                  "limits": {"hidden_rel_err": [None, 1e-4],
                             "state_rel_err": [None, 1e-4],
                             "tail_rel_err": [None, 1e-4],
                             "router_rel_err": [None, 1e-5],
                             "paged_kernel_rel_err": [None, 1e-4],
                             "ssm_kernel_rel_err": [None, 1e-4],
                             "ssm_prefill_rel_err": [None, 1e-4],
                             "dead_rows_untouched": [1, None],
                             "served_token_agree_share": [1.0, None],
                             "state_dropped_projection": [None, 0.01],
                             "padding_absorbed_projection": [None, 0.01],
                             "no_skip_projection": [None, 0.01]},
                  "controls": {
                      "fp8_control": "hidden_rel_err",
                      "state_other_layer_control": "state_rel_err",
                      "tail_other_layer_control": "tail_rel_err",
                      "ssm_kernel_bf16_state_control": "ssm_kernel_rel_err",
                      "ssm_prefill_bf16_state_control":
                          "ssm_prefill_rel_err",
                      "router_bf16_control": "router_rel_err",
                      "paged_kernel_control": "paged_kernel_rel_err"}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


def _real_mix():
    with open(os.path.join(HERE, "..", "traffic",
                           "serve-workers.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key;
    depth, pattern, experts held and vocabulary alone reduced, each
    beside its published value."""
    cfg = _real_config()
    assert cfg["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"]["num_hidden_layers"] == 88
    assert cfg["published"]["n_routed_experts"] == 512
    assert cfg["published"]["vocab_size"] == 131072
    pattern = cfg["published"]["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (88, 40, 40, 8)
    assert pattern[27:38] == cfg["hybrid_override_pattern"] == "MEMEMEMEM*E"
    want = {"model_type": "nemotron_h", "hidden_size": 4096,
            "num_hidden_layers": 11, "n_routed_experts": 128,
            "vocab_size": 32768, "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "conv_kernel": 4, "chunk_size": 128, "expand": 2,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "moe_intermediate_size": 2688,
            "moe_latent_size": 1024, "num_experts_per_tok": 22,
            "moe_shared_expert_intermediate_size": 5376,
            "n_shared_experts": 1, "routed_scaling_factor": 5,
            "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5,
            "num_nextn_predict_layers": 1, "use_conv_bias": True,
            "time_step_min": 0.001, "time_step_max": 0.1,
            "intermediate_size": 2688, "max_position_embeddings": 262144}
    assert {k: cfg[k] for k in want} == want
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"]
    assert "one of 4 chips that share each layer, one of 8 stages of 11 " \
        "layers" in cfg["deployment"]


def test_adapter_makes_the_preset_the_configuration_and_refuses():
    from chipbench.lib import arch_nemotron_h
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_nemotron_h.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    assert (model.n_layers, model.d_model, model.head_dim) == (11, 4096, 128)
    assert model.layers_of("mamba2", "latent_moe", "attention_only") == 11
    assert len(model.period) == 11 and model.rope_theta is None
    assert (model.moe_experts, model.experts_here, model.moe_top_k) == (
        512, 128, 22)
    # one period and a quarter of the vocabulary: 9.30 GB of bf16 weights
    assert model.num_params() == 4_379_724_160 + 268_435_456 + 4096
    assert round(2 * model.num_params() / 1e9, 2) == 9.30
    for wrong in ({"mlp_hidden_act": "silu"}, {"attention_bias": True},
                  {"use_conv_bias": False}, {"n_group": 2},
                  {"hybrid_override_pattern": "MEMEMEMEM-E"},
                  {"hybrid_override_pattern": "MEME"}, {"expand": 4}):
        with pytest.raises(SystemExit):
            arch_nemotron_h.model_overrides(dict(cfg, **wrong))


def test_the_parent_program_is_refused_before_anything_starts():
    """``_refuse_unknown`` on a program that lacks the preset or a field
    (what the parent commit is to this cell); the adapter itself imports
    nothing from the program."""
    from chipbench.lib import arch_nemotron_h
    from chipbench.runners.serve_arch import _refuse_unknown
    ov = arch_nemotron_h.model_overrides(_real_config())
    _refuse_unknown("nemotron-3-super-120b-a12b", ov)   # this program: fine
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("nemotron-3-super-120b-a12b",
                        dict(ov, no_such_field=1))
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("no-such-preset", ov)
    with open(arch_nemotron_h.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_reference_agrees_with_the_program_through_the_adapter():
    """The toy configuration through the adapter (a SHARE of the experts:
    ids 4-11 of 16), a whole forward of the program against the
    reference given the same share: logits."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import arch_nemotron_h
    from chipbench.lib import reference_nemotron_h as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(TOY["program"]["preset"],
                     **arch_nemotron_h.model_overrides(
                         TOY, {"dtype": "float32"}))
    assert (cfg.experts_here, cfg.moe_held_first) == (8, 4)
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (27,), 0, 256)
    got = GPT(cfg).apply({"params": params}, tokens[None])[0]
    want = ref.logits(ref.from_program_params(params), tokens, TOY)
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(
        jnp.abs(want).max())
    # and the references made wrong on purpose are other functions
    weights = ref.from_program_params(params)
    right = ref.hidden(weights, tokens, TOY)
    for wrong in ({"no_skip": True}, {"reset_at": 20}, {"state_bits": 7},
                  {"router_bits": 3}, {"bits": 3}, {"absorb": (20, 5)}):
        other = ref.hidden(weights, tokens, TOY, **wrong)
        assert float(jnp.abs(other - right).max()) > 1e-4, wrong
    # a padded run changes no row before the pad
    padded = ref.hidden(weights, tokens, TOY, pad_to=32)
    assert float(jnp.abs(padded - right).max()) < 1e-5


def test_the_45_s_schedule_is_the_mix_s_own():
    """Every ``--seed`` offers the same requests at the same times with
    the same token ids (``contents_seed``), all from the held slice of
    the vocabulary; no request can outgrow the server; the runner finds a
    short and a long reference sample whose prompts are padded."""
    from chipbench.runners.serve_arch import cell_schedule, reference_samples
    from chipbench.runners.serve_hybrid import _padded
    mix, cfg = _real_mix(), _real_config()
    a, b = (cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45)
    assert a == b
    assert all(0 <= t < 32768 for r in a for t in r["prompt"])
    server = mix["server"]
    assert all(128 <= len(r["prompt"]) <= server["max_prompt_len"]
               and 128 <= r["max_new_tokens"] <= 3072
               and len(r["prompt"]) + r["max_new_tokens"]
               <= server["max_seq_len"] for r in a)
    recs = [{"due": r["due_s"], "prompt_len": len(r["prompt"]), "done": 1,
             "tokens": [1] * r["max_new_tokens"]} for r in a
            if _padded(len(r["prompt"]))]
    picked = {s["which"]: s for s in reference_samples(
        recs, a, mix["reference"])}
    assert set(picked) == {"short", "long"}
    long = picked["long"]
    assert len(long["prompt"]) + len(long["tokens"]) >= 2048


def test_bytes_by_hand():
    from chipbench.lib import ssm_bytes
    cfg = _real_config()
    # 128 heads x 64 x 128 float32
    assert ssm_bytes.state_bytes(cfg) == 4_194_304
    # state both ways, 3 x 10,240 bf16 tail both ways, delta u, the
    # decay and y over 8192 lanes, B and C of 8 x 128, float32
    assert ssm_bytes.decode_row_bytes(cfg) == 2 * 4_194_304 + 2 * 61_440 \
        + 4 * (3 * 8192 + 2 * 1024)
    # 5 layers: 21.0 MB of state a request, read and written every step
    assert round(5 * 4_194_304 / 1e6, 1) == 21.0
    assert ssm_bytes.decode_flops(cfg) == 5 * 1_048_576
    # an expert's two matrices of 1024 x 2688, bf16
    assert ssm_bytes.expert_bytes(cfg) == 2 * 5_505_024
    # shared 2 x 4096 x 5376, projections 2 x 4096 x 1024, router
    # 4096 x 512, bf16
    assert ssm_bytes.layer_step_bytes(cfg) == 2 * (
        44_040_192 + 8_388_608 + 2_097_152)
    assert ssm_bytes.latent_moe_bytes(cfg, 105 * 10, 10) == \
        1050 * 11_010_048 + 10 * 109_051_904


def _record(**serve):
    return {"config": _real_config(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_trace_patterns_tell_the_layers_apart():
    from chipbench.lib import ssm_trace
    pats = ssm_trace.patterns(_real_config())
    kind = lambda line: next(                                 # noqa: E731
        (k for k, rx in pats.items() if rx.search(line)), None)
    assert kind("%ssm_decode.3 = (f32[65,1,8192], f32[5,73,128,8192]) "
                "custom-call(...)") == "ssm_kernel"
    assert kind("%moe_experts_decode.2 = bf16[80,1024] custom-call(s32[128] "
                "%a, bf16[1,128,1024,2688] %w)") == "experts"
    assert kind("%ragged-dot-none.4 = bf16[90112,2688] custom-call("
                ")") == "experts"
    assert kind("%fusion.7 = f32[4,128,128,128] fusion(f32[4,128,128] "
                "%g)") == "ssm_scan"
    assert kind("%fusion.8 = f32[8,4,128,128,64] fusion()") == "ssm_scan"
    assert kind("%fusion.9 = bf16[65,18560] fusion(bf16[65,4096] %x, "
                "bf16[1,4096,18560] %w)") == "ssm_proj"
    assert kind("%fusion.10 = f32[65,512] fusion(f32[65,4096] %x, "
                "bf16[1,4096,512] %w)") == "moe_dense"
    assert kind("%fusion.11 = bf16[65,4096] fusion(bf16[65,5376] %h, "
                "bf16[5376,4096] %w)") == "moe_dense"
    # attention's projections and scores are nobody's
    assert kind("%fusion.12 = f32[4,32,1024,1024] fusion(bf16[4,1024,32,128]"
                " %q)") is None
    assert kind("%fusion.13 = bf16[65,32,128] fusion(bf16[4096,32,128] "
                "%wq)") is None


def test_ssm_readers_on_a_hand_made_record():
    from chipbench.metrics import (latent_moe_roofline_share,
                                   latent_moe_time_share,
                                   moe_experts_touched_mean,
                                   ssm_roofline_share, ssm_step_device_ms,
                                   ssm_time_share)
    run = _record(stats0={"moe_layer_steps": 0, "moe_experts_touched": 0},
                  stats1={"moe_layer_steps": 1000,
                          "moe_experts_touched": 105_000})
    assert moe_experts_touched_mean.read(run) == 105.0
    run["traced"] = {
        "stats0": {"gdn_layer_steps": 500, "gdn_state_rows": 20_000,
                   "moe_layer_steps": 500, "moe_experts_touched": 50_000},
        "stats1": {"gdn_layer_steps": 2_000, "gdn_state_rows": 87_500,
                   "moe_layer_steps": 2_000, "moe_experts_touched": 207_500}}
    run["trace"] = {"busy_s": 4.0}
    run["spans"] = {"modules": {"engine_decode_block": {
        "count": 12, "total_s": 3.6}}}
    run["ssm_trace"] = {
        "kernels": {"ssm_decode": {"runs": 1500.0, "seconds": 0.8},
                    "moe_experts_decode": {"runs": 1500.0, "seconds": 2.0}},
        "modules": {"engine_decode_block": {"ssm_kernel": 0.8,
                                            "experts": 2.0,
                                            "moe_dense": 0.25},
                    "engine_prefill": {"ssm_scan": 0.05, "experts": 0.1,
                                       "moe_dense": 0.05}}}
    # 1500 runs / 5 Mamba-2 layers = 300 steps in 3.6 s
    assert ssm_step_device_ms.read(run) == pytest.approx(12.0)
    assert ssm_time_share.read(run) == pytest.approx((0.8 + 0.05) / 4.0)
    # 45 rows x 1500 layer steps x 8,617,984 B at 819 GB/s = 0.7103 s
    assert ssm_roofline_share.read(run) == pytest.approx(0.8878, rel=1e-3)
    assert latent_moe_time_share.read(run) == pytest.approx(2.4 / 4.0)
    # (105 x 11,010,048 + 109,051,904) B x 1500 at 819 GB/s = 2.317 s
    assert latent_moe_roofline_share.read(run) == pytest.approx(
        2.3171 / 2.25, rel=1e-3)
    # a parent without the counters, a trace without the kernels
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["spans"], bare["ssm_trace"] = {"busy_s": 1.0}, {}, {}
    for reader in (ssm_step_device_ms, ssm_time_share, ssm_roofline_share,
                   latent_moe_time_share, latent_moe_roofline_share,
                   moe_experts_touched_mean):
        assert reader.read(bare) is None
        assert reader.read({"device": {"platform": "cpu"}}) is None


def test_benchmark_json_lists_the_cell_under_every_metric_it_reports():
    """Written as "is a subset of": a later PR's metric may list the cell
    too."""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-super-120b-a12b", "serve-workers", 1)
    assert len(cell["why"]) <= 200
    assert [(c["file"], c["reduced"]) for c in bench["configs"]
            if c["name"] == cell["config"]] == [
        ("chipbench/configs/nemotron-3-super-120b-a12b.json",
         _real_config()["reduced"])]
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {
        "serve_tpot_mean_ms", "engine_ttft_p95_ms", "tpot_p95_ms",
        "paged_time_share", "prefill_time_share", "slot_wait_mean_ms",
        "engine_queue_wait_p95_ms", "engine_host_share", "tpot_stepping_ms",
        "tpot_prefill_stall_ms", "tpot_block_tail_ms",
        "prefill_padded_share", "prefill_stall_trace_error",
        "moe_experts_touched_mean", "live_rows_mean",
        "ssm_step_device_ms", "ssm_time_share",
        "ssm_roofline_share", "latent_moe_time_share",
        "latent_moe_roofline_share"} <= lists
    # steps counted by another model's kernels, bytes of another layer
    assert not lists & {"decode_step_device_ms", "hybrid_step_device_ms",
                        "gdn_time_share", "gdn_roofline_share",
                        "moe_roofline_share", "mla_roofline_share"}
    for m in bench["per_layer"]:
        if m["name"].startswith(("ssm_", "latent_moe_")):
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
            assert m["moves"] == "serve_tpot_mean_ms"
            assert os.path.exists(os.path.join(
                HERE, "..", "metrics", m["name"] + ".py"))


def test_runner_end_to_end_on_the_cpu():
    """``serve_ssm.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window, reference on a short and a long
    request, the record every serve reader reads."""
    from chipbench.metrics import (live_rows_mean, moe_experts_touched_mean,
                                   serve_tpot_mean_ms)
    from chipbench.runners import serve_ssm

    lines = []
    record = serve_ssm.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "ssm_decode_is_pallas", "experts_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), (checks, record["compared"])
    done = dict(lines)["serve_done"]
    warmed = {tuple(p) for p in dict(lines)["replica"]["pairs"]}
    assert all(b * w <= 64 or w == 1 for b, w in warmed)
    used = done["prefill_pairs_used"]
    assert used and {(b, w) for b, w, _ in used} <= warmed
    assert set(record["phases"]) == {
        "cluster_s", "replica_s", "warm_s", "window_s", "drained_s",
        "reference_s"}
    assert {m["which"] for m in done["reference"]} == {"short", "long"}
    long = next(m for m in done["reference"] if m["which"] == "long")
    assert set(TOY_MIX["reference"]["limits"]) <= set(long)
    assert record["compared"]["long.hidden_rel_err"] == {
        "value": long["hidden_rel_err"], "limit": [None, 1e-4]}
    assert record["compared"]["long.state_rel_err"]["value"] < 2e-5
    # a control is judged against the far side of its reading's limit
    assert record["compared"][
            "control.long.ssm_kernel_bf16_state_control"] == {
        "value": long["ssm_kernel_bf16_state_control"],
        "limit": [1e-4, None]}
    assert long["ssm_kernel_bf16_state_control"] > 1e-3 > long[
        "ssm_kernel_rel_err"]
    assert long["ssm_prefill_bf16_state_control"] > 1e-3 > long[
        "ssm_prefill_rel_err"]
    assert long["dead_rows_untouched"] == 1
    assert long["state_other_layer_control"] > 0.5
    assert long["tail_other_layer_control"] > 0.5
    assert long["no_skip_control"] > 1e-2 > abs(long["no_skip_projection"])
    assert long["router_bf16_control"] > 1e-3 > long["router_rel_err"]
    assert long["paged_kernel_control"] > 1e-2
    # the accepted reader of rows a layer step reads this cell too
    assert done["stats1"]["gdn_layer_steps"] > 0
    assert 0 < live_rows_mean.read(record) <= 4
    assert done["load_end"]["state_entries"] == 4 + 1 + 8
    assert done["load_end"]["state_entries_in_use"] == 0
    assert serve_tpot_mean_ms.read(record) > 0
    # 8 of 16 experts are held: a step touches some of them
    assert 0 < moe_experts_touched_mean.read(record) <= 8
