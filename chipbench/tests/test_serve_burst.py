"""The ``serve_ssm_dense`` runner and what it finds by name, on the CPU at
the tiny size: the configuration file against the catalog row, the
adapter and its refusals, the reference against the program through the
adapter, the 45 s schedule and its bursts, the byte function by hand,
each new reader on a hand-made record, that ``BENCHMARK.json`` lists the
cell under every metric it reports, and the runner end to end with a toy
configuration and mix passed in directly (``rehearsal.json`` is not this
PR's to edit).  Run it on its own (``tests/conftest.py`` forces eight
host devices, and the runner then finds 8 where the toy cell asks for
1)."""

import json
import os
import statistics

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite-4.0-h-micro.serve-burst"

TOY = {
    "source": "none: a toy of the Granite 4.0-H layers for CPU tests only",
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "num_hidden_layers": 8,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "tie_word_embeddings": True, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.125,
    "logits_scaling": 8,
    "program": {"preset": "tiny-granite-h",
                "adapter": "chipbench.lib.arch_granite_h",
                "reference": "chipbench.lib.reference_granite_h"}}

TOY_MIX = {
    "kind": "serve_ssm_dense", "rate_per_s": 3,
    "arrivals": {"process": "gamma", "cv": 2.0},
    "prompt_len": {"dist": "uniform", "min": 17, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 14},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0, "prefill_wave_tokens": 64},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_pairs": [[32, 2]],
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {"short_max_total_tokens": 30, "long_min_context": 40,
                  "limits": {"hidden_rel_err": [None, 1e-4],
                             "logits_rel_err": [None, 1e-4],
                             "state_rel_err": [None, 1e-4],
                             "tail_rel_err": [None, 1e-4],
                             "paged_kernel_rel_err": [None, 1e-4],
                             "ssm_kernel_rel_err": [None, 1e-4],
                             "ssm_prefill_rel_err": [None, 1e-4],
                             "dead_rows_untouched": [1, None],
                             "served_token_agree_share": [1.0, None],
                             "state_dropped_projection": [None, 0.01],
                             "padding_absorbed_projection": [None, 0.01],
                             "no_skip_projection": [None, 0.01],
                             "embedding_one_projection": [None, 0.01],
                             "residual_one_projection": [None, 0.01],
                             "softmax_one_projection": [None, 0.01],
                             "softmax_sqrt_projection": [None, 0.01],
                             "logits_unscaled_projection": [None, 0.01],
                             "logits_tied_scaled_projection": [None, 0.01]},
                  "controls": {
                      "fp8_control": "hidden_rel_err",
                      "embedding_one_control": "hidden_rel_err",
                      "residual_one_control": "hidden_rel_err",
                      "softmax_one_control": "hidden_rel_err",
                      "softmax_sqrt_control": "hidden_rel_err",
                      "logits_unscaled_control": "logits_rel_err",
                      "logits_fp8_control": "logits_rel_err",
                      "state_other_layer_control": "state_rel_err",
                      "tail_other_layer_control": "tail_rel_err",
                      "ssm_kernel_bf16_state_control": "ssm_kernel_rel_err",
                      "ssm_prefill_bf16_state_control":
                          "ssm_prefill_rel_err",
                      "paged_kernel_control": "paged_kernel_rel_err",
                      "paged_kernel_sqrt_scale_control":
                          "paged_kernel_rel_err"}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _real_mix():
    with open(os.path.join(HERE, "..", "traffic", "serve-burst.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key;
    nothing reduced."""
    cfg = _real_config()
    assert cfg["reduced"] == []
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    assert kinds.count("mamba") == 36
    want = {"model_type": "granitemoehybrid", "hidden_size": 2048,
            "vocab_size": 100352, "intermediate_size": 8192,
            "shared_intermediate_size": 8192, "num_local_experts": 0,
            "num_experts_per_tok": 0, "num_attention_heads": 32,
            "num_key_value_heads": 8, "mamba_n_heads": 64,
            "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
            "mamba_d_conv": 4, "mamba_chunk_size": 256, "mamba_expand": 2,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False, "hidden_act": "silu",
            "position_embedding_type": "nope", "rope_theta": 10000,
            "rope_scaling": None, "normalization_function": "rmsnorm",
            "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
            "max_position_embeddings": 131072,
            "embedding_multiplier": 12, "residual_multiplier": 0.22,
            "attention_multiplier": 0.015625, "logits_scaling": 8}
    assert {k: cfg[k] for k in want} == want
    assert cfg["assumed"] and "one chip holds the whole model" in cfg[
        "deployment"]
    assert cfg["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")


def test_adapter_makes_the_preset_the_configuration_and_refuses():
    from chipbench.lib import arch_granite_h
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_granite_h.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    # the preset IS the configuration: the overrides change no size
    plain = get_config(cfg["program"]["preset"])
    assert {k: getattr(plain, k) for k in ov if k != "param_dtype"} == {
        k: v for k, v in ov.items() if k != "param_dtype"}
    assert (model.n_layers, model.d_model, model.head_dim) == (40, 2048, 64)
    assert model.layers_of("mamba2_mlp") == 36 and len(model.period) == 10
    assert model.rope_theta is None and model.tie_embeddings
    assert (model.embedding_multiplier, model.residual_multiplier,
            model.attention_multiplier, model.logits_scaling) == (
                12.0, 0.22, 0.015625, 8.0)
    assert model.num_params() == 3_191_396_096
    assert round(2 * model.num_params() / 1e9, 2) == 6.38
    for wrong in ({"num_local_experts": 8}, {"attention_bias": True},
                  {"mamba_proj_bias": True}, {"mamba_conv_bias": False},
                  {"position_embedding_type": "rope"},
                  {"mamba_n_groups": 3}, {"hidden_act": "gelu"},
                  {"layer_types": ["mamba"] * 39 + ["moe"]},
                  {"layer_types": ["mamba"] * 4}, {"mamba_expand": 4},
                  {"shared_intermediate_size": 4096}):
        with pytest.raises(SystemExit):
            arch_granite_h.model_overrides(dict(cfg, **wrong))


def test_the_parent_program_is_refused_before_anything_starts():
    """``_refuse_unknown`` on a program that lacks the preset or a field
    (what the parent commit is to this cell: it has neither the preset
    nor the four multipliers); the adapter itself imports nothing from
    the program."""
    from chipbench.lib import arch_granite_h
    from chipbench.runners.serve_arch import _refuse_unknown
    ov = arch_granite_h.model_overrides(_real_config())
    _refuse_unknown("granite-4.0-h-micro", ov)      # this program: fine
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("granite-4.0-h-micro", dict(ov, no_such_field=1))
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("no-such-preset", ov)
    with open(arch_granite_h.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_reference_agrees_with_the_program_through_the_adapter():
    """The toy configuration through the adapter, a whole forward of the
    program against the reference: logits; and each reference made wrong
    on purpose is another function."""
    import jax
    import jax.numpy as jnp
    from chipbench.lib import arch_granite_h
    from chipbench.lib import reference_granite_h as ref
    from ray_tpu.models import GPT, get_config
    cfg = get_config(TOY["program"]["preset"],
                     **arch_granite_h.model_overrides(
                         TOY, {"dtype": "float32"}))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (27,), 0, 256)
    got = GPT(cfg).apply({"params": params}, tokens[None])[0]
    weights = ref.from_program_params(params)
    want = ref.logits(weights, tokens, TOY)
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(
        jnp.abs(want).max())
    right = ref.hidden(weights, tokens, TOY)
    for wrong in ({"no_skip": True}, {"reset_at": 20}, {"bits": 3},
                  {"absorb": (20, 5)}, {"wrong": {"e": 1.0}},
                  {"wrong": {"r": 1.0}}, {"wrong": {"s": 1.0}},
                  {"wrong": {"s": "sqrt"}}):
        other = ref.hidden(weights, tokens, TOY, **wrong)
        assert float(jnp.abs(other - right).max()) > 1e-4, wrong
    # the logits' divisor is the head's alone
    assert float(jnp.abs(ref.hidden(weights, tokens, TOY, wrong={"L": 1.0})
                         - right).max()) == 0.0
    unscaled = ref.logits(weights, tokens, TOY, wrong={"L": 1.0})
    assert float(jnp.abs(unscaled - 8 * want).max()) < 1e-4
    # a padded run changes no row before the pad
    padded = ref.hidden(weights, tokens, TOY, pad_to=32)
    assert float(jnp.abs(padded - right).max()) < 1e-5


def test_the_45_s_schedule_is_the_mix_s_own():
    """Every ``--seed`` offers the same requests at the same times
    (token ids alone are the seed's); gaps are the gamma generator's at
    the mix's cv; no request can outgrow the server;
    the runner finds a short and a long reference sample whose prompts
    are padded; every prefill program the schedule can form under the
    mix's horizon is among those it warms."""
    from chipbench.runners.serve_arch import cell_schedule, reference_samples
    from chipbench.runners.serve_hybrid import _padded, warmed_pairs
    from chipbench.runners.serve_latent import _fits
    mix, cfg = _real_mix(), _real_config()
    assert mix["arrivals"]["process"] == "gamma"
    assert "contents_seed" not in mix and mix["weights_seed"]
    a, b = (cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45)
    shape = lambda s: [(r["due_s"], len(r["prompt"]),       # noqa: E731
                        r["max_new_tokens"]) for r in s]
    assert shape(a) == shape(b) and a != b
    assert all(1 <= t < cfg["vocab_size"] for r in a for t in r["prompt"])
    gaps = [y["due_s"] - x["due_s"] for x, y in zip(a, a[1:])]
    # cv 1.0 since the steadiness rule of ISSUE 50 took cv 2.0 back
    assert mix["arrivals"]["cv"] == 1.0
    assert 0.85 < statistics.pstdev(gaps) / statistics.mean(gaps) < 1.2
    server = mix["server"]
    assert all(64 <= len(r["prompt"]) <= server["max_prompt_len"]
               and 32 <= r["max_new_tokens"] <= 1024
               and len(r["prompt"]) + r["max_new_tokens"]
               <= server["max_seq_len"] for r in a)
    recs = [{"due": r["due_s"], "prompt_len": len(r["prompt"]), "done": 1,
             "tokens": [1] * r["max_new_tokens"]} for r in a
            if _padded(len(r["prompt"]))]
    picked = {s["which"]: s for s in reference_samples(
        recs, a, mix["reference"])}
    assert set(picked) == {"short", "long"}
    long = picked["long"]
    assert len(long["prompt"]) + len(long["tokens"]) >= 1024
    pairs = _fits(mix, warmed_pairs(a, mix))
    assert all(b * w <= server["prefill_wave_tokens"] for b, w in pairs)
    assert {b for b, _ in pairs} == {64, 128, 256, 512}
    assert len(pairs) == 14 and max(w for _, w in pairs) == 8
    # state entries and pages as the issue reckons them
    assert server["num_slots"] + 1 + 8 == 73
    assert server["kv_pool_pages"] == 2081


def test_bytes_by_hand():
    from chipbench.lib import mamba_bytes
    cfg = _real_config()
    # 64 heads x 64 x 128 float32
    assert mamba_bytes.state_bytes(cfg) == 2_097_152
    # state both ways, 3 x 4,352 bf16 tail both ways, delta u, the decay
    # and y over 4096 lanes, B and C of 1 x 128, float32
    assert mamba_bytes.decode_row_bytes(cfg) == 2 * 2_097_152 + 2 * 26_112 \
        + 4 * (3 * 4096 + 2 * 128) == 4_296_704
    # 36 layers: 75.5 MB of state a request, read and written every step
    assert round(36 * 2_097_152 / 1e6, 1) == 75.5


def _record(**serve):
    return {"config": _real_config(), "mix": _real_mix(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_trace_patterns_tell_the_layers_apart():
    from chipbench.lib import mamba_trace
    assert mamba_trace._chunks(_real_config(), _real_mix()) == [64, 128, 256]
    pats = mamba_trace.patterns(_real_config(), _real_mix())
    kind = lambda line: next(                                 # noqa: E731
        (k for k, rx in pats.items() if rx.search(line)), None)
    assert kind("%ssm_decode.3 = (f32[65,1,4096], f32[36,73,128,4096]) "
                "custom-call(...)") == "ssm_kernel"
    assert kind("%fusion.7 = f32[4,64,256,256] fusion(f32[4,256,64] "
                "%g)") == "ssm_scan"
    assert kind("%fusion.8 = f32[2,4,64,128,64] fusion()") == "ssm_scan"
    assert kind("%fusion.6 = f32[32,64,64,64] fusion()") == "ssm_scan"
    assert kind("%fusion.9 = bf16[65,8512] fusion(bf16[65,2048] %x, "
                "bf16[1,2048,8512] %w)") == "ssm_proj"
    assert kind("%fusion.10 = bf16[65,2048] fusion(bf16[65,4096] %y, "
                "bf16[4096,2048] %w)") == "ssm_proj"
    assert kind("%fusion.11 = bf16[65,8192] fusion(bf16[65,2048] %x, "
                "bf16[1,2048,8192] %g, bf16[1,2048,8192] %u)") == "mlp"
    assert kind("%fusion.12 = bf16[65,2048] fusion(bf16[65,8192] %h, "
                "bf16[8192,2048] %w)") == "mlp"
    # attention's projections, the decode rows and the head are nobody's
    assert kind("%fusion.13 = bf16[65,32,64] fusion(bf16[2048,32,64] "
                "%wq)") is None
    assert kind("%fusion.14 = f32[65,64,64] fusion()") is None
    assert kind("%fusion.15 = f32[65,100352] fusion(bf16[65,2048] %x, "
                "bf16[100352,2048] %e)") is None


def test_readers_on_a_hand_made_record():
    from chipbench.metrics import (live_rows_max, live_rows_mean,
                                   mamba_decode_roofline_share,
                                   mamba_mixer_time_share,
                                   mamba_step_device_ms, mlp_time_share)
    run = _record(stats0={"gdn_layer_steps": 0, "gdn_state_rows": 0,
                          "live_rows_max": 0},
                  stats1={"gdn_layer_steps": 36_000,
                          "gdn_state_rows": 900_000, "live_rows_max": 41})
    assert live_rows_mean.read(run) == 25.0
    assert live_rows_max.read(run) == 41
    run["traced"] = {
        "stats0": {"gdn_layer_steps": 3_600, "gdn_state_rows": 72_000},
        "stats1": {"gdn_layer_steps": 10_800, "gdn_state_rows": 252_000}}
    run["trace"] = {"busy_s": 4.0}
    run["spans"] = {"modules": {"engine_decode_block": {
        "count": 8, "total_s": 3.6}}}
    run["mamba_trace"] = {
        "kernel": {"runs": 7200.0, "seconds": 1.4},
        "modules": {"engine_decode_block": {"ssm_kernel": 1.4,
                                            "ssm_proj": 0.5, "mlp": 1.2},
                    "engine_prefill": {"ssm_scan": 0.1, "ssm_proj": 0.02,
                                       "mlp": 0.08}}}
    # 7200 runs / 36 Mamba-2 layers = 200 steps in 3.6 s
    assert mamba_step_device_ms.read(run) == pytest.approx(18.0)
    assert mamba_mixer_time_share.read(run) == pytest.approx(2.02 / 4.0)
    assert mlp_time_share.read(run) == pytest.approx(1.28 / 4.0)
    # 25 rows x 7200 layer steps x 4,296,704 B at 819 GB/s = 0.9443 s
    assert mamba_decode_roofline_share.read(run) == pytest.approx(
        0.9443 / 1.4, rel=1e-3)
    # a parent without the counters, a trace without the kernel
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["spans"], bare["mamba_trace"] = (
        {"busy_s": 1.0}, {}, {})
    for reader in (mamba_step_device_ms, mamba_mixer_time_share,
                   mlp_time_share, mamba_decode_roofline_share,
                   live_rows_max):
        assert reader.read(bare) is None
        assert reader.read({"device": {"platform": "cpu"}}) is None


def test_benchmark_json_lists_the_cell_under_every_metric_it_reports():
    """Written as "is a subset of": a later PR's metric may list the cell
    too."""
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "serve-burst", 1)
    assert len(cell["why"]) <= 200
    assert [(c["file"], c["reduced"], c["source"]) for c in bench["configs"]
            if c["name"] == cell["config"]] == [
        ("chipbench/configs/granite-4.0-h-micro.json", [],
         _real_config()["source"])]
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 7
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {
        "serve_tpot_mean_ms", "live_rows_mean", "mamba_step_device_ms",
        "mamba_mixer_time_share", "mamba_decode_roofline_share",
        "mlp_time_share", "live_rows_max"} <= lists
    # steps counted by another model's kernels, bytes of another layer,
    # readers that index another family's keys
    assert not lists & {"decode_step_device_ms", "hybrid_step_device_ms",
                        "gdn_time_share", "gdn_roofline_share",
                        "ssm_step_device_ms", "ssm_time_share",
                        "ssm_roofline_share", "moe_roofline_share",
                        "moe_experts_touched_mean", "mla_roofline_share"}
    for m in bench["per_layer"]:
        if m["name"].startswith(("mamba_", "mlp_")) or \
                m["name"] == "live_rows_max":
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tpot_mean_ms"
            assert os.path.exists(os.path.join(
                HERE, "..", "metrics", m["name"] + ".py"))


def test_runner_end_to_end_on_the_cpu():
    """``serve_ssm_dense.run`` with the toy configuration and mix:
    cluster, replica, rehearsed warm-up, window, reference on a short
    and a long request, the record every serve reader reads."""
    from chipbench.metrics import (live_rows_max, live_rows_mean,
                                   serve_tpot_mean_ms)
    from chipbench.runners import serve_ssm_dense

    lines = []
    record = serve_ssm_dense.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    assert "experts_decode_is_pallas" not in checks
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "ssm_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), (checks, record["compared"])
    done = dict(lines)["serve_done"]
    warmed = {tuple(p) for p in dict(lines)["replica"]["pairs"]}
    assert (32, 2) in warmed
    assert all(b * w <= 64 or w == 1 for b, w in warmed)
    used = done["prefill_pairs_used"]
    assert used and {(b, w) for b, w, _ in used} <= warmed
    assert set(record["phases"]) == {
        "cluster_s", "replica_s", "warm_s", "window_s", "drained_s",
        "reference_s"}
    assert {m["which"] for m in done["reference"]} == {"short", "long"}
    long = next(m for m in done["reference"] if m["which"] == "long")
    assert set(TOY_MIX["reference"]["limits"]) <= set(long)
    assert set(TOY_MIX["reference"]["controls"]) <= set(long)
    assert long["mamba_layers"] == 6
    assert record["compared"]["long.hidden_rel_err"] == {
        "value": long["hidden_rel_err"], "limit": [None, 1e-4]}
    assert record["compared"]["long.logits_rel_err"]["value"] < 2e-5
    assert record["compared"]["long.state_rel_err"]["value"] < 2e-5
    # a control is judged against the far side of its reading's limit
    assert record["compared"]["control.long.logits_unscaled_control"] == {
        "value": long["logits_unscaled_control"], "limit": [1e-4, None]}
    assert long["logits_unscaled_control"] == pytest.approx(7.0, rel=1e-3)
    for fault in ("embedding_one", "residual_one", "softmax_one",
                  "softmax_sqrt"):
        assert long[fault + "_control"] > 1e-2 > abs(
            long[fault + "_projection"]), fault
    assert abs(long["logits_tied_scaled_projection"]) < 1e-3
    assert long["ssm_kernel_bf16_state_control"] > 1e-3 > long[
        "ssm_kernel_rel_err"]
    assert long["ssm_prefill_bf16_state_control"] > 1e-3 > long[
        "ssm_prefill_rel_err"]
    assert long["dead_rows_untouched"] == 1
    assert long["state_other_layer_control"] > 0.5
    assert long["tail_other_layer_control"] > 0.5
    assert long["no_skip_control"] > 1e-2 > abs(long["no_skip_projection"])
    assert long["paged_kernel_control"] > 1e-2
    assert long["paged_kernel_sqrt_scale_control"] > 1e-2 > long[
        "paged_kernel_rel_err"]
    # the accepted reader of rows a layer step reads this cell too, and
    # the high-water marks are the window's own
    assert done["stats1"]["gdn_layer_steps"] > 0
    assert done["stats0"]["live_rows_max"] == 0
    assert 0 < live_rows_mean.read(record) <= live_rows_max.read(record) <= 4
    assert 0 < done["stats1"]["state_entries_max"] <= 4 + 8
    assert done["load_end"]["state_entries"] == 4 + 1 + 8
    assert done["load_end"]["state_entries_in_use"] == 0
    assert serve_tpot_mean_ms.read(record) > 0
