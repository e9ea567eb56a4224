"""The ``serve_arch`` runner and what it finds by name, on the CPU at the
tiny size: the reference against the program through the adapter, the
runner end to end with a toy configuration and mix passed in directly
(``rehearsal.json`` is not theirs to edit), the byte function against
hand-worked numbers, and each new reader on a hand-made record."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

TOY = {
    "source": "none: a toy of the SmallThinker block for CPU tests only",
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 128,
    "moe_ffn_hidden_size": 32, "moe_num_active_primary_experts": 3,
    "moe_num_primary_experts": 8, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 6,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_layout": [0, 1, 1, 1] * 3, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 3,
    "sliding_window_size": 8, "tie_word_embeddings": False,
    "vocab_size": 256,
    "program": {"preset": "tiny-smallthinker",
                "adapter": "chipbench.lib.arch_smallthinker",
                "reference": "chipbench.lib.reference_smallthinker"}}

TOY_MIX = {
    "kind": "serve_arch", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 16, "max": 30},
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
                             "dropped_expert_projection": [None, 0.01],
                             "no_window_projection": [None, 0.01],
                             "router_rel_err": [None, 1e-5],
                             "window_kernel_rel_err": [None, 1e-4],
                             "served_token_agree_share": [1.0, None]}}}


def _real_config():
    with open(os.path.join(HERE, "..", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key,
    ``num_hidden_layers`` alone reduced; the layouts are kept whole."""
    cfg = _real_config()
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"]["num_hidden_layers"] == 52
    want = {"head_dim": 128, "hidden_size": 2560,
            "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
            "moe_num_active_primary_experts": 6,
            "moe_num_primary_experts": 64, "num_attention_heads": 28,
            "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
            "rope_theta": 1500000, "sliding_window_size": 4096,
            "tie_word_embeddings": False, "vocab_size": 151936,
            "rope_layout": [0, 1, 1, 1] * 13,
            "sliding_window_layout": [0, 1, 1, 1] * 13}
    assert {k: cfg[k] for k in want} == want


def test_adapter_makes_the_preset_the_configuration():
    from chipbench.lib import arch_smallthinker
    from ray_tpu.models import get_config
    cfg = _real_config()
    ov = arch_smallthinker.model_overrides(cfg, {"param_dtype": "bfloat16"})
    model = get_config(cfg["program"]["preset"], **ov)
    assert (model.n_layers, model.d_model, model.head_dim) == (8, 2560, 128)
    assert model.rope_layout == model.window_layout == (0, 1, 1, 1) * 2
    assert model.moe_dropless and model.moe_act == "relu"
    # 8 layers: 7.93 GB of bf16 weights (ISSUE 26's arithmetic)
    assert model.num_params() == 8 * (20_971_520 + 163_840 + 64 * 5_898_240
                                      + 2 * 2560) + 2 * 151936 * 2560 + 2560
    assert round(2 * model.num_params() / 1e9, 2) == 7.93
    with pytest.raises(SystemExit):
        arch_smallthinker.model_overrides(dict(cfg, norm_topk_prob=False))


def test_reference_agrees_with_the_program_through_the_adapter():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.lib import arch_smallthinker
    from chipbench.lib import reference_smallthinker as ref
    from ray_tpu.models import GPT, get_config

    ov = arch_smallthinker.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 40))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    want = GPT(cfg).apply({"params": params}, jnp.asarray(tokens))[0]
    weights = ref.from_program_params(params)
    got = ref.logits(weights, tokens[0], TOY)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)


def test_the_numeric_check_tells_a_wrong_program_from_the_right_one():
    """``lib/replica_arch.py``'s readings through a tiny paged engine on
    the CPU in float32, where the arithmetic is exact: the program as it
    is reads zeros, and a program that drops an expert, leaves the window
    out or rounds its router to bfloat16 is each caught by the reading
    made for it and by a limit of the kind the cell sets."""
    import dataclasses
    import types

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ray_tpu.ops.moe as moe
    from chipbench.lib import arch_smallthinker, replica_arch
    from chipbench.lib import reference_smallthinker as ref
    from ray_tpu.models import GPT, get_config
    from ray_tpu.serve.llm_engine import LLMEngine

    ov = arch_smallthinker.model_overrides(TOY, {"dtype": "float32"})
    cfg = get_config(TOY["program"]["preset"], **ov)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 13))
    params = GPT(cfg).init(jax.random.PRNGKey(1),
                           jnp.asarray(tokens))["params"]
    eng = LLMEngine(cfg, params, num_slots=2, paged=True, page_size=4,
                    max_seq_len=64, max_prompt_len=32, block_size=4,
                    min_prefill_bucket=4)
    try:
        prompt = [int(t) for t in tokens[0]]
        served = eng.submit(prompt, max_new_tokens=30,
                            temperature=0.0).tokens
        (good,) = replica_arch.ArchBenchLLMServer.bench_reference(
            types.SimpleNamespace(engine=eng),
            [{"prompt": prompt, "tokens": served}], TOY)
        assert good["context"] == 43 and good["past"] == 34
        assert good["hidden_rel_err"] < 1e-5
        assert abs(good["dropped_expert_projection"]) < 1e-4
        assert abs(good["no_window_projection"]) < 1e-4
        assert good["router_rel_err"] < 1e-6 and good["router_rows"] == 336
        assert good["window_kernel_rel_err"] < 1e-5
        assert good["served_token_agree_share"] == 1.0
        # what each fault would read, from the references made wrong
        assert good["dropped_expert_control"] > 0.1
        assert good["no_window_control"] > 0.1
        assert good["window_kernel_control"] > 0.1

        weights = ref.from_program_params(eng.params)
        seq = (prompt + served)[:-1]

        def wrong_program(**replaced):
            model = GPT(dataclasses.replace(cfg, **replaced), decode=True,
                        paged_pages=eng.kv_pool_pages,
                        page_size=eng.page_size)
            got = replica_arch.program_hidden(eng, seq, len(prompt), model)
            return got, ref.hidden_check(weights, jnp.asarray(seq),
                                         got["hidden"], TOY)
        _, bad = wrong_program(moe_top_k=2)
        assert bad["dropped_expert_projection"] > 0.99
        assert bad["hidden_rel_err"] > 0.1
        _, bad = wrong_program(sliding_window=None, window_layout=None)
        assert bad["no_window_projection"] > 0.99
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
        # a reference in fewer bits is further from the reference than
        # the float32 program is, by orders
        want = ref.hidden(weights, jnp.asarray(seq), TOY)
        for bits, least in ((7, 1e-3), (3, 3e-2)):
            low = ref.hidden(weights, jnp.asarray(seq), TOY, bits=bits)
            assert float(jnp.mean(ref._row_err(low, want))) > least
    finally:
        eng.close()


def test_rehearsal_of_the_schedule_finds_the_waves():
    from chipbench.runners.serve_arch import rehearse
    mix = {"prompt_len": {"min": 16, "max": 100}, "warm_horizon_s": 1.0}
    at = lambda t, n: {"due_s": t, "prompt": [1] * n}        # noqa: E731
    sched = [at(0.0, 20), at(0.2, 30), at(0.9, 17), at(3.0, 100),
             at(5.0, 60), at(5.5, 50)]
    # three of bucket 32 inside a second -> waves 1, 2, 4; two of bucket
    # 64; one of 128; the warm-up requests' bucket 16 at wave 1
    assert rehearse(sched, mix) == [(16, 1), (32, 1), (32, 2), (32, 4),
                                    (64, 1), (64, 2), (128, 1)]


def test_the_mix_names_the_weights_the_server_makes():
    """``weights_seed`` reaches ``LLMServer`` whatever ``--seed`` is;
    a mix without it draws the weights from ``--seed`` as before."""
    from chipbench.runners import serve_arch
    with open(os.path.join(HERE, "..", "traffic", "serve-reason.json")) as f:
        mix = json.load(f)
    cfg = _real_config()
    a, b = (serve_arch.server_args(cfg, mix, seed31) for seed31 in (7, 11))
    assert a == b and a["seed"] == mix["weights_seed"]
    assert a["seed"] == 2700000015 % (2**31 - 1)      # PERF.md, PR 29
    assert a["paged"] and a["num_slots"] == 32
    assert a["config_overrides"]["n_layers"] == 8
    bare = {k: v for k, v in mix.items() if k != "weights_seed"}
    assert [serve_arch.server_args(cfg, bare, s)["seed"]
            for s in (7, 11)] == [7, 11]
    # and the token ids of the run those weights were chosen by
    assert mix["contents_seed"] == 2700000015
    a, b = (serve_arch.cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert a == b and len(a) == 27


def test_the_mix_may_name_the_token_ids_too():
    from chipbench.runners.serve_arch import cell_schedule
    a, b = (cell_schedule(TOY_MIX, seed, 3.0, 256) for seed in (3, 4))
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a, b))
    fixed = dict(TOY_MIX, contents_seed=5)
    a, b = (cell_schedule(fixed, seed, 3.0, 256) for seed in (3, 4))
    assert a == b == cell_schedule(TOY_MIX, 5, 3.0, 256)


def test_expert_bytes_by_hand():
    from chipbench.lib import moe_bytes
    cfg = _real_config()
    # gate, up, down: 3 x 2560 x 768 weights of 2 bytes
    assert moe_bytes.expert_bytes(cfg) == 11_796_480
    assert moe_bytes.layer_bytes(cfg) == 754_974_720          # 0.755 GB
    # 61 experts touched a layer step, 8 layers: 5.76 GB a decode step
    assert moe_bytes.touched_bytes(cfg, 61 * 8) == 5_756_682_240
    assert moe_bytes.expert_bytes(cfg, 4) == 2 * 11_796_480


def _record(**serve):
    return {"config": _real_config(),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "serve": serve}


def test_window_pages_skipped_share_reader():
    from chipbench.metrics import window_pages_skipped_share as m
    run = _record(stats0={"window_pages_read": 10,
                          "window_pages_skipped": 0},
                  stats1={"window_pages_read": 310,
                          "window_pages_skipped": 100})
    assert m.read(run) == 0.25
    assert m.read(_record(stats0={}, stats1={})) is None      # the parent
    assert m.read({}) is None


def test_moe_readers_on_a_hand_made_record():
    from chipbench.metrics import (moe_experts_touched_mean,
                                   moe_roofline_share, moe_time_share)
    # the counters over the WINDOW: 16.5 experts touched a layer step
    whole = _record(
        stats0={"moe_layer_steps": 64, "moe_experts_touched": 400},
        stats1={"moe_layer_steps": 76_064, "moe_experts_touched": 1_254_400})
    assert moe_experts_touched_mean.read(whole) == 16.5
    assert moe_experts_touched_mean.read(_record(
        stats0={"moe_layer_steps": 64, "moe_experts_touched": 400},
        stats1={"moe_layer_steps": 64, "moe_experts_touched": 400})) is None
    run = _record(stats0={}, stats1={})
    assert moe_experts_touched_mean.read(run) is None         # the parent
    # the counters over the TRACED interval, as the runner snapshots them
    run["traced"] = {
        "stats0": {"moe_layer_steps": 500, "moe_experts_touched": 9_000},
        "stats1": {"moe_layer_steps": 1500, "moe_experts_touched": 70_000}}
    run["trace"] = {"busy_s": 2.0}
    run["spans"] = {"kernel_runs": {"paged_attention_decode": 800.0}}
    run["moe_trace"] = {
        "engine_decode_block": {"products": 1.0, "router": 0.2},
        "engine_prefill": {"products": 0.3}}
    assert moe_time_share.read(run) == pytest.approx(0.75)
    # 800 layer steps x 61 experts x 11.8 MB at 819 GB/s = 0.7029 s
    assert moe_roofline_share.read(run) == pytest.approx(0.7029, rel=1e-3)
    # a parent without the counters, a trace without the operations
    bare = _record(stats0={}, stats1={})
    bare["trace"], bare["spans"], bare["moe_trace"] = {"busy_s": 1.0}, {}, {}
    assert moe_time_share.read(bare) is None
    assert moe_roofline_share.read(bare) is None


def test_moe_trace_recognises_the_expert_operations():
    from chipbench.lib import moe_trace
    pats = moe_trace.patterns(_real_config())
    dense = ("%fusion.9 = bf16[64,768,33]{2,1,0} fusion(bf16[8,64,2560,768]"
             "{3,2,1,0} %get-tuple-element.1, s32[] %p), kind=kOutput")
    grouped = ("%ragged-dot-none.1 = bf16[12288,768]{1,0} custom-call(s32[1]"
               " %a, bf16[12288,2560] %x, bf16[64,2560,768] %w)")
    router = ("%fusion.3 = f32[33,1,64]{2,1,0} fusion(f32[33,1,2560] %h, "
              "bf16[8,2560,64]{2,1,0} %r), kind=kOutput")
    attn = ("%fusion.5 = bf16[33,1,2560]{2,1,0} fusion(bf16[33,3584] %o, "
            "bf16[8,3584,2560] %wo), kind=kOutput")
    loop = ("%while.5 = (s32[], bf16[8,64,2560,768]{3,2,1,0}) while((s32[],"
            " bf16[8,64,2560,768]) %t), condition=%c, body=%b")
    assert pats["products"].search(dense) and pats["products"].search(grouped)
    assert pats["router"].search(router)
    assert not any(rx.search(attn) for rx in pats.values())
    assert moe_trace.opcode(loop) == "while"
    assert moe_trace.opcode(dense) == "fusion"
    assert moe_trace.opcode(grouped) == "custom-call"


def test_runner_end_to_end_on_the_cpu():
    """``serve_arch.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window, reference on a short and a long
    request, the record every serve reader reads."""
    from chipbench.metrics import (serve_tpot_mean_ms,
                                   window_pages_skipped_share)
    from chipbench.runners import serve_arch

    lines = []
    record = serve_arch.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), checks
    done = dict(lines)["serve_done"]
    assert {m["which"] for m in done["reference"]} == {"short", "long"}
    long = next(m for m in done["reference"] if m["which"] == "long")
    assert set(TOY_MIX["reference"]["limits"]) <= set(long)
    # each number compared goes beside its limit into the result's line
    assert record["compared"]["long.hidden_rel_err"] == {
        "value": long["hidden_rel_err"], "limit": [None, 1e-4]}
    from chipbench.run import compared
    line = compared(record)
    assert line["no_failed_request"] == {"value": 1, "limit": [1, None]}
    assert line["platform_tpu"]["value"] == 0
    assert line["short.router_rel_err"]["limit"] == [None, 1e-5]
    assert long["hidden_rel_err"] < 1e-5 and long["past"] > 0
    assert done["stats1"]["moe_layer_steps"] > 0
    assert serve_tpot_mean_ms.read(record) > 0
    assert window_pages_skipped_share.read(record) > 0
