"""The ``serve_mtp`` runner and what it finds by name, on the CPU at the
tiny size: the configuration file against the catalog row, the adapter
and its refusals, the 45 s schedule, the byte functions by hand, each new
reader on a hand-made record and on a trace recorded on the chip, that
``BENCHMARK.json`` lists the cell under every metric it reports (written
as "is a subset of"), and the runner end to end with a toy configuration
and mix passed in directly (``rehearsal.json`` is not this PR's to
edit)."""

import importlib
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "k-exaone-236b-a23b.serve-think"
NEW_METRICS = ("mtp_accept_share", "spec_tokens_per_step_mean",
               "spec_step_device_ms", "mtp_draft_time_share",
               "paged_verify_roofline_share")

_KINDS = ["sliding_attention"] * 3 + ["full_attention"]
TOY = {
    "source": "none: a toy of K-EXAONE's layers for CPU tests only",
    "model_type": "exaone_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 6, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
    "hidden_act": "silu",
    "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
    "sliding_window": 8, "layer_types": _KINDS * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "num_experts": 4, "experts_held_first": 2,
    "published": {"num_experts": 8},
    "num_experts_per_tok": 3, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "mtp_layer_types": ["full_attention"], "tie_word_embeddings": False,
    "program": {"preset": "tiny-k-exaone",
                "adapter": "chipbench.lib.arch_k_exaone",
                "reference": "chipbench.lib.reference_k_exaone"}}

_FAULTS = ("no_qk_norm", "global_rotates", "no_window", "bias_in_gates",
           "no_route_scale", "no_shared_expert", "absent_experts_added",
           "mtp_halves_swapped", "mtp_same_token")
TOY_MIX = {
    "kind": "serve_mtp", "rate_per_s": 3,
    "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "uniform", "min": 17, "max": 30},
    "output_len": {"dist": "uniform", "min": 6, "max": 30},
    "draw_seed": 2,
    "server": {"num_slots": 4, "page_size": 4, "max_seq_len": 64,
               "max_prompt_len": 32, "block_size": 4,
               "prefix_cache_pages": 0, "prefill_wave_tokens": 64,
               "temperature": 1.0},
    "config_overrides": {"dtype": "float32"},
    "max_concurrent_queries": 64, "warm_horizon_s": 1.0,
    "warm_concat": {"products": [[2, [1, 2]]], "exact": []},
    "warm_requests": 1, "trace_seconds": 1,
    "reference": {
        "short_max_total_tokens": 30, "long_min_context": 44,
        "faults_in": "first",
        "limits": {"hidden_rel_err": [None, 1e-4],
                   "mtp_hidden_rel_err": [None, 1e-4],
                   "router_rel_err": [None, 1e-5],
                   "verify_kernel_rel_err": [None, 1e-4],
                   "verify_kernel_dead_rows_zero": [1, None],
                   "accept_ratio_err": [None, 1e-5],
                   # a few dozen tokens and drafts: the bands are wide and
                   # the z controls have no power here (not listed)
                   "window_accept_err": [None, 0.3],
                   "served_loglik_z": [-6, 6],
                   "replay_state_ok": [1, None],
                   "replay_accept_z": [-6, 6],
                   "replay_loglik_z": [-6, 6],
                   "replay_q_rel_err": [None, 1e-4],
                   **{f"{f}_projection": [None, 0.05] for f in _FAULTS}},
        "controls": {"fp8_control": "hidden_rel_err",
                     "mtp_fp8_control": "mtp_hidden_rel_err",
                     "router_bf16_control": "router_rel_err",
                     "verify_kernel_control": "verify_kernel_rel_err",
                     "accept_bf16_control": "accept_ratio_err",
                     "replay_q_other_row_control": "replay_q_rel_err"}}}


def _load(*parts):
    with open(os.path.join(HERE, "..", *parts)) as f:
        return json.load(f)


def _real_config():
    return _load("configs", "k-exaone-236b-a23b.json")


def _real_mix():
    return _load("traffic", "serve-think.json")


def _reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}").read


def test_the_configuration_keeps_every_published_key():
    """The catalog row's ``config`` (model-configs guide) key for key;
    depth, experts held and vocabulary alone reduced, each beside its
    published value."""
    cfg = _real_config()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 153600}
    want = {"model_type": "exaone_moe", "hidden_size": 6144,
            "num_hidden_layers": 6, "num_experts": 16, "vocab_size": 19200,
            "num_attention_heads": 64, "num_key_value_heads": 8,
            "head_dim": 128, "intermediate_size": 18432,
            "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
            "num_shared_experts": 1, "routed_scaling_factor": 2.5,
            "first_k_dense_replace": 1, "sliding_window": 128,
            "sliding_window_pattern": "LLLG", "n_group": 1, "topk_group": 1,
            "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5,
            "max_position_embeddings": 262144, "scoring_func": "sigmoid",
            "mtp_layer_types": ["full_attention"],
            "mtp_sliding_windows": [0]}
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_parameters"] == {"rope_theta": 1000000,
                                      "rope_type": "default"}
    assert len(cfg["layer_types"]) == len(cfg["sliding_windows"]) == 48
    assert cfg["layer_types"][:6] == _KINDS + _KINDS[:2]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["assumed"] and "8 chips share each layer" in cfg["deployment"]
    assert cfg["source"].endswith("K-EXAONE-236B-A23B/blob/main/config.json")


def test_adapter_makes_the_preset_the_configuration_and_refuses():
    from chipbench.lib import arch_k_exaone
    from ray_tpu.models import get_config
    cfg = get_config("k-exaone-236b-a23b",
                     **arch_k_exaone.model_overrides(
                         _real_config(), {"param_dtype": "bfloat16"}))
    assert (cfg.n_layers, cfg.moe_experts, cfg.experts_here,
            cfg.vocab_size, cfg.mtp_layers) == (6, 128, 16, 19200, 1)
    assert cfg.window_layout[:6] == cfg.rope_layout[:6] == (1, 1, 1, 0, 1, 1)
    assert cfg.qk_norm_per_head and cfg.first_dense_layers == 1
    for key, value in (("scoring_func", "softmax"), ("n_group", 2),
                       ("num_nextn_predict_layers", 2),
                       ("hidden_act", "gelu")):
        with pytest.raises(SystemExit, match="cannot express"):
            arch_k_exaone.model_overrides(dict(_real_config(),
                                               **{key: value}))


def test_the_parent_program_is_refused_before_anything_starts():
    """``_refuse_unknown`` on a program that lacks the preset or a field
    (what the parent commit is to this cell: no ``mtp_layers``, no
    ``qk_norm_per_head``, no preset); the adapter itself imports nothing
    from the program."""
    from chipbench.lib import arch_k_exaone
    from chipbench.runners.serve_arch import _refuse_unknown
    ov = arch_k_exaone.model_overrides(_real_config())
    _refuse_unknown("k-exaone-236b-a23b", ov)       # this program: fine
    assert {"mtp_layers", "qk_norm_per_head"} <= set(ov)
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("k-exaone-236b-a23b", dict(ov, no_such_field=1))
    with pytest.raises(SystemExit, match="cannot express"):
        _refuse_unknown("no-such-preset", ov)
    with open(arch_k_exaone.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_the_45_s_schedule_is_the_mix_s_own():
    """Every ``--seed`` offers the same requests at the same times with
    the same token ids (``contents_seed``), all from the held eighth of
    the vocabulary; no request can outgrow the server; three prefill
    buckets; the runner finds a short and a long reference sample."""
    from chipbench.runners.serve import _buckets
    from chipbench.runners.serve_arch import cell_schedule, reference_samples
    mix, cfg = _real_mix(), _real_config()
    a, b = (cell_schedule(mix, s, 45, cfg["vocab_size"])
            for s in (7, 2900000011))
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45)
    assert a == b
    assert all(0 <= t < 19200 for r in a for t in r["prompt"])
    server = mix["server"]
    assert (server["num_slots"], server["page_size"], server["max_seq_len"],
            server["temperature"], server["prefix_cache_pages"]) == (
        32, 64, 4096, 1.0, 0)
    assert all(256 <= len(r["prompt"]) <= server["max_prompt_len"] == 1024
               and 256 <= r["max_new_tokens"] <= 3072
               and len(r["prompt"]) + r["max_new_tokens"]
               <= server["max_seq_len"] for r in a)
    assert _buckets(256, 1024) == [256, 512, 1024]
    assert mix["prompt_len"]["median"] == 512
    assert mix["output_len"]["median"] == 1536
    recs = [{"due": r["due_s"], "prompt_len": len(r["prompt"]), "done": 1,
             "tokens": [1] * r["max_new_tokens"]} for r in a]
    picked = {s["which"]: s for s in reference_samples(
        recs, a, mix["reference"])}
    assert set(picked) == {"short", "long"}
    long = picked["long"]
    assert len(long["prompt"]) + len(long["tokens"]) >= 2048


def test_bytes_by_hand():
    from chipbench.lib import spec_bytes
    cfg = _real_config()
    assert spec_bytes.page_bytes(cfg, 64) == 8 * 64 * 256 * 2 == 262144
    assert spec_bytes.row_bytes(cfg) == 8 * 256 * 2 == 4096
    assert spec_bytes.query_bytes(cfg) == 2 * 2 * 64 * 128 * 2
    assert spec_bytes.pool_layers(cfg) == 7
    # the last query sees 1,300 positions: pages 0 .. 20 in a global
    # layer; under the window of 128 the first query (at 1,298) sees
    # from 1,171 on: page 18, so pages 18, 19, 20
    assert spec_bytes.verify_pages(1300, 64) == 21
    assert spec_bytes.verify_pages(1300, 64, 128) == 3
    # a window edge exactly on a page boundary, and a context inside it
    assert spec_bytes.verify_pages(64 + 129, 64, 128) == 4 - 1
    assert spec_bytes.verify_pages(64 + 128, 64, 128) == 3
    assert spec_bytes.verify_pages(100, 64, 128) == 2
    assert spec_bytes.verify_pages(2, 64, 128) == 1
    # one live row's step in the 7 pool layers at that context: 5 window
    # layers, the global one and the module's
    pages = 5 * 3 + 2 * 21
    need = spec_bytes.verify_bytes(cfg, pages, 2 * 7, 64)
    assert need == pages * 262144 + 14 * 4096 + 7 * 65536
    assert spec_bytes.verify_flops(cfg, pages, 64) == pages * 8 * 64 * 64 \
        * 128
    # its products are a fifteenth of the time its bytes take on a v5e
    assert 10 < (need / 819e9) / (
        spec_bytes.verify_flops(cfg, pages, 64) / 197e12) < 20


def test_the_engine_s_page_count_is_the_byte_function_s():
    """``LLMEngine._count_verify_pages`` (what the cell's counters add
    up) against ``spec_bytes.verify_pages`` step by step."""
    import types

    import numpy as np
    from chipbench.lib import spec_bytes
    from ray_tpu.serve.llm_engine import EngineStats, LLMEngine
    eng = types.SimpleNamespace(
        page_size=64, cfg=types.SimpleNamespace(sliding_window=128),
        _window_layers=5, _pool_layers=7, stats=EngineStats())
    eng._add_pages = types.MethodType(LLMEngine._add_pages, eng)
    lengths = np.asarray([2, 100, 192, 193, 1300, 4096])
    LLMEngine._count_verify_pages(eng, lengths)
    full = sum(spec_bytes.verify_pages(int(n), 64) for n in lengths)
    win = sum(spec_bytes.verify_pages(int(n), 64, 128) for n in lengths)
    assert eng.stats.decode_pages_read == 2 * full + 5 * win
    assert eng.stats.window_pages_read == 5 * win
    assert eng.stats.window_pages_skipped == 5 * (full - win)


def _record(**serve):
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "config": _real_config(), "mix": _real_mix(),
            "serve": {"stats0": {}, "stats1": {}, **serve}}


def test_trace_patterns_find_the_step_s_two_landmarks():
    from chipbench.lib import spec_trace
    pats = spec_trace.patterns(_real_config())
    head = ("%fusion.455 = f32[66,19200]{1,0} fusion(bf16[66,6144]{1,0} %a, "
            "bf16[6144,19200]{1,0} %lm_head), kind=kOutput")
    module_in = ("%fusion.122 = bf16[66,6144]{1,0} fusion(bf16[66,12288]"
                 "{1,0} %x, bf16[12288,6144]{1,0} %eh_proj), kind=kOutput")
    embed = "%gather.1 = bf16[66,6144]{1,0} gather(bf16[19200,6144]{1,0} %e)"
    assert pats["head"].search(head) and not pats["head"].search(embed)
    assert pats["module_in"].search(module_in)
    assert not pats["module_in"].search(head)
    assert not pats["head"].search(module_in)


def test_new_readers_on_a_hand_made_record():
    """Each reader on the counters and reductions it reads, and on a
    record that has none (a parent commit's): nothing, and no raise."""
    run = _record(
        stats0={"drafts_proposed": 100, "drafts_accepted": 20,
                "step_tokens": 119},
        stats1={"drafts_proposed": 1100, "drafts_accepted": 320,
                "step_tokens": 1417})
    assert _reader("mtp_accept_share")(run) == pytest.approx(0.3)
    assert _reader("spec_tokens_per_step_mean")(run) == pytest.approx(1.298)
    run["spans"] = {"modules": {"engine_decode_block": {"total_s": 2.8},
                                "engine_prefill": {"total_s": 0.4}}}
    run["spec_trace"] = {
        "parts": {"verify": 2.0, "accept": 0.1, "draft": 0.7},
        "kernel": {"runs": 1400.0, "seconds": 0.35}}
    assert _reader("spec_step_device_ms")(run) == pytest.approx(14.0)
    assert _reader("mtp_draft_time_share")(run) == pytest.approx(0.25)
    # 20 live rows a step at ~1,300 positions: 57 pages and 14 rows a
    # row's step over the 7 pool layers
    steps = 100
    run["traced"] = {
        "stats0": {"pool_layer_steps": 0, "decode_pages_read": 0,
                   "decode_rows_written": 0},
        "stats1": {"pool_layer_steps": 7 * steps,
                   "decode_pages_read": 57 * 20 * steps,
                   "decode_rows_written": 14 * 20 * steps}}
    from chipbench.lib import spec_bytes
    need = spec_bytes.verify_bytes(_real_config(), 57 * 20 * 200,
                                   14 * 20 * 200, 64)
    assert _reader("paged_verify_roofline_share")(run) == pytest.approx(
        need / 819e9 / 0.35)
    assert 0 < _reader("paged_verify_roofline_share")(run) <= 1
    bare = _record()
    bare["trace"], bare["spans"], bare["spec_trace"] = {}, {}, {}
    for name in NEW_METRICS:
        assert _reader(name)(bare) is None, name


def test_new_readers_on_a_trace_recorded_on_the_chip(monkeypatch, capsys):
    """``spec.xplane.pb`` (my chip run, PR 46): a small drafting engine
    under the program's own names (``engine_decode_block``: blocks of 4
    verify steps over 2 + 1 pool layers, head ``[256, 512]``, module
    input ``[512, 256]``), traced with the Python tracer off.  The
    split finds all three parts, the kernel three executions a step."""
    from chipbench.lib import spans, spec_trace
    path = os.path.join(HERE, "spec.xplane.pb")
    cfg = {"hidden_size": 256, "vocab_size": 512, "num_hidden_layers": 2,
           "num_nextn_predict_layers": 1}
    red = spec_trace.reduce(path, cfg)
    parts, kernel = red["parts"], red["kernel"]
    assert set(parts) == {"verify", "accept", "draft"}
    assert parts["verify"] > parts["draft"] > parts["accept"] > 0
    assert kernel["runs"] > 0 and kernel["runs"] % 3 == 0
    run = {"config": cfg, "spec_trace": red,
           "spans": spans.reduce_spans(path)}
    block = run["spans"]["modules"]["engine_decode_block"]["total_s"]
    assert sum(parts.values()) <= block
    assert sum(parts.values()) > 0.5 * block
    steps = kernel["runs"] / 3
    assert _reader("spec_step_device_ms")(run) == pytest.approx(
        1e3 * block / steps)
    assert 0 < _reader("mtp_draft_time_share")(run) < 0.5
    # a parent commit's trace: no module, nothing to split
    mini = os.path.join(HERE, "mini.xplane.pb")
    assert spec_trace.reduce(mini, cfg)["parts"] == {}
    # and where a drafting configuration's trace shows no split, the two
    # metrics fall silent with a line that says so
    monkeypatch.setattr(spec_trace.trace, "find_xplane", lambda _: mini)
    silent = {"config": cfg, "trace_dir": HERE}
    assert _reader("mtp_draft_time_share")(silent) is None
    assert '"chipbench": "spec_trace_no_split"' in capsys.readouterr().out


def test_benchmark_json_lists_the_cell_under_every_metric_it_reports():
    """Written as "is a subset of": a later PR's metric may list the cell
    too."""
    bench = _load("..", "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone-236b-a23b", "serve-think", 1)
    assert len(cell["why"]) <= 200
    assert [(c["file"], c["reduced"], c["source"]) for c in bench["configs"]
            if c["name"] == cell["config"]] == [
        ("chipbench/configs/k-exaone-236b-a23b.json",
         _real_config()["reduced"], _real_config()["source"])]
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert {
        "serve_tpot_mean_ms", "engine_ttft_p95_ms", "tpot_p95_ms",
        "paged_time_share", "prefill_time_share", "slot_wait_mean_ms",
        "engine_queue_wait_p95_ms", "engine_host_share", "tpot_stepping_ms",
        "tpot_prefill_stall_ms", "tpot_block_tail_ms",
        "prefill_padded_share", "prefill_stall_trace_error",
        "kv_rows_written_mean", "moe_experts_touched_mean",
        "window_pages_skipped_share", "moe_time_share",
        *NEW_METRICS} <= lists
    # moe_time_share's reader finds the expert layer by these two names
    config = _real_config()
    assert (config["moe_num_primary_experts"], config["moe_ffn_hidden_size"]
            ) == (config["num_experts"], config["moe_intermediate_size"])
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tpot_mean_ms"
            assert os.path.exists(os.path.join(
                HERE, "..", "metrics", m["name"] + ".py"))


def test_runner_end_to_end_on_the_cpu():
    """``serve_mtp.run`` with the toy configuration and mix: cluster,
    replica, rehearsed warm-up, window at temperature 1.0, reference on a
    short and a long request, the record every serve reader reads."""
    from chipbench.metrics import (kv_rows_written_mean,
                                   moe_experts_touched_mean,
                                   serve_tpot_mean_ms,
                                   window_pages_skipped_share)
    from chipbench.runners import serve_mtp

    lines = []
    record = serve_mtp.run({
        "cell": {"name": "toy.serve", "config": "toy", "chips": 1},
        "config": TOY, "mix": TOY_MIX, "seed": 3, "seed31": 3,
        "seconds": 3.0, "trace": False, "allow_cpu": True,
        "say": lambda what, **facts: lines.append((what, facts))})
    assert record["kind"] == "serve" and record["failed"] == 0
    checks = dict(record["checks"])
    # the CPU is not the chip, and has no Pallas kernel to resolve to
    for not_here in ("platform_tpu", "paged_decode_is_pallas",
                     "experts_decode_is_pallas"):
        assert checks.pop(not_here) is False
    assert all(checks.values()), (checks, record["compared"])
    done = dict(lines)["serve_done"]
    assert set(record["phases"]) == {
        "cluster_s", "replica_s", "warm_s", "window_s", "drained_s",
        "reference_s"}
    assert [m["which"] for m in done["reference"]] == ["short", "long"]
    short, long = done["reference"]
    # every limit is of a reading a sample has, but the window's own
    assert set(TOY_MIX["reference"]["limits"]) - {"window_accept_err"} <= (
        set(short))
    window = done["window"]
    assert record["compared"]["window.accept_err"]["value"] == pytest.approx(
        abs(window["accepted"] / window["drafts"]
            - window["accept_expected"]))
    # a program that lets every draft stand is outside the same limit
    assert record["compared"]["control.window.always_accept"] == {
        "value": pytest.approx(1 - window["accept_expected"]),
        "limit": [0.3, None]}
    for m in (short, long):
        # the engine's own block program stepped rows of this request
        assert m["replay_state_ok"] == 1 and m["replay_rows"] >= 2
        assert m["replay_q_rel_err"] < 1e-4 < m["replay_q_other_row_control"]
        assert m["replay_drafts"] == 2 * 4 * m["replay_rows"]
        assert 0 < m["accept_expected_mean"] < 1
        print({k: v for k, v in m.items()
               if k.startswith(("replay", "served", "accept", "module"))})
    assert "no_window_projection" not in long       # faults_in: first
    assert long["hidden_rel_err"] < 1e-4 > long["mtp_hidden_rel_err"]
    assert record["compared"]["short.hidden_rel_err"] == {
        "value": short["hidden_rel_err"], "limit": [None, 1e-4]}
    # a control is judged against the far side of its reading's limit
    assert record["compared"]["control.short.accept_bf16_control"] == {
        "value": short["accept_bf16_control"], "limit": [1e-5, None]}
    assert short["fp8_control"] > 1e-2 and short["mtp_fp8_control"] > 1e-2
    assert short["router_bf16_control"] > 1e-3 > short["router_rel_err"]
    assert short["verify_kernel_control"] > 1e-2
    assert short["accept_bf16_control"] > 1e-4 > short["accept_ratio_err"]
    assert 0 < short["accept_probability_mean"] < 1
    for fault in _FAULTS:
        assert short[f"{fault}_control"] > 1e-3, fault
    assert 0 < _reader("mtp_accept_share")(record) < 1
    assert _reader("spec_tokens_per_step_mean")(record) == pytest.approx(
        1 + _reader("mtp_accept_share")(record), rel=0.1)
    # two rows a live row's step in each of the 7 pool layers
    assert 0 < kv_rows_written_mean.read(record) <= 2 * 4
    assert 0 < window_pages_skipped_share.read(record) < 1
    assert serve_tpot_mean_ms.read(record) > 0
    # 4 of 8 experts are held: a step touches some of them
    assert 0 < moe_experts_touched_mean.read(record) <= 4
    for name in ("spec_step_device_ms", "mtp_draft_time_share",
                 "paged_verify_roofline_share"):
        assert _reader(name)(record) is None, name  # no trace was taken
