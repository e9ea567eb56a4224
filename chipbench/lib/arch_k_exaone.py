"""From K-EXAONE-236B-A23B's published ``config.json`` keys (``model_type:
exaone_moe``, as ``chipbench/configs/k-exaone-236b-a23b.json`` holds
them) to overrides of the program's preset: what
``lib/arch_kanana2.py`` is for Kanana-2.  Named by the configuration
file's ``program.adapter`` (``chipbench/README-arch.md``, ``README-mtp.md``).

``num_experts`` of the file is how many experts THIS chip holds and
``vocab_size`` its rows of embedding and head (both listed in
``reduced``); the router's width is the published count,
``published.num_experts``; ``experts_held_first`` is the first id held.
``layer_types`` keeps its published 48 entries: layer l's kind is entry
l, whatever the depth.  Nothing is imported from the program: a parent
commit without the preset is refused by the runner."""

from chipbench.lib.configs import _dtype

_KINDS = {"sliding_attention": 1, "full_attention": 0}


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's blocks cannot
    express is refused."""
    c = config
    for key, want in (("hidden_act", "silu"), ("scoring_func", "sigmoid"),
                      ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True),
                      ("num_nextn_predict_layers", 1),
                      ("mtp_layer_types", ["full_attention"]),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's blocks "
                             "cannot express this configuration")
    n, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    if set(c["layer_types"]) - set(_KINDS) or len(c["layer_types"]) < n:
        raise SystemExit(f"layer_types: not {n} of {sorted(_KINDS)}")
    if c["mlp_layer_types"][:n] != ["dense"] * dense + ["sparse"] * (
            n - dense):
        raise SystemExit("mlp_layer_types: not first_k_dense_replace "
                         "dense layers and then sparse ones")
    if c["rope_parameters"].get("rope_type", "default") != "default":
        raise SystemExit("rope_type: the program rotates unscaled")
    sliding = tuple(_KINDS[kind] for kind in c["layer_types"])
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": n, "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": float(c["rope_parameters"]["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]), "tie_embeddings": False,
        "qk_norm": True, "qk_norm_per_head": True,
        "sliding_window": c["sliding_window"],
        # a sliding layer rotates and windows, a global one does neither
        "rope_layout": sliding, "window_layout": sliding,
        "moe_experts": c["published"]["num_experts"],
        "moe_experts_held": c["num_experts"],
        "moe_held_first": c.get("experts_held_first", 0),
        "moe_top_k": c["num_experts_per_tok"],
        "moe_d_ff": c["moe_intermediate_size"],
        "moe_act": "silu", "moe_dropless": True,
        "moe_scoring": "sigmoid",
        "moe_route_scale": float(c["routed_scaling_factor"]),
        "moe_shared_experts": c["num_shared_experts"],
        "first_dense_layers": dense,
        "mtp_layers": c["num_nextn_predict_layers"],
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
