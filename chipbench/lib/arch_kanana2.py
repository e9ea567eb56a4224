"""From Kanana-2-30B-A3B's published ``config.json`` keys
(``model_type: deepseek_v3``, as ``chipbench/configs/kanana-2-30b-a3b.json``
holds them) to overrides of the program's preset: what
``lib/arch_smallthinker.py`` is for SmallThinker.  Named by the
configuration file's ``program.adapter`` (see ``chipbench/README-arch.md``
and ``README-latent.md``).

``n_routed_experts`` of the file is how many experts THIS chip holds (it
is listed in ``reduced``); the router's width is the published count,
``published.n_routed_experts``; ``experts_held_first`` is the first id
held."""

from chipbench.lib.configs import _dtype

def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's blocks cannot
    express is refused."""
    c = config
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("q_lora_rank", None), ("rope_scaling", None),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's blocks "
                             "cannot express this configuration")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise SystemExit("qk_head_dim is not qk_nope + qk_rope")
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["qk_head_dim"], "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "rope_interleave": bool(c["rope_interleave"]),
        "moe_experts": c["published"]["n_routed_experts"],
        "moe_experts_held": c["n_routed_experts"],
        "moe_held_first": c.get("experts_held_first", 0),
        "moe_top_k": c["num_experts_per_tok"],
        "moe_d_ff": c["moe_intermediate_size"],
        "moe_act": "silu", "moe_dropless": True,
        "moe_scoring": "sigmoid",
        "moe_route_scale": float(c["routed_scaling_factor"]),
        "moe_shared_experts": c["n_shared_experts"],
        "first_dense_layers": c["first_k_dense_replace"],
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
