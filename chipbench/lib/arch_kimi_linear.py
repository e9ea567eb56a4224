"""From Kimi-Linear-48B-A3B's published ``config.json`` keys
(``model_type: kimi_linear``, as
``chipbench/configs/kimi-linear-48b-a3b.json`` holds them) to overrides
of the program's preset: what ``lib/arch_kanana2.py`` is for Kanana-2.
Named by the configuration file's ``program.adapter`` (see
``chipbench/README-arch.md`` and ``README-kda.md``).

``num_experts`` of the file is how many experts THIS chip holds (it is
listed in ``reduced``); the router's width is the published count,
``published.num_experts``; ``experts_held_first`` is the first id held.
``linear_attn_config`` counts its layers from 1."""

from chipbench.lib.configs import _dtype


def layer_types(config: dict) -> tuple:
    """The program's block class of each layer, from the file's two
    lists (``kda_layers`` and ``full_attn_layers``, counted from 1)."""
    lin = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda | full != set(range(1, n + 1)) or kda & full:
        raise SystemExit("kda_layers and full_attn_layers do not name "
                         "every layer once")
    return tuple("kda" if i + 1 in kda else "full_attention"
                 for i in range(n))


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's blocks cannot
    express is refused."""
    c, lin = config, config["linear_attn_config"]
    for key, want in (("hidden_act", "silu"), ("q_lora_rank", None),
                      ("rope_scaling", None), ("mla_use_nope", True),
                      ("moe_router_activation_func", "sigmoid"),
                      ("moe_renormalize", True), ("num_expert_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's blocks "
                             "cannot express this configuration")
    kinds = layer_types(c)
    # the pattern's period: from one latent layer to the next
    at = [i for i, k in enumerate(kinds) if k == "full_attention"]
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["model_max_length"],
        "rope_theta": None,                         # mla_use_nope
        "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "layer_types": kinds, "layer_period": at[1] - at[0],
        "linear_key_heads": lin["num_heads"],
        "linear_value_heads": lin["num_heads"],
        "linear_key_head_dim": lin["head_dim"],
        "linear_value_head_dim": lin["head_dim"],
        "linear_conv_kernel": lin["short_conv_kernel_size"],
        "linear_gate_rank": lin["head_dim"],
        "moe_experts": c["published"]["num_experts"],
        "moe_experts_held": c["num_experts"],
        "moe_held_first": c.get("experts_held_first", 0),
        "moe_top_k": c["num_experts_per_token"],
        "moe_d_ff": c["moe_intermediate_size"],
        "moe_act": "silu", "moe_dropless": True,
        "moe_scoring": "sigmoid",
        "moe_route_scale": float(c["routed_scaling_factor"]),
        "moe_shared_experts": c["num_shared_experts"],
        "first_dense_layers": c["first_k_dense_replace"],
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
