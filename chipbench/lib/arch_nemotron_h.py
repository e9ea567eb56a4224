"""From Nemotron-3-Super's published ``config.json`` keys (``model_type:
nemotron_h``, as ``chipbench/configs/nemotron-3-super-120b-a12b.json``
holds them) to overrides of the program's preset: what
``lib/arch_smallthinker.py`` is for SmallThinker.  Named by the
configuration file's ``program.adapter`` (see ``chipbench/README-arch.md``
and ``README-ssm.md``).

``hybrid_override_pattern`` of the file is the run of layers THIS chip
holds, ``num_hidden_layers`` letters of it; ``n_routed_experts`` how many
experts it holds and ``vocab_size`` its rows of embedding and head (all
four are listed in ``reduced``); the router's width is the published
count, ``published.n_routed_experts``; ``experts_held_first`` is the
first id held.  Nothing is imported from the program: a parent commit
without the preset is refused by the runner, not by an ImportError."""

from chipbench.lib.configs import _dtype

LAYER_TYPES = {"M": "mamba2", "E": "latent_moe", "*": "attention_only"}


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's layers cannot
    express is refused."""
    c = config
    for key, want in (("mamba_hidden_act", "silu"),
                      ("mlp_hidden_act", "relu2"), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mlp_bias", False),
                      ("use_bias", False), ("use_conv_bias", True),
                      ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("n_shared_experts", 1),
                      ("sliding_window", None),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's layers "
                             "cannot express this configuration")
    n = c["num_hidden_layers"]
    pattern = c["hybrid_override_pattern"][:n]
    if len(pattern) != n or set(pattern) - set(LAYER_TYPES):
        raise SystemExit(f"hybrid_override_pattern {pattern!r}: not "
                         f"{n} letters of {sorted(LAYER_TYPES)}")
    if c["mamba_num_heads"] * c["mamba_head_dim"] != c["expand"] * c[
            "hidden_size"]:
        raise SystemExit("mamba heads x head_dim is not expand x hidden")
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": n, "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": None, "norm_eps": float(c["layer_norm_epsilon"]),
        "tie_embeddings": False,
        "layer_types": tuple(LAYER_TYPES[letter] for letter in pattern),
        "mamba_heads": c["mamba_num_heads"],
        "mamba_head_dim": c["mamba_head_dim"],
        "ssm_state_size": c["ssm_state_size"],
        "mamba_groups": c["n_groups"],
        "mamba_conv_kernel": c["conv_kernel"],
        "mamba_chunk": c["chunk_size"],
        "moe_experts": c["published"]["n_routed_experts"],
        "moe_experts_held": c["n_routed_experts"],
        "moe_held_first": c.get("experts_held_first", 0),
        "moe_top_k": c["num_experts_per_tok"],
        "moe_d_ff": c["moe_intermediate_size"],
        "moe_act": "relu2", "moe_dropless": True,
        "moe_scoring": "sigmoid",
        "moe_route_scale": float(c["routed_scaling_factor"]),
        "moe_latent_size": c["moe_latent_size"],
        "moe_shared_d_ff": c["moe_shared_expert_intermediate_size"],
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
