"""From Granite 4.0-H's published ``config.json`` keys (``model_type:
granitemoehybrid``, as ``chipbench/configs/granite-4.0-h-micro.json``
holds them) to overrides of the program's preset: what
``lib/arch_nemotron_h.py`` is for Nemotron-H.  Named by the
configuration file's ``program.adapter`` (see ``chipbench/README-arch.md``
and ``README-ssm-dense.md``).

Nothing is imported from the program: a parent commit without the preset
or the multipliers' fields is refused by the runner, not by an
ImportError."""

from chipbench.lib.configs import _dtype

LAYER_TYPES = {"mamba": "mamba2_mlp", "attention": "full_attention"}


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's blocks cannot
    express is refused: routed experts, a bias, positions, a group count
    that does not divide the heads."""
    c = config
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True),
                      ("num_local_experts", 0), ("num_experts_per_tok", 0),
                      ("position_embedding_type", "nope"),
                      ("normalization_function", "rmsnorm"),
                      ("rope_scaling", None)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's blocks "
                             "cannot express this configuration")
    n = c["num_hidden_layers"]
    kinds = c["layer_types"][:n]
    if len(kinds) != n or set(kinds) - set(LAYER_TYPES):
        raise SystemExit(f"layer_types {sorted(set(kinds))}: not {n} of "
                         f"{sorted(LAYER_TYPES)}")
    heads, groups = c["mamba_n_heads"], c["mamba_n_groups"]
    if heads % groups:
        raise SystemExit(f"mamba_n_groups {groups} does not divide "
                         f"mamba_n_heads {heads}")
    if heads * c["mamba_d_head"] != c["mamba_expand"] * c["hidden_size"]:
        raise SystemExit("mamba heads x head_dim is not expand x hidden")
    if c["shared_intermediate_size"] != c["intermediate_size"]:
        raise SystemExit("shared_intermediate_size differs from "
                         "intermediate_size: which is the SwiGLU's?")
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": n, "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        "d_ff": c["shared_intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": None, "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "layer_types": tuple(LAYER_TYPES[k] for k in kinds),
        "mamba_heads": heads, "mamba_head_dim": c["mamba_d_head"],
        "ssm_state_size": c["mamba_d_state"], "mamba_groups": groups,
        "mamba_conv_kernel": c["mamba_d_conv"],
        "mamba_chunk": c["mamba_chunk_size"],
        "embedding_multiplier": float(c["embedding_multiplier"]),
        "residual_multiplier": float(c["residual_multiplier"]),
        "attention_multiplier": float(c["attention_multiplier"]),
        "logits_scaling": float(c["logits_scaling"]),
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
