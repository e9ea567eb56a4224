"""From SmallThinker's published ``config.json`` keys (as
``chipbench/configs/smallthinker-21b-a3b.json`` holds them) to overrides
of the program's preset: what ``lib/configs.py model_overrides`` is for
the dense block.  Named by the configuration file's
``program.adapter`` (see ``chipbench/README-arch.md``)."""

from chipbench.lib.configs import _dtype


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's block cannot
    express is refused."""
    c = config
    for key, want in (("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True), ("rope_scaling", None)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's block "
                             "cannot express this configuration")
    n = c["num_hidden_layers"]
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": n, "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
        "d_ff": c["moe_ffn_hidden_size"],
        "moe_d_ff": c["moe_ffn_hidden_size"],
        "moe_experts": c["moe_num_primary_experts"],
        "moe_top_k": c["moe_num_active_primary_experts"],
        "moe_act": "relu", "moe_dropless": True,
        "moe_router_pre_attn": True,
        "sliding_window": c["sliding_window_size"],
        # the published lists are kept whole in the file; a model cut in
        # depth runs their first n entries
        "rope_layout": tuple(c["rope_layout"][:n]),
        "window_layout": tuple(c["sliding_window_layout"][:n]),
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
