"""From Olmo-Hybrid's published ``config.json`` keys (as
``chipbench/configs/olmo-hybrid-7b.json`` holds them) to overrides of the
program's preset: what ``lib/arch_smallthinker.py`` is for SmallThinker.
Named by the configuration file's ``program.adapter`` (see
``chipbench/README-arch.md`` and ``README-hybrid.md``)."""

from chipbench.lib.configs import _dtype


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    configuration as the file holds it.  What the program's blocks cannot
    express is refused."""
    c = config
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("rope_parameters", {"rope_theta": None})):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's blocks "
                             "cannot express this configuration")
    n = c["num_hidden_layers"]
    kinds = set(c["layer_types"][:n])
    if not kinds <= {"linear_attention", "full_attention"}:
        raise SystemExit(f"layer_types {sorted(kinds)}: the program has "
                         "no such block class")
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": n, "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["hidden_size"] // c["num_attention_heads"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": None, "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
        "qk_norm": True, "post_norm": True,
        # the published list is kept whole in the file; a model cut in
        # depth runs its first n entries
        "layer_types": tuple(c["layer_types"][:n]),
        "linear_key_heads": c["linear_num_key_heads"],
        "linear_value_heads": c["linear_num_value_heads"],
        "linear_key_head_dim": c["linear_key_head_dim"],
        "linear_value_head_dim": c["linear_value_head_dim"],
        "linear_conv_kernel": c["linear_conv_kernel_dim"],
        "linear_allow_neg_eigval": bool(c["linear_allow_neg_eigval"]),
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
