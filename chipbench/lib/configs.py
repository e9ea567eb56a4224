"""From a published ``config.json`` (Hugging Face key names, kept in
``chipbench/configs/<name>.json``) to the program's preset-plus-overrides,
which both entry points take (``ShardedRunConfig.model_overrides``,
``build_app(config_overrides=...)``)."""

import json
import os

from chipbench.lib.cluster import ROOT


def load_benchmark() -> dict:
    """BENCHMARK.json; under CHIPBENCH_REHEARSAL=1 its cells and
    configurations are replaced by the toys of chipbench/rehearsal.json
    (the metric lists stay)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if os.environ.get("CHIPBENCH_REHEARSAL") == "1":
        toys = load_json("chipbench/rehearsal.json")
        bench["configs"], bench["workloads"] = (toys["configs"],
                                                toys["workloads"])
    return bench


def load_json(rel_path: str) -> dict:
    with open(os.path.join(ROOT, rel_path)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    """``(cell, config_entry, config, mix)`` by the names in
    BENCHMARK.json; the mix is ``chipbench/traffic/<traffic>.json``."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, entry, load_json(entry["file"]),
            load_json(f"chipbench/traffic/{cell['traffic']}.json"))


def _dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def model_overrides(config: dict, extra: dict = None) -> dict:
    """Overrides of ``TransformerConfig`` that make the named preset the
    published model.  What the block cannot express is refused."""
    c = config
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    if hd * c["num_attention_heads"] != c["hidden_size"]:
        raise SystemExit("head_dim * heads != hidden_size: the program's "
                         "block cannot express this configuration")
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("mlp_bias", False), ("sliding_window", None)):
        if c.get(key, want) != want:
            raise SystemExit(f"{key}={c[key]!r}: the program's block "
                             "cannot express this configuration")
    out = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "d_ff": c["intermediate_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c["rms_norm_eps"]),
        "tie_embeddings": bool(c["tie_word_embeddings"]),
    }
    for key, value in (extra or {}).items():
        out[key] = _dtype(value) if key in ("dtype", "param_dtype") else value
    return out
