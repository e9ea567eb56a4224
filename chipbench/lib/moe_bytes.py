"""Bytes an expert layer's decode step REQUIRES from HBM, from the
published sizes: the three matrices (gate, up, down) of each expert that
at least one live row chose.  Activations, the router and the gates are
left out: at 33 rows they are under 0.1% of one expert."""


def expert_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """One expert's three matrices (bf16 by default)."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"] \
        * bytes_per_weight


def touched_bytes(cfg: dict, experts_touched: float,
                  bytes_per_weight: int = 2) -> float:
    """``experts_touched`` summed over layer steps (the engine's
    ``moe_experts_touched``) -> bytes those layer steps had to read."""
    return experts_touched * expert_bytes(cfg, bytes_per_weight)


def layer_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Every expert of one layer: what a formulation that reads them all
    moves whatever the routing."""
    return cfg["moe_num_primary_experts"] * expert_bytes(cfg,
                                                         bytes_per_weight)
