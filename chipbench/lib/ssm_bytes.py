"""Bytes a Mamba-2 layer and a LatentMoE layer REQUIRE in a decode step,
from the published sizes (``model_type: nemotron_h`` keys).

A decode step of one live row in one Mamba-2 layer reads and writes that
row's state (``heads * head_dim * state`` float32 each way), reads and
writes its convolution tail (``taps - 1`` inputs of every channel of
``[x; B; C]``, bfloat16), reads ``delta u``, the decay over the same
lanes and ``B``, ``C`` and writes the output (float32, some 100 KB).  The
state is 98% of it: the kernel is a mover of state, so its bound is HBM
bandwidth; its arithmetic (``decode_flops``) is a hundredth of that time
on a v5e.

An expert layer's decode step reads the two matrices of each HELD expert
a live row chose, and once a step the shared expert's two, the latent's
two projections and the router."""


def _sizes(cfg: dict):
    h, p, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
               cfg["ssm_state_size"])
    return h, p, n, cfg["n_groups"], h * p + 2 * cfg["n_groups"] * n


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state in one layer (float32)."""
    h, p, n, _, _ = _sizes(cfg)
    return h * p * n * 4


def decode_row_bytes(cfg: dict) -> int:
    """What one live row's decode step moves in one layer."""
    h, p, n, g, channels = _sizes(cfg)
    tail = (cfg["conv_kernel"] - 1) * channels * 2
    vectors = (3 * h * p + 2 * g * n) * 4      # delta u, decay, y; B, C
    return 2 * state_bytes(cfg) + 2 * tail + vectors


def decode_flops(cfg: dict) -> int:
    """Operations of one row's step in one layer: decay, the rank-one
    write, ``H C``: 5 a state element."""
    h, p, n, _, _ = _sizes(cfg)
    return 5 * h * p * n


def expert_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """One routed expert's two matrices in the latent (bf16 by
    default)."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"] \
        * bytes_per_weight


def layer_step_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """What an expert layer reads once a step whatever the routing: the
    shared expert, the latent's two projections, the router over all the
    published experts."""
    d = cfg["hidden_size"]
    return bytes_per_weight * d * (
        2 * cfg["moe_shared_expert_intermediate_size"]
        + 2 * cfg["moe_latent_size"]
        + cfg["published"]["n_routed_experts"])


def latent_moe_bytes(cfg: dict, experts_touched: float,
                     layer_steps: float) -> float:
    """``experts_touched`` summed over ``layer_steps`` layer steps (the
    engine's ``moe_experts_touched`` / ``moe_layer_steps``) -> bytes
    those layer steps had to read."""
    return (experts_touched * expert_bytes(cfg)
            + layer_steps * layer_step_bytes(cfg))
