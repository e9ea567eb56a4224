"""Bytes and operations a Kimi Delta Attention layer REQUIRES, from the
published sizes (``linear_attn_config``: H heads, keys and values of
``head_dim``, convolutions of ``short_conv_kernel_size`` taps), whatever
layout or kernel serves them.

A decode step of one live row in one layer reads and writes that row's
state (``H * d * d`` float32 each way: 2,097,152 B at 32 heads of 128),
reads and writes its convolution tail (``taps - 1`` inputs of every
channel of ``[q; k; v]``, bfloat16: 73,728 B each way), reads ``q, k, v``,
the decay a key channel and the write strength a head and writes the
output.  The state is 96% of it: the kernel is a mover of state, so its
bound is HBM bandwidth; its arithmetic (``decode_flops``) is a hundredth
of that time on a v5e."""


def _sizes(cfg: dict):
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state in one layer (float32)."""
    h, d, _ = _sizes(cfg)
    return h * d * d * 4


def decode_row_bytes(cfg: dict) -> int:
    """What one live row's decode step moves in one layer."""
    h, d, taps = _sizes(cfg)
    tail = (taps - 1) * 3 * h * d * 2
    # q, k, v, the decay a channel, the output (float32) and beta
    vectors = (5 * h * d + h) * 4
    return 2 * state_bytes(cfg) + 2 * tail + vectors


def decode_flops(cfg: dict) -> int:
    """Operations of one row's step in one layer: decay, ``S^T k``, the
    rank-one write, ``S^T q``: 7 a state element."""
    h, d, _ = _sizes(cfg)
    return 7 * h * d * d


def kda_layers(cfg: dict) -> int:
    """KDA layers among the layers the configuration runs."""
    n = cfg.get("num_hidden_layers", 0)
    return sum(1 for i in (cfg.get("linear_attn_config") or {}).get(
        "kda_layers", ()) if i <= n)
