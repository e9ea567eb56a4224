"""Bytes and operations a gated-delta (linear_attention) layer REQUIRES,
from the published sizes (H value heads, keys of dk, values of dv).

A decode step of one live row in one layer reads and writes that row's
state (``H * dk * dv`` float32 each way), reads and writes its
convolution tail (``taps - 1`` inputs of every channel of ``[q; k; v]``,
bfloat16), reads ``q, k, v`` and the two gates and writes the output (a
few KB).  The state is 99% of it: the kernel is a mover of state, so its
bound is HBM bandwidth; its arithmetic (``decode_flops``) is a hundredth
of that time on a v5e."""


def _sizes(cfg: dict):
    h, hk = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return h, hk, dk, dv, 2 * hk * dk + h * dv


def state_bytes(cfg: dict) -> int:
    """One row's recurrent state in one layer (float32)."""
    h, _, dk, dv, _ = _sizes(cfg)
    return h * dk * dv * 4


def decode_row_bytes(cfg: dict) -> int:
    """What one live row's decode step moves in one layer."""
    h, _, dk, dv, channels = _sizes(cfg)
    tail = (cfg["linear_conv_kernel_dim"] - 1) * channels * 2
    vectors = (2 * h * dk + h * dv + 2 * h) * 4 + h * dv * 4
    return 2 * state_bytes(cfg) + 2 * tail + vectors


def decode_flops(cfg: dict) -> int:
    """Operations of one row's step in one layer: decay, ``S^T k``, the
    rank-one write, ``S^T q``: 7 a state element."""
    h, _, dk, dv, _ = _sizes(cfg)
    return 7 * h * dk * dv


def chunked_flops_per_token(cfg: dict, chunk: int = 64) -> float:
    """Operations a token of a prompt costs one layer in the chunked
    (WY) form with chunks of ``chunk``: the in-chunk products (``k k^T``,
    ``q k^T``, the triangular inverse by doubling, ``T [k; v]``, ``attn
    V'``) and the products with the state (``W S``, ``q S``, ``k^T
    V'``)."""
    import math
    h, _, dk, dv, _ = _sizes(cfg)
    c = chunk
    in_chunk = 2 * c * (2 * dk + 2 * math.ceil(math.log2(c)) * c
                        + dk + dv + dv)
    with_state = 2 * 3 * dk * dv
    return h * (in_chunk + with_state)
