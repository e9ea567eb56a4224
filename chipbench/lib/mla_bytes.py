"""Bytes and operations a latent-attention (MLA) layer REQUIRES, from
the published sizes (H heads, latent r, rotated dr, queries dn + dr,
values dv), whatever layout or kernel serves them.

A decode step of one live row in one layer reads each cached token's row
once: ``[c | k_rope]``, ``r + dr`` bfloat16 values (1,152 B at 512 + 64)
shared by every head.  Absorbed, a cached token costs each head one
product over the whole row for its score and one over ``c`` for its
value: ``2 * H * ((r + dr) + r)`` operations (69.6 k at 32 heads): 60 a
byte, so at a v5e's ridge of 240 a byte the bound is the bytes."""


def _sizes(cfg: dict):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_rope_head_dim"])


def row_bytes(cfg: dict) -> int:
    """One cached token's row in one layer (bfloat16)."""
    _, r, dr = _sizes(cfg)
    return 2 * (r + dr)


def decode_token_flops(cfg: dict) -> int:
    """Operations one cached token costs one row's absorbed decode step
    in one layer."""
    h, r, dr = _sizes(cfg)
    return 2 * h * ((r + dr) + r)


def decode_seconds(cfg: dict, context_tokens: float, peaks: dict) -> float:
    """The least time a chip of ``peaks`` (``lib/peaks.py``) takes to
    read ``context_tokens`` cached rows and multiply them: the larger of
    the two bounds."""
    return context_tokens * max(
        row_bytes(cfg) / peaks["hbm_bytes_per_s"],
        decode_token_flops(cfg) / peaks["bf16_flops"])

