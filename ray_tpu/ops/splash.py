"""Splash-attention wrapper: JAX's production TPU attention kernel.

``jax.experimental.pallas.ops.tpu.splash_attention`` is the public
kernel (causal-grid skipping, block sizes per generation) exposed here
as ``attention(..., impl="splash")``, a comparison impl beside the
in-tree kernel (ops/flash_attention.py).  No benchmark cell runs it and
no record bears a speed for it.  Layout adapter only — inputs stay
[B, S, H, D] like every other impl.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _make_kernel(n_heads: int, q_len: int, kv_len: int, causal: bool):
    # built fresh per trace: caching the kernel object would leak arrays
    # created under one trace into the next (UnexpectedTracerError);
    # mask construction is cheap numpy and jit caching dedups the rest
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel, splash_attention_mask)
    if causal:
        mask = splash_attention_mask.CausalMask((q_len, kv_len))
    else:
        mask = splash_attention_mask.FullMask((q_len, kv_len))
    mh = splash_attention_mask.MultiHeadMask([mask] * n_heads)
    return splash_attention_kernel.make_splash_mha(
        mask=mh, head_shards=1, q_seq_shards=1)


def splash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """[B, S, H, D] x3 -> [B, S, H, D]; heads must already match
    (GQA expansion happens in ops.attention)."""
    b, s, h, d = q.shape
    kv_len = k.shape[1]
    if causal and s != kv_len:
        raise ValueError(
            "causal splash attention requires q_len == kv_len (got "
            f"{s} vs {kv_len}); decode-style queries use ops.attention "
            "with q_offset")
    scale = sm_scale if sm_scale is not None else d ** -0.5
    kernel = _make_kernel(h, s, kv_len, causal)

    def per_example(qi, ki, vi):
        # splash wants [H, S, D] and pre-scaled queries
        return kernel(qi.transpose(1, 0, 2) * scale,
                      ki.transpose(1, 0, 2),
                      vi.transpose(1, 0, 2)).transpose(1, 0, 2)

    out = jax.vmap(per_example)(q, k, v)
    return out.astype(q.dtype)


