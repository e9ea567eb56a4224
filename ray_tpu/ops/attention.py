"""Multi-head attention with selectable implementation.

``impl``:
  - ``"xla"``    — einsum attention; runs everywhere, materializes [Sq, Sk].
  - ``"flash"``  — Pallas TPU flash kernel (ray_tpu/ops/flash_attention.py);
                   O(S) memory, fused online softmax on the MXU.
  - ``"auto"``   — flash on TPU backends, xla elsewhere.

Layout convention throughout the framework: ``q``: [batch, q_len, heads,
head_dim]; ``k``/``v``: [batch, kv_len, kv_heads, head_dim] with grouped-query
attention when ``kv_heads < heads``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def backend_platform() -> str:
    """Platform of the default backend — the ONE question every kernel
    dispatch in ops/ asks.  A backend that cannot start (chip held by
    another process, libtpu already loaded) raises here: it must never
    be read as "not a TPU", or an explicit ``impl="flash"`` would
    quietly become the Pallas interpreter or the XLA path."""
    return jax.devices()[0].platform


def resolve_impl(impl: str) -> str:
    """``"auto"`` -> flash on TPU backends, xla elsewhere."""
    if impl == "auto":
        return "flash" if backend_platform() == "tpu" else "xla"
    return impl


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, KvH, D] -> [B, S, KvH*n_rep, D] for grouped-query attention."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, sm_scale: Optional[float] = None,
                  q_offset: int = 0,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Reference einsum attention (fp32 logits/softmax, input-dtype output).

    ``q_offset``: global position of q[0] relative to k[0] — used by the ring
    attention fallback and by decode (q_len==1 at position offset).
    ``mask``: optional key-padding mask [B, Kv] (True = attend) or an
    additive/boolean [B, 1|H, Q, Kv] mask (encoders: BERT/T5 padding).
    """
    *_, q_len, heads, head_dim = q.shape
    kv_len, kv_heads = k.shape[-3], k.shape[-2]
    if kv_heads != heads:
        k = repeat_kv(k, heads // kv_heads)
        v = repeat_kv(v, heads // kv_heads)
    scale = sm_scale if sm_scale is not None else head_dim ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = jnp.arange(q_len)[:, None] + q_offset
        k_pos = jnp.arange(kv_len)[None, :]
        logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
    if mask is not None:
        if mask.ndim == 2:                      # [B, Kv] key padding
            # 0/1 integer padding masks are boolean in intent — coerce,
            # else they'd fall into the additive branch and mask nothing.
            # A float 2-D mask is ambiguous (additive -1e9 convention
            # would be silently inverted): refuse it loudly.
            if jnp.issubdtype(mask.dtype, jnp.floating):
                raise ValueError(
                    "2-D attention masks must be bool/int key-padding "
                    "masks (True/1 = attend); pass additive float masks "
                    "as [B, 1|H, Q, Kv]")
            mask = mask.astype(jnp.bool_)[:, None, None, :]
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: Optional[float] = None,
              impl: str = "auto",
              mask: Optional[jax.Array] = None,
              q_lens: Optional[jax.Array] = None) -> jax.Array:
    """Public fused attention entry point (see module docstring).
    ``q_lens`` [B] (causal): each row's real length.  The flash kernel
    skips the query spans past it and returns zeros there
    (``flash_attention``); the other impls compute every position, which
    is as good to a caller that reads the real ones."""
    impl = resolve_impl(impl)
    if impl == "flash" and mask is not None:
        impl = "xla"       # the Pallas kernel has no padding-mask path
    if impl == "flash":
        heads, kv_heads = q.shape[-2], k.shape[-2]
        if kv_heads != heads:
            k = repeat_kv(k, heads // kv_heads)
            v = repeat_kv(v, heads // kv_heads)
        from ray_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               sm_scale=sm_scale, q_lens=q_lens)
    if impl == "xla":
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                             mask=mask)
    raise ValueError(f"unknown attention impl: {impl!r}")
