"""Ring attention: exact causal attention over a context-parallel mesh axis.

Sequence/context parallelism is absent from the reference (SURVEY.md §5
"Long-context": no ring attention, no Ulysses anywhere); here it is a
first-class op.  The sequence axis is sharded over the mesh's ``context``
axis; each device holds a [B, S/N, H, D] shard of q/k/v, and K/V shards
rotate around the ICI ring via ``jax.lax.ppermute`` while every device
accumulates its local q block's attention with an online softmax — flash
attention's rescaling trick applied across devices.  The whole thing is
differentiable (scan + ppermute autodiff), so the same code path serves
training.

Causal skipping: a device only attends to K/V shards at or before its own
global offset, so steps with fully-masked blocks skip the matmuls via
``lax.cond``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q_scaled, k, v, q_off, kv_off, causal, block_size):
    """Unnormalized blockwise attention; returns (m, l, o) partials."""
    bq = q_scaled.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q_scaled, k,
                   preferred_element_type=jnp.float32)
    if causal:
        rows = q_off + jnp.arange(bq)[:, None]
        cols = kv_off + jnp.arange(block_size)[None, :]
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # [b, h, q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str, causal: bool = True,
                         sm_scale: Optional[float] = None) -> jax.Array:
    """Per-shard ring attention; call inside shard_map over ``axis_name``.

    q/k/v: local shards [B, S_local, H, D]; sequence is sharded contiguously
    (shard i holds global positions [i*S_local, (i+1)*S_local)).
    """
    b, s_local, h, d = q.shape
    n = _axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else d ** -0.5
    q_scaled = q.astype(jnp.float32) * scale
    q_off = my * s_local

    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        m, l, acc, kv = carry
        k_t, v_t = kv
        src = (my - t) % n           # which shard's kv we currently hold
        kv_off = src * s_local

        def attend(_):
            ms, ls, os_ = _block_attend(q_scaled, k_t, v_t, q_off, kv_off,
                                        causal, s_local)
            m_new = jnp.maximum(m, ms)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(ms - m_new)
            l_new = l * alpha + ls * beta
            acc_new = acc * alpha[..., None] + os_ * beta[..., None]
            return m_new, l_new, acc_new

        if causal:
            # Shards strictly after ours in global order are fully masked.
            m, l, acc = jax.lax.cond(kv_off <= q_off, attend,
                                     lambda _: (m, l, acc), None)
        else:
            m, l, acc = attend(None)
        kv = jax.lax.ppermute((k_t, v_t), axis_name, perm)
        return (m, l, acc, kv), None

    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, acc0, (k, v)),
                                     jnp.arange(n))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, axis_name: str = "context",
                   causal: bool = True,
                   sm_scale: Optional[float] = None,
                   batch_axes=("data", "fsdp")) -> jax.Array:
    """Global-array entry point: shard_maps over the context axis.

    q/k/v are global [B, S, H, D] arrays inside jit; the sequence dimension
    is (re)sharded over ``axis_name`` and attention runs as a ring.  Batch
    stays sharded over the data axes; heads/head_dim replicated across the
    ring (tensor-parallel head sharding composes outside, since shard_map
    only binds the named axes in ``in_specs``).
    """
    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    spec = P(batch_axes, axis_name, None, None)
    fn = functools.partial(ring_attention_local, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale)
    return _shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
