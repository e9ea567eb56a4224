"""Ulysses (DeepSpeed-style) sequence parallelism via head/sequence all-to-all.

Like ring attention (ray_tpu/ops/ring_attention.py), this is a long-context
primitive absent from the reference (SURVEY.md §5 "Long-context": no Ulysses
anywhere).  The sequence axis is sharded over the mesh ``context`` axis; an
``all_to_all`` swaps the shard dimension from sequence to heads, so each
device runs *exact* full-sequence attention for ``H/N`` heads with any local
kernel (the Pallas flash kernel on TPU), then a second all-to-all swaps back.

Trade-off vs ring attention: two all-to-alls per layer (O(S·H·D/N) bytes over
ICI) instead of N ppermute steps, and the full [S] sequence is materialized
per device for its head slice — better when heads ≥ ring size and the flash
kernel dominates; ring is better when S/N is all that fits in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import Mesh, PartitionSpec as P


def ulysses_attention_local(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            axis_name: str, causal: bool = True,
                            sm_scale: Optional[float] = None,
                            impl: str = "auto") -> jax.Array:
    """Per-shard Ulysses attention; call inside shard_map over ``axis_name``.

    q: local shard [B, S_local, H, D]; k/v: [B, S_local, KvH, D].  Requires
    H % axis_size == 0 and KvH % axis_size == 0 (repeat KV first for GQA
    ratios finer than the axis size).
    """
    n = _axis_size(axis_name)
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            f"heads {q.shape[2]}/kv_heads {k.shape[2]} not divisible by "
            f"sequence-parallel axis size {n}")
    # [B, S/N, H, D] -> [B, S, H/N, D]: split heads, concat sequence.
    swap = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                             split_axis=2, concat_axis=1, tiled=True)
    q_full, k_full, v_full = swap(q), swap(k), swap(v)

    from ray_tpu.ops.attention import attention
    out = attention(q_full, k_full, v_full, causal=causal,
                    sm_scale=sm_scale, impl=impl)
    # [B, S, H/N, D] -> [B, S/N, H, D]
    return jax.lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                              concat_axis=2, tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      mesh: Mesh, axis_name: str = "context",
                      causal: bool = True, sm_scale: Optional[float] = None,
                      impl: str = "auto",
                      batch_axes=("data", "fsdp")) -> jax.Array:
    """Global-array entry point: shard_maps over the context axis.

    q/k/v are global [B, S, H, D] arrays inside jit; the sequence dimension
    is (re)sharded over ``axis_name``, each shard all-to-alls into full-
    sequence/partial-heads layout, attends locally, and swaps back.
    """
    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    spec = P(batch_axes, axis_name, None, None)
    fn = functools.partial(ulysses_attention_local, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale, impl=impl)
    return _shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
