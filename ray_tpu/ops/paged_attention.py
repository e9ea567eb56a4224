"""Paged (block) KV-cache attention for continuous-batching decode.

The reference has no paged KV — it serves LLMs by scaling whole replicas
and batching requests (`python/ray/serve/batching.py`); its KV layout is
whatever the user's model framework allocates.  Our continuous-batching
engine (serve/llm_engine.py) originally gave every decode slot a dense
``[max_seq_len]`` cache row, so every decode step read the full row span
from HBM — serving short chats with a long cache burned bandwidth
linearly in ``max_seq_len``, and slot count was capped by
``slots * max_seq`` HBM reservation.

Paged layout instead pools KV in fixed-size pages shared by all slots:

  kv_pages:     [num_pages, kv_heads, page_size, 2*head_dim]  (per layer,
                K in [..., :head_dim], V in [..., head_dim:])
  block_tables: [rows, max_pages_per_seq] int32  (logical -> physical)

A sequence at position ``p`` occupies ``ceil((p+1)/page_size)`` pages.
The layout is dictated by TPU tiling: Mosaic DMAs slice memrefs in
(8, 128) tiles, so the page's minor dim must be a multiple of 128 —
``2*head_dim`` is exactly that for the common head_dims (64, 128, 256),
and fusing K and V makes a page one DMA instead of two.  kv_heads sits
outside (page_size, 2*head_dim) so per-head views are tile-aligned.

Two implementations:

  - ``paged_attention_xla`` — gather the table span, mask by length,
    dense attention.  Runs on every backend (the CPU test oracle and
    fallback).  It reads the whole (static) table span, so its HBM win
    comes from sizing ``max_pages_per_seq`` to the workload.
  - ``paged_attention_tpu`` — Pallas kernel: grid over rows, per-row
    ``fori_loop`` DMAs ONLY the row's occupied pages HBM->VMEM
    (double-buffered) with flash-style online softmax.  HBM traffic per
    decode step scales with actual context length — the property the
    dense row layout can't have.

``paged_attention`` dispatches by backend.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import backend_platform, xla_attention


def paged_attention_xla(q: jax.Array, kv_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array, *,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """Gather-based paged decode attention (one query token per row).

    q:            [rows, heads, head_dim]
    kv_pages:     [num_pages, kv_heads, page_size, 2*head_dim]
    block_tables: [rows, max_pages] physical page ids, position-ordered
    lengths:      [rows] number of valid positions (current pos + 1)
    returns       [rows, heads, head_dim]
    """
    rows, _, hd = q.shape
    _, kvh, ps, _ = kv_pages.shape
    # [rows, mp, kvh, ps, 2hd] -> [rows, mp*ps, kvh, 2hd] position-major
    kv = jnp.moveaxis(kv_pages[block_tables], 2, 3
                      ).reshape(rows, -1, kvh, 2 * hd)
    span = kv.shape[1]
    mask = jnp.arange(span)[None, :] < lengths[:, None]
    out = xla_attention(q[:, None], kv[..., :hd], kv[..., hd:],
                        causal=False, mask=mask, sm_scale=sm_scale)
    return out[:, 0]


def _tpu_kernel(q2: jax.Array, kv_pages: jax.Array,
                block_tables: jax.Array, lengths: jax.Array,
                sm_scale: float) -> jax.Array:
    """Pallas TPU decode kernel: per-row loop over occupied pages only.

    ``q2`` is the query padded to [rows, heads, 2*head_dim] (zeros in
    the V half) so every buffer's minor dim is lane-aligned; the zero
    half makes q2 . kv_page contract to K-only scores, and p . kv_page
    leaves the real output in the V half of the accumulator — no
    sub-tile slicing anywhere in the kernel.  The row's page count
    (ceil(length/page_size)) is a traced ``fori_loop`` bound, so pages
    past the row's context are never DMA'd.  In-kernel math stays 2-D
    per kv head (Mosaic rejects batched dot_generals).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, hd2 = q2.shape
    num_pages, kvh, ps, _ = kv_pages.shape
    g = heads // kvh

    def kernel(tables_ref, len_ref, q_ref, kv_ref, out_ref,
               kvbuf, acc_ref, m_ref, l_ref, sems):
        r = pl.program_id(0)
        length = len_ref[r]
        n_pg = pl.cdiv(length, ps)

        def get_dma(slot, i):
            return pltpu.make_async_copy(
                kv_ref.at[tables_ref[r, i]], kvbuf.at[slot],
                sems.at[slot])

        @pl.when(n_pg > 0)
        def _():
            get_dma(0, 0).start()

        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)
        qv = q_ref[0].astype(jnp.float32) * sm_scale      # [heads, 2hd]

        def body(i, _):
            slot = i % 2

            @pl.when(i + 1 < n_pg)
            def _():
                get_dma((i + 1) % 2, i + 1).start()

            get_dma(slot, i).wait()
            pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
            valid = pos < length
            for h in range(kvh):                 # static per-head 2-D ops
                lo, hi = h * g, (h + 1) * g
                kv_h = kvbuf[slot, h].astype(jnp.float32)   # [ps, 2hd]
                # zero V-half of q2 -> K-only scores
                s = jax.lax.dot_general(
                    qv[lo:hi], kv_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [g, ps]
                s = jnp.where(valid, s, -1e30)
                m_prev = m_ref[lo:hi]                       # [g, 1]
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[lo:hi] = (l_ref[lo:hi] * alpha
                                + jnp.sum(p, axis=1, keepdims=True))
                # [g, 2hd]: K-half is junk, V-half is the real p @ V
                pv = jax.lax.dot_general(
                    p, kv_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc_ref[lo:hi] = acc_ref[lo:hi] * alpha + pv
                m_ref[lo:hi] = m_new
            return 0

        jax.lax.fori_loop(0, n_pg, body, 0)
        norm = jnp.maximum(l_ref[:], 1e-30)               # [heads, 1]
        out_ref[0] = (acc_ref[:] / norm).astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, lengths
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, heads, hd2), lambda r, *_: (r, 0, 0),
                         memory_space=pltpu.VMEM),         # q2
            pl.BlockSpec(memory_space=pl.ANY),             # kv_pages (HBM)
        ],
        out_specs=pl.BlockSpec((1, heads, hd2), lambda r, *_: (r, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, ps, hd2), kv_pages.dtype),  # double-buffer
            pltpu.VMEM((heads, hd2), jnp.float32),          # acc
            pltpu.VMEM((heads, 1), jnp.float32),            # running max
            pltpu.VMEM((heads, 1), jnp.float32),            # running sum
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, heads, hd2), q2.dtype),
        name="paged_attention_decode",
    )(block_tables, lengths, q2, kv_pages)


def paged_attention_tpu(q, kv_pages, block_tables, lengths, *,
                        sm_scale: Optional[float] = None) -> jax.Array:
    hd = q.shape[-1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    q2 = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    out2 = _tpu_kernel(q2, kv_pages, block_tables,
                       lengths.astype(jnp.int32), scale)
    return out2[..., hd:]       # V half holds the attention output


def resolve_paged_impl(kv_minor: int, impl: str = "auto") -> str:
    """Which implementation ``paged_attention`` runs for a pool whose
    minor dim (``2*head_dim``) is ``kv_minor``: ``"tpu"`` or ``"xla"``.

    Only ``"auto"`` may settle for the XLA gather — off the TPU, or when
    the page is not lane-aligned (Mosaic DMA slices need a minor dim
    that is a multiple of 128, so test-size heads cannot use the
    kernel).  An explicit ``"tpu"`` with such a shape raises.
    ``RAY_TPU_PAGED_ATTENTION_IMPL=xla|tpu`` is read as an explicit
    request — the on-chip engine-machinery tests force ``xla`` so they
    can demand BIT-exact equality with lone dense generation (the
    Pallas kernel's page-wise online softmax is numerically equivalent
    but not bitwise, so greedy decode can tie-flip vs the dense
    oracle)."""
    import os
    if impl == "auto":
        impl = os.environ.get("RAY_TPU_PAGED_ATTENTION_IMPL", "auto")
    if impl == "auto":
        aligned = kv_minor % 128 == 0
        return "tpu" if aligned and backend_platform() == "tpu" else "xla"
    if impl == "tpu" and kv_minor % 128:
        raise ValueError(
            f"paged_attention impl='tpu' needs 2*head_dim % 128 == 0 "
            f"(got {kv_minor}); use impl='auto' or 'xla' for this shape")
    if impl not in ("tpu", "xla"):
        raise ValueError(f"unknown paged attention impl: {impl!r}")
    return impl


def paged_attention(q, kv_pages, block_tables, lengths, *,
                    sm_scale: Optional[float] = None,
                    impl: str = "auto") -> jax.Array:
    """Backend-dispatched paged decode attention (see module docstring
    and :func:`resolve_paged_impl`)."""
    impl = resolve_paged_impl(kv_pages.shape[-1], impl)
    fn = paged_attention_tpu if impl == "tpu" else paged_attention_xla
    return fn(q, kv_pages, block_tables, lengths, sm_scale=sm_scale)
