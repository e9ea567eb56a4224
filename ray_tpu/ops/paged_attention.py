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

  kv_pages:     [layers, num_pages, kv_heads, page_size, 2*head_dim]
                (ONE stacked pool for the whole model; K in
                [..., :head_dim], V in [..., head_dim:])
  block_tables: [rows, max_pages_per_seq] int32  (logical -> physical)

A sequence at position ``p`` occupies ``ceil((p+1)/page_size)`` pages,
the same page ids in every layer.  The layout is dictated by TPU
tiling: Mosaic DMAs slice memrefs in (8, 128) tiles, so the page's
minor dim must be a multiple of 128 — ``2*head_dim`` is exactly that
for the common head_dims (64, 128, 256), and fusing K and V makes a
page one DMA instead of two.  kv_heads sits outside (page_size,
2*head_dim) so per-head views are tile-aligned.

ADDRESSING.  The pool is addressed, never sliced: a reader names
``[layer, page]`` (the kernel's DMA source, the oracle's gather index),
the writer ``[layer, page, :, offset]`` (``write_kv_pages`` below,
called by models/gpt.py ``_decode_attend_paged``), on the whole stacked
array.  ``pool[layer]`` as a value is a copy of one layer's pool (108 MB
at SmolLM2-360M's default pool) — under the layer scan one such copy,
its relayout and its write-back a layer, which was 85% of the serving
cell's device time (PERF.md, PR 25).  So the pool rides the model's
layer scan and the engine's step scan as loop-carried, donated state,
and everything here takes it whole plus a ``layer`` index (an int, or a
traced scalar under the layer scan).

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

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import backend_platform, xla_attention


def write_kv_pages(pool: jax.Array, kv: jax.Array,
                   block_tables: jax.Array, positions: jax.Array, *,
                   layer=0) -> jax.Array:
    """Write a call's fused K/V into layer ``layer`` of the pool, in
    place when the pool is loop-carried and donated; returns the pool.

    pool:      [layers, num_pages, kv_heads, page_size, 2*head_dim]
    kv:        [rows, T, kv_heads, 2*head_dim]
    positions: [rows, T] absolute positions, contiguous along T; a T > 1
               window must start on a multiple of ``gcd(T, page_size)``
               (the engine's windows start on page boundaries)

    Both forms move ``rows * T * kv_heads * 2*head_dim`` values whatever
    the pool's size, and both keep XLA on the pool's row-major layout
    (the Pallas kernel's operand layout).  The obvious
    ``pool.at[layer, pages, :, offs].set(kv)`` does not: its scatter
    prefers kv_heads minor to page_size and XLA relays the WHOLE pool
    out to that layout and back, a step.  Times on a v5e, 32 layers of
    SmolLM2-360M's pool (PERF.md, PR 25):

      - T == 1 (decode): ONE row scatter on the pool viewed as
        ``[layers*pages*kv_heads*page_size, 2*head_dim]`` (a bitcast):
        0.46 ms a step for 33 rows, against 3.2 ms for a
        dynamic_update_slice a row.
      - T > 1 (prefill): a loop of one dynamic_update_slice per chunk of
        ``gcd(T, page_size)`` positions, ``[kv_heads, chunk,
        2*head_dim]`` each: 8.1 ms for 4 x 2048 tokens, against 95 ms
        for the row scatter.
    """
    _, n_pages, kvh, ps, d = pool.shape
    t = positions.shape[1]
    kv = kv.astype(pool.dtype)
    if t == 1:
        assert pool.size // d < 2 ** 31, "flat row index overflows int32"
        pages = jnp.take_along_axis(block_tables, positions // ps, axis=1)
        at = ((((layer * n_pages + pages) * kvh + jnp.arange(kvh)) * ps)
              + positions % ps)                                # [rows, kvh]
        pool = pool.reshape(-1, d).at[at.reshape(-1)].set(
            kv.reshape(-1, d)).reshape(pool.shape)
    else:
        c = math.gcd(t, ps)
        starts = positions[:, ::c]                             # [rows, T/c]
        pages = jnp.take_along_axis(block_tables, starts // ps,
                                    axis=1).reshape(-1)
        offs = (starts % ps).reshape(-1)
        chunks = jnp.moveaxis(kv.reshape(-1, c, kvh, d), 1, 2)

        def write_chunk(i, pool):
            chunk = jax.lax.dynamic_index_in_dim(chunks, i, keepdims=False)
            return jax.lax.dynamic_update_slice(
                pool, chunk[None, None], (layer, pages[i], 0, offs[i], 0))

        pool = jax.lax.fori_loop(0, chunks.shape[0], write_chunk, pool)
    return pool


def gather_kv_pages(kv_pages: jax.Array, block_tables: jax.Array, *,
                    layer=0) -> jax.Array:
    """Each row's whole table span of layer ``layer``, position-major:
    ``[rows, max_pages*page_size, kv_heads, 2*head_dim]``.  One gather
    indexed (layer, page) on the stacked pool."""
    kvh, d = kv_pages.shape[2], kv_pages.shape[4]
    # [rows, mp, kvh, ps, 2hd] -> [rows, mp*ps, kvh, 2hd]
    return jnp.moveaxis(kv_pages[layer, block_tables], 2, 3).reshape(
        block_tables.shape[0], -1, kvh, d)


def paged_attention_xla(q: jax.Array, kv_pages: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array, *,
                        layer=0, window=None,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """Gather-based paged decode attention (one query token per row).

    q:            [rows, heads, head_dim]
    kv_pages:     [layers, num_pages, kv_heads, page_size, 2*head_dim]
    block_tables: [rows, max_pages] physical page ids, position-ordered
    lengths:      [rows] number of valid positions (current pos + 1)
    layer:        which layer's pages to read (int or traced scalar)
    window:       None, or a scalar (int or traced): only the last
                  ``window`` of the ``lengths`` positions are visible
    returns       [rows, heads, head_dim]
    """
    hd = q.shape[-1]
    kv = gather_kv_pages(kv_pages, block_tables, layer=layer)
    pos = jnp.arange(kv.shape[1])[None, :]
    mask = pos < lengths[:, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None] - window)
    out = xla_attention(q[:, None], kv[..., :hd], kv[..., hd:],
                        causal=False, mask=mask, sm_scale=sm_scale)
    return out[:, 0]


def _tpu_kernel(q2: jax.Array, kv_pages: jax.Array,
                block_tables: jax.Array, lengths: jax.Array,
                layer: jax.Array, sm_scale: float,
                window: Optional[jax.Array] = None) -> jax.Array:
    """Pallas TPU decode kernel: per-row loop over occupied pages only.

    ``kv_pages`` is the whole stacked pool, left in HBM; ``layer`` [1]
    rides as a scalar-prefetch operand beside the tables, and a page's
    DMA source is ``kv_pages[layer, page]``.

    ``q2`` is the query padded to [rows, heads, 2*head_dim] (zeros in
    the V half) so every buffer's minor dim is lane-aligned; the zero
    half makes q2 . kv_page contract to K-only scores, and p . kv_page
    leaves the real output in the V half of the accumulator — no
    sub-tile slicing anywhere in the kernel.  The row's page count
    (ceil(length/page_size)) is a traced ``fori_loop`` bound, so pages
    past the row's context are never DMA'd.  In-kernel math stays 2-D
    per kv head (Mosaic rejects batched dot_generals).

    ``window`` [1] (optional) is one more scalar-prefetch operand: the
    page loop then starts at ``max(0, length - window) // page_size``
    and positions before ``length - window`` are masked, so a window
    layer at 6000 tokens reads 65 pages and not 94.  Without it the
    kernel is built as it was (no operand, no extra mask).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, hd2 = q2.shape
    _, _, kvh, ps, _ = kv_pages.shape
    g = heads // kvh

    windowed = window is not None

    def kernel(*refs):
        tables_ref, len_ref, layer_ref = refs[:3]
        (q_ref, kv_ref, out_ref, kvbuf, acc_ref, m_ref, l_ref,
         sems) = refs[3 + windowed:]
        r = pl.program_id(0)
        length = len_ref[r]
        n_pg = pl.cdiv(length, ps)
        if windowed:
            first_pos = jnp.maximum(length - refs[3][0], 0)
            pg0 = first_pos // ps
        else:
            first_pos, pg0 = None, 0

        def get_dma(slot, i):
            return pltpu.make_async_copy(
                kv_ref.at[layer_ref[0], tables_ref[r, i]], kvbuf.at[slot],
                sems.at[slot])

        @pl.when(n_pg > pg0)
        def _():
            get_dma(pg0 % 2 if windowed else 0, pg0).start()

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
            if windowed:
                valid = valid & (pos >= first_pos)
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

        jax.lax.fori_loop(pg0, n_pg, body, 0)
        norm = jnp.maximum(l_ref[:], 1e-30)               # [heads, 1]
        out_ref[0] = (acc_ref[:] / norm).astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, lengths, layer (, window)
        num_scalar_prefetch=3 + windowed,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, heads, hd2), lambda r, *_: (r, 0, 0),
                         memory_space=pltpu.VMEM),         # q2
            pl.BlockSpec(memory_space=pl.ANY),   # stacked kv_pages (HBM)
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
    )(block_tables, lengths, layer, *([window] if windowed else []),
      q2, kv_pages)


def paged_attention_tpu(q, kv_pages, block_tables, lengths, *, layer=0,
                        window=None,
                        sm_scale: Optional[float] = None) -> jax.Array:
    hd = q.shape[-1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    q2 = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    if window is not None:
        window = jnp.asarray(window, jnp.int32).reshape(1)
    out2 = _tpu_kernel(q2, kv_pages, block_tables,
                       lengths.astype(jnp.int32),
                       jnp.asarray(layer, jnp.int32).reshape(1), scale,
                       window)
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


def paged_attention(q, kv_pages, block_tables, lengths, *, layer=0,
                    window=None, sm_scale: Optional[float] = None,
                    impl: str = "auto") -> jax.Array:
    """Backend-dispatched paged decode attention over layer ``layer`` of
    the stacked pool (see module docstring and
    :func:`resolve_paged_impl`); under a ``window`` (scalar, traced or
    not) only the last ``window`` positions of each row."""
    impl = resolve_paged_impl(kv_pages.shape[-1], impl)
    fn = paged_attention_tpu if impl == "tpu" else paged_attention_xla
    return fn(q, kv_pages, block_tables, lengths, layer=layer,
              window=window, sm_scale=sm_scale)
