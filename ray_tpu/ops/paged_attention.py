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

  kv_pages:     [layers, num_pages, kv_heads, page_size, row]
                (ONE stacked pool for the whole model; ``row`` is what
                the model's attention caches of a token in a KV head:
                ``2*head_dim``, K in [..., :head_dim], V in [...,
                head_dim:]; or a LATENT row, below)
  block_tables: [rows, max_pages_per_seq] int32  (logical -> physical)

A sequence at position ``p`` occupies ``ceil((p+1)/page_size)`` pages,
the same page ids in every layer.  The layout is dictated by TPU
tiling: Mosaic DMAs slice memrefs in (8, 128) tiles, so the page's
minor dim must be a multiple of 128 — ``2*head_dim`` is exactly that
for the common head_dims (64, 128, 256), and fusing K and V makes a
page one DMA instead of two.  kv_heads sits outside (page_size, row) so
per-head views are tile-aligned.

LATENT ROWS (models/gpt.py LatentAttention).  A latent-attention model
caches ONE vector a token for all heads, ``[c | k_rope]`` (512 + 64 at
the DeepSeek-V3 sizes): ``kv_heads`` is 1, a key is the whole row and
the value its first ``v_width`` (``c``): the same bytes.  576 is 4.5
lane tiles, so the row is PADDED with zeros to 640: one leaf, one DMA a
page, every function here unchanged but for which columns the two
products read (``v_width``); the zero columns add nothing to a score.
The price is 11% more bytes a page than the model's 1,152 B a token (a
share of the HBM roofline of 0.9 at best).  The other layout, two
leaves ``[.., page, 512]`` and the 64 rotated dims packed two tokens a
lane tile, reads no padding and pays a second DMA a page, a second
pool leaf through every engine program and a second product a chunk;
it was not built (PERF.md section 6, PR 37, has the measurement this
one was kept on).

ADDRESSING.  The pool is addressed, never sliced: a reader names
``[layer, page]`` (the kernel's DMA source, the oracle's gather index),
a writer ``[layer, page, :, offset]``, on the whole stacked array.
``pool[layer]`` as a value is a copy of one layer's pool (108 MB at
SmolLM2-360M's default pool) — under the layer scan one such copy, its
relayout and its write-back a layer, which was 85% of the serving cell's
device time (PERF.md, PR 25).  So the pool rides the model's layer scan
and the engine's step scan as loop-carried, donated state, and
everything here takes it whole plus a ``layer`` index (an int, or a
traced scalar under the layer scan).

WHO WRITES (models/gpt.py ``_decode_attend_paged`` is the call site of
both).  A prompt's rows (``T > 1``): ``write_kv_pages`` below, a loop of
``dynamic_update_slice``.  A decode step's one row (``T == 1``), or the
two rows of a step that verifies a draft (``q`` ``[rows, T, heads,
head_dim]``: ``T`` query positions a row, causal between them, each
counting its window from its own position; serve/llm_engine.py
drafting): ``paged_attention`` itself, handed ``new_rows``.  On the chip
the Pallas kernel puts each row it keeps into the row's own tail page, which it has
in VMEM with the row's last chunk anyway, and sends the tile-aligned
group that holds it back to the pool, the pool aliased through the call;
a row that holds no request writes NOTHING.  Off the chip ``new_rows``
go through ``write_kv_pages``' ``T == 1`` form (one XLA row scatter,
every row of the batch, a dead row into the scratch page its table
names, which nothing reads) in front of the gather; that pair is also
the kernel's oracle.  The scatter cost 13-14 us a layer at 33 rows x 4-5
KV heads and 158 us at 65 x 30, the kernel's write 0.03-0.35 us a live
row (PERF.md, PR 45).

Two implementations:

  - ``paged_attention_xla`` — gather the table span, mask by length,
    dense attention.  Runs on every backend (the CPU test oracle and
    fallback).  It reads the whole (static) table span, so its HBM win
    comes from sizing ``max_pages_per_seq`` to the workload.
  - ``paged_attention_tpu`` — Pallas kernel: one invocation walks the
    flat list of (live row, occupied page) work, DMAing ONLY those
    pages HBM->VMEM through a pipeline that stays full across rows,
    bf16 pages straight to the MXU, flash-style online softmax in
    float32.  A row that holds no request issues nothing.  HBM traffic
    and time per decode step scale with the context the live rows
    actually hold — the property the dense row layout can't have.  With
    ``new_rows`` the same kernel also writes them (above); without, the
    pool is only read.

``paged_attention`` dispatches by backend.  Both take ``live`` [rows]
(optional): rows it leaves out are not read and return zeros.
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

    pool:      [layers, num_pages, kv_heads, page_size, row]
    kv:        [rows, T, kv_heads, row]  (``[k | v]``, or a latent row)
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

      - T == 1 (a decode step where the kernel does not run; on the
        chip the kernel writes: module docstring): ONE row scatter on
        the pool viewed as ``[layers*pages*kv_heads*page_size,
        2*head_dim]`` (a bitcast): 0.46 ms a step for 33 rows, against
        3.2 ms for a dynamic_update_slice a row.
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
                        layer=0, window=None, live=None,
                        sm_scale: Optional[float] = None,
                        v_width: Optional[int] = None,
                        new_rows: Optional[jax.Array] = None):
    """Gather-based paged decode attention (one query token per row).

    q:            [rows, heads, head_dim]  (latent: [rows, heads, row])
    kv_pages:     [layers, num_pages, kv_heads, page_size, row]
    block_tables: [rows, max_pages] physical page ids, position-ordered
    lengths:      [rows] number of valid positions (current pos + 1)
    layer:        which layer's pages to read (int or traced scalar)
    window:       None, or a scalar (int or traced): only the last
                  ``window`` of the ``lengths`` positions are visible
    live:         None (every row is read), or [rows] bool: a row it
                  leaves out comes back as zeros
    v_width:      None: the row is ``[k | v]`` halves.  An int: a latent
                  row (module docstring): keys the whole row, values its
                  first ``v_width`` columns
    new_rows:     None, or [rows, kv_heads, row]: the token ``lengths``
                  counts last, scattered into the pool first
                  (``write_kv_pages``, every row); the result is then
                  ``(out, pool)``
    returns       [rows, heads, head_dim]  (latent: [rows, heads, v_width])
    """
    if q.ndim == 4:
        return _multi_xla(q, kv_pages, block_tables, lengths, layer, window,
                          live, sm_scale, new_rows)
    if new_rows is not None:
        kv_pages = write_kv_pages(kv_pages, new_rows[:, None], block_tables,
                                  lengths[:, None] - 1, layer=layer)
        return paged_attention_xla(
            q, kv_pages, block_tables, lengths, layer=layer, window=window,
            live=live, sm_scale=sm_scale, v_width=v_width), kv_pages
    hd = q.shape[-1]
    kv = gather_kv_pages(kv_pages, block_tables, layer=layer)
    pos = jnp.arange(kv.shape[1])[None, :]
    mask = pos < lengths[:, None]
    if window is not None:
        mask = mask & (pos >= lengths[:, None] - window)
    k, v = ((kv[..., :hd], kv[..., hd:]) if v_width is None
            else (kv, kv[..., :v_width]))
    out = xla_attention(q[:, None], k, v,
                        causal=False, mask=mask, sm_scale=sm_scale)[:, 0]
    if live is not None:
        out = jnp.where(live[:, None, None], out, jnp.zeros_like(out))
    return out


def _multi_xla(q, kv_pages, block_tables, lengths, layer, window, live,
               sm_scale, new_rows):
    """``paged_attention_xla`` at ``T`` query positions a row (``q``
    [rows, T, heads, head_dim], ``new_rows`` [rows, T, kv_heads, row]):
    the T rows scattered one position after the other (a row's T
    positions may straddle a page), then each query over the positions
    up to its own, ``lengths - T + t + 1`` of them.  ``(out, pool)``."""
    t, hd = q.shape[1], q.shape[-1]
    ends = lengths[:, None] - (t - 1) + jnp.arange(t)      # [rows, T]
    for i in range(t):
        kv_pages = write_kv_pages(kv_pages, new_rows[:, i:i + 1],
                                  block_tables, ends[:, i:i + 1] - 1,
                                  layer=layer)
    kv = gather_kv_pages(kv_pages, block_tables, layer=layer)
    pos = jnp.arange(kv.shape[1])[None, None, :]
    mask = pos < ends[:, :, None]
    if window is not None:
        mask = mask & (pos >= ends[:, :, None] - window)
    out = xla_attention(q, kv[..., :hd], kv[..., hd:], causal=False,
                        mask=mask[:, None], sm_scale=sm_scale)
    if live is not None:
        out = jnp.where(live[:, None, None, None], out, jnp.zeros_like(out))
    return out, kv_pages


# a work item of the kernel is a chunk of one row's pages: this many
# positions (on a v5e 64 a chunk ran at 250 GB/s, 256 at 500, 512 at
# 720, 1024 at 740: PERF.md, PR 32) unless that is more than
# _CHUNK_BYTES of pages (gptj-6b's page is 1 MiB: 8 of them twice over
# are all of VMEM); and the buffers of that size, the one computed on
# and the ones in flight (a third buffer bought 4% at most)
_CHUNK_TOKENS = 512
# a latent page's chunk (one KV head, 32 query heads a product): on a
# v5e 24 rows of 5,000 positions ran in 267 us at 512 a chunk and 246 at
# 1024, of 9,000 in 459 and 404 (PERF.md, PR 37)
_LATENT_CHUNK_TOKENS = 1024
_CHUNK_BYTES = 2 << 20
_PIPELINE_DEPTH = 2


def _tpu_kernel(q: jax.Array, kv_pages: jax.Array,
                block_tables: jax.Array, lengths: jax.Array,
                layer: jax.Array, sm_scale: float,
                window: Optional[jax.Array] = None,
                live: Optional[jax.Array] = None,
                v_width: Optional[int] = None,
                new_rows: Optional[jax.Array] = None, tq: int = 1):
    """Pallas TPU decode kernel: ONE invocation walks the flat list of
    (live row, chunk of occupied pages) work with a page pipeline that
    never drains between rows.

    ``kv_pages`` is the whole stacked pool, left in HBM; a page's DMA
    source is ``kv_pages[layer, page]``.  ``q`` [rows, heads, qw] and
    the output [rows, heads, qw] sit whole in VMEM (33 x 28 x 128 bf16
    is 236 KB).  Tables, lengths, ``layer`` [1] and, where given,
    ``window`` [1] and ``live`` [rows] ride as scalar-prefetch operands.

    The kernel first compacts, in SMEM, the rows it has to read: those
    ``live`` names (all of them without the operand) whose page range
    ``max(0, length - window) // page_size .. ceil(length / page_size)``
    is not empty.  Every other row issues no DMA and no vector work;
    its output is zeros.  A row's range is cut into chunks of ``cp``
    pages (``_CHUNK_TOKENS`` positions; one online-softmax step each:
    a step's latency is a chain of two MXU round trips and two lane
    reductions whatever it covers, so a page a step is bound by that
    chain, not by bytes).  A fetch cursor runs ``_PIPELINE_DEPTH - 1``
    chunks ahead of the compute cursor over the flat list, so while a
    row's last chunk is in the MXU the next row's first pages are
    already in flight.  Pages past a row's context and pages wholly
    behind the window are never named; a chunk's buffer past the row's
    last page keeps what an earlier chunk left there (zeros at first),
    under the position mask.

    A page goes to the MXU in the pool's dtype (float32 accumulation);
    the scale, the softmax statistics and the accumulator are float32,
    and ``p`` is cast to the pool's dtype for ``p @ v`` — what
    ``ops/attention.py xla_attention`` computes.  In-kernel math stays
    2-D per kv head (Mosaic rejects batched dot_generals); a head's
    statistics are loop-carried values, not scratch.

    ``qw`` decides how a page is split.  ``qw == head_dim`` (head_dim a
    multiple of 128: the halves are whole lane tiles): scores contract
    against the page's K half, ``p`` against its V half, as views.
    ``qw == 2*head_dim`` (the caller zero-padded the query): scores
    contract against the whole page (the zero half makes them K-only),
    ``p @ page`` leaves the output in the V half, and the caller slices
    it out — no sub-tile slicing in the kernel.  ``v_width`` (a latent
    row, ``qw`` the row's width): scores against the whole page, ``p``
    against its first ``v_width`` columns (whole lane tiles), the output
    ``[rows, heads, v_width]``: ``kvh`` is 1 and all ``heads`` queries
    of a row ride one product.

    ``new_rows`` [rows, kv_heads, row] (the pool's dtype, whole in
    VMEM): the kernel also WRITES the step's token, and returns ``(out,
    pool)``, the pool aliased to its second output.  ``lengths`` counts
    the new token, so it belongs at offset ``(length - 1) % page_size``
    of the row's last page, which arrives in ``kvbuf`` with the row's
    last chunk.  When that chunk has landed the row is put into the
    buffered page (a select over the tile-aligned group of ``grp``
    positions that holds the offset: no single-row store), the group
    starts back to ``pool[layer, page]``, and the chunk's two products
    run on the patched buffer while it flies: the new token's score and
    value come out of them like every other position's, the row exactly
    as the pool's dtype holds it.  The write-back is waited for when
    the row's products are done, before the next fetch into its slot.  A
    row's tail page is its own (the engine lends whole read-only pages
    only), so no other row reads or writes it inside a call; a row the
    kernel does not keep writes nothing.

    ``tq`` > 1 (a step that verifies a draft): ``tq`` query positions a
    row, the last at ``length - 1``.  ``q`` and the output are then
    ``[rows, kv_heads * tq * g, qw]``, a KV head's ``tq * g`` queries
    side by side, position-major (``paged_attention_tpu`` lays them out
    so), and ride one product a KV head: ``tq * g`` rows of the MXU tile
    where one position fills ``g``.  The query of position ``length - tq
    + i`` sees the keys up to its own, so the positions of a step are
    causal among themselves, and under a window those after ``its own
    position - window``; the row's page range starts where its FIRST
    query's does.  ``new_rows`` is ``[rows, tq * kv_heads, row]``,
    position-major, and every one is written: each into the buffered page
    that holds its position, in the chunk that page arrives with (an
    earlier position may lie a page, and so a chunk, ahead of the last),
    the group of each going back once (one group holds both: one
    write-back), an earlier chunk's waited for before that chunk ends.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, heads, qw = q.shape           # heads: tq positions' worth
    _, _, kvh, ps, hd2 = kv_pages.shape
    g = heads // kvh                    # a KV head's queries, tq * its group
    depth = _PIPELINE_DEPTH
    page_bytes = kvh * ps * hd2 * kv_pages.dtype.itemsize
    chunk_tokens = _CHUNK_TOKENS if v_width is None else _LATENT_CHUNK_TOKENS
    cp = max(1, min(chunk_tokens // ps, _CHUNK_BYTES // page_bytes))
    windowed, masked = window is not None, live is not None
    writes = new_rows is not None
    n_prefetch = 3 + windowed + masked
    # positions of a page that one write-back moves: the dtype's sublane
    # tile (bf16 packs 16 positions a tile), or the whole page
    tile = 32 // kv_pages.dtype.itemsize
    grp = tile if ps % tile == 0 else ps
    # the page's K and V parts as the two products see them
    if v_width is not None:
        k_cols, v_cols = slice(None), slice(0, v_width)
    elif qw != hd2:
        k_cols, v_cols = slice(0, qw), slice(qw, hd2)
    else:
        k_cols, v_cols = slice(None), slice(None)
    vw = v_width or qw                  # the output's width

    def kernel(*refs):
        tables_ref, len_ref, layer_ref = refs[:3]
        window_ref = refs[3] if windowed else None
        live_ref = refs[3 + windowed] if masked else None
        rest = list(refs[n_prefetch:])
        # with new rows: their operand, the pool as an output and the
        # write-back's semaphore besides
        new_ref, pool_ref, wsem = ((rest.pop(2), rest.pop(3), rest.pop())
                                   if writes else (None, None, None))
        (q_ref, kv_ref, out_ref, kvbuf, row_out, ids, firsts, ends,
         sems) = rest

        # branch-free and unrolled (0.3-0.7 us a call less than a loop
        # of conditional stores): every row is written at the running
        # count, and only a kept row moves the count past its entry
        n = jnp.int32(0)
        for r in range(rows):
            length = len_ref[r]
            end = pl.cdiv(length, ps)
            first = 0
            if windowed:        # of the row's FIRST query
                reach = window_ref[0] + (tq - 1) if tq > 1 else window_ref[0]
                first = jnp.maximum(length - reach, 0) // ps
            keep = end > first
            if masked:
                keep = keep & (live_ref[r] != 0)
            ids[n], firsts[n], ends[n] = r, first, end
            n = n + keep.astype(jnp.int32)
        out_ref[...] = jnp.zeros_like(out_ref)
        kvbuf[...] = jnp.zeros_like(kvbuf)

        def chunk_dmas(slot, at, end, row=None):
            """Start the DMA of every page of the chunk that begins at
            page ``at`` of table row ``row`` into ``slot``; without
            ``row``, wait for them (a wait names no source)."""
            for k in range(cp):
                @pl.when(at + k < end)
                def _():
                    dma = pltpu.make_async_copy(
                        kv_ref.at[layer_ref[0], 0 if row is None
                                  else tables_ref[row, at + k]],
                        kvbuf.at[slot, k], sems.at[slot])
                    dma.wait() if row is None else dma.start()

        def fetch(cursor, slot):
            """Start the DMAs of the chunk at ``cursor`` = (index into
            the compacted rows, first page of the chunk) into ``slot``,
            if there is one; returns the next chunk's cursor."""
            j, at = cursor

            @pl.when(j < n)
            def _():
                chunk_dmas(slot, at, ends[j], ids[j])

            more = at + cp < ends[jnp.minimum(j, rows - 1)]
            return (jnp.where(more, j, j + 1),
                    jnp.where(more, at + cp,
                              firsts[jnp.minimum(j + 1, rows - 1)]))

        cursor = (jnp.int32(0), firsts[0])
        for slot in range(depth - 1):
            cursor = fetch(cursor, slot)

        def tail_dma(slot, k, at, page, sem=0):
            """The write-back of the group at ``at`` of page ``k`` of
            ``slot`` to ``pool[layer, page]``."""
            return pltpu.make_async_copy(
                kvbuf.at[slot, k, :, pl.ds(at, grp)],
                pool_ref.at[layer_ref[0], page, :, pl.ds(at, grp)],
                wsem.at[sem])

        def patch(slot, k, at, off, r, i):
            """New row ``i`` of row ``r`` into position ``at + off`` of
            page ``k`` of ``slot``: a select over the group at ``at``
            (float32 and back is exact, and slices a packed dtype
            nowhere but on whole tiles)."""
            rows_new = new_ref[r].astype(jnp.float32)
            hit = jax.lax.broadcasted_iota(jnp.int32, (grp, hd2), 0) == off
            for h in range(kvh):
                at_h = (slot, k, h, pl.ds(at, grp))
                new = rows_new[i * kvh + h:i * kvh + h + 1]
                kvbuf[at_h] = jnp.where(
                    hit, new, kvbuf[at_h].astype(jnp.float32)
                ).astype(kvbuf.dtype)

        def row(j, carry):
            step, cursor = carry
            r, first, end = ids[j], firsts[j], ends[j]
            length = len_ref[r]
            first_pos = (jnp.maximum(length - window_ref[0], 0)
                         if windowed else None)
            qf = q_ref[r].astype(jnp.float32)              # [heads, qw]
            qs = [qf[h * g:(h + 1) * g].astype(kvbuf.dtype)
                  for h in range(kvh)]
            stats = tuple((jnp.full((g, 1), -1e30, jnp.float32),
                           jnp.zeros((g, 1), jnp.float32),
                           jnp.zeros((g, vw), jnp.float32))
                          for _ in range(kvh))
            n_chunks = pl.cdiv(end - first, cp)
            if tq > 1:
                # query ``i`` of the product's rows (position-major in a
                # KV head) stands ``back`` positions before the last
                back = (tq - 1) - jax.lax.broadcasted_iota(
                    jnp.int32, (g, cp * ps), 0) // (g // tq)
            if writes and tq > 1:
                # new row i: its chunk, its page there, its group in the
                # page, its place in the group, its physical page; and
                # whether its group goes back on its own (the last one's
                # always; an earlier one's where the next lies elsewhere)
                w_pos = [length - tq + i for i in range(tq)]
                w_page = [p // ps - first for p in w_pos]
                w_chunk = [pg // cp for pg in w_page]
                w_k = [pg - c * cp for pg, c in zip(w_page, w_chunk)]
                w_at = [0 if grp == ps else pl.multiple_of(
                    p % ps // grp * grp, grp) for p in w_pos]
                w_off = [p % ps - at for p, at in zip(w_pos, w_at)]
                w_phys = [tables_ref[r, p // ps] for p in w_pos]
                w_own = [w_pos[i] // grp != w_pos[i + 1] // grp
                         for i in range(tq - 1)] + [True]
            elif writes:
                # where the new token goes: page ``tail_k`` of the last
                # chunk, the group at ``tail_at``, position ``off`` of it
                tail_k = end - 1 - (first + (n_chunks - 1) * cp)
                off = (length - 1) % ps
                tail_at = (0 if grp == ps
                           else pl.multiple_of(off // grp * grp, grp))
                off = off - tail_at
                tail_page = tables_ref[r, end - 1]

            def chunk(t, carry):
                step, cursor, stats = carry
                cursor = fetch(cursor, (step + depth - 1) % depth)
                slot, at = step % depth, first + t * cp
                chunk_dmas(slot, at, end)
                if writes and tq > 1:
                    for i in range(tq):
                        @pl.when(t == w_chunk[i])
                        def _():
                            patch(slot, w_k[i], w_at[i], w_off[i], r, i)
                    for i in range(tq):
                        @pl.when((t == w_chunk[i]) & w_own[i])
                        def _():
                            tail_dma(slot, w_k[i], w_at[i], w_phys[i],
                                     i).start()
                elif writes:
                    @pl.when(t == n_chunks - 1)
                    def _():
                        patch(slot, tail_k, tail_at, off, r, 0)
                        tail_dma(slot, tail_k, tail_at, tail_page).start()
                pos = at * ps + jax.lax.broadcasted_iota(
                    jnp.int32, (g, cp * ps), 1)
                if tq > 1:
                    valid = pos < length - back
                    if windowed:
                        valid = valid & (pos >= length - back
                                         - window_ref[0])
                else:
                    valid = pos < length
                    if windowed:
                        valid = valid & (pos >= first_pos)
                new = []
                for h in range(kvh):             # static per-head 2-D ops
                    m_prev, l_prev, acc = stats[h]
                    kv = kvbuf[slot, :, h].reshape(cp * ps, hd2)
                    s = jax.lax.dot_general(
                        qs[h], kv[:, k_cols], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    s = jnp.where(valid, s, -1e30)        # [g, cp*ps]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(s, axis=1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    pv = jax.lax.dot_general(
                        p.astype(kvbuf.dtype), kv[:, v_cols],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [g, vw]
                    new.append((m_new,
                                l_prev * alpha
                                + jnp.sum(p, axis=1, keepdims=True),
                                acc * alpha + pv))
                if writes and tq > 1:
                    # an earlier row's write-back leaves this slot before
                    # a later chunk's fetch lands in it
                    for i in range(tq - 1):
                        @pl.when((t == w_chunk[i]) & w_own[i])
                        def _():
                            tail_dma(slot, w_k[i], w_at[i], w_phys[i],
                                     i).wait()
                return step + 1, cursor, tuple(new)

            step, cursor, stats = jax.lax.fori_loop(
                0, n_chunks, chunk, (step, cursor, stats))
            if writes and tq > 1:
                tail_dma((step - 1) % depth, w_k[-1], w_at[-1], w_phys[-1],
                         tq - 1).wait()
            elif writes:
                tail_dma((step - 1) % depth, tail_k, tail_at,
                         tail_page).wait()
            for h, (_, l, acc) in enumerate(stats):
                row_out[h * g:(h + 1) * g] = acc / jnp.maximum(l, 1e-30)
            out_ref[r] = row_out[...].astype(out_ref.dtype)
            return step, cursor

        jax.lax.fori_loop(0, n, row, (jnp.int32(0), cursor))

    out_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((rows, heads, vw), q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, lengths, layer (, window) (, live)
        num_scalar_prefetch=n_prefetch,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),        # q, whole
            pl.BlockSpec(memory_space=pl.ANY),   # stacked kv_pages (HBM)
        ] + [pl.BlockSpec(memory_space=pltpu.VMEM)] * writes,  # new rows
        out_specs=([out_spec, pl.BlockSpec(memory_space=pl.ANY)] if writes
                   else out_spec),
        scratch_shapes=[
            pltpu.VMEM((depth, cp, kvh, ps, hd2), kv_pages.dtype),
            pltpu.VMEM((heads, vw), jnp.float32),   # one row's output
            pltpu.SMEM((rows,), jnp.int32),         # rows to read
            pltpu.SMEM((rows,), jnp.int32),         # their first page
            pltpu.SMEM((rows,), jnp.int32),         # the page they end at
            pltpu.SemaphoreType.DMA((depth,)),
        ] + [pltpu.SemaphoreType.DMA((tq,))] * writes,  # the write-backs
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=([out_shape, jax.ShapeDtypeStruct(
            kv_pages.shape, kv_pages.dtype)] if writes else out_shape),
        # the pool (the operand after the scalars and q) IS the second
        # output: in place when it is loop-carried and donated
        input_output_aliases={n_prefetch + 1: 1} if writes else {},
        name="paged_attention_decode",
    )(block_tables, lengths, layer, *([window] if windowed else []),
      *([live] if masked else []), q, kv_pages,
      *([new_rows] if writes else []))


def paged_attention_tpu(q, kv_pages, block_tables, lengths, *, layer=0,
                        window=None, live=None,
                        sm_scale: Optional[float] = None,
                        v_width: Optional[int] = None,
                        new_rows: Optional[jax.Array] = None):
    hd = q.shape[-1]
    scale = sm_scale if sm_scale is not None else hd ** -0.5
    tq = 1
    if q.ndim == 4:
        # T query positions a row: a KV head's queries side by side,
        # position-major, and the new rows position-major (_tpu_kernel);
        # the output comes back in the same order
        rows, tq, heads, _ = q.shape
        kvh = kv_pages.shape[2]
        by_kv = lambda a, *axes: a.reshape(                 # noqa: E731
            rows, *axes, a.shape[-1]).swapaxes(1, 2).reshape(
                rows, tq * heads, a.shape[-1])
        q = by_kv(q, tq, kvh, heads // kvh)
        new_rows = new_rows.reshape(rows, tq * kvh, -1)
    # a half of the page is whole lane tiles, or the query is padded
    # with zeros to the page's width (see _tpu_kernel); a latent row's
    # query comes at the page's width
    split = hd % 128 == 0 or v_width is not None
    if not split:
        q = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    if window is not None:
        window = jnp.asarray(window, jnp.int32).reshape(1)
    if live is not None:
        live = live.astype(jnp.int32)
    if new_rows is not None:
        new_rows = new_rows.astype(kv_pages.dtype)
    got = _tpu_kernel(q, kv_pages, block_tables, lengths.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32).reshape(1), scale,
                      window, live, v_width, new_rows, tq)
    if new_rows is None:
        return got if split else got[..., hd:]
    out, pool = got
    out = out if split else out[..., hd:]
    if tq > 1:
        out = by_kv(out, kvh, tq, heads // kvh).reshape(rows, tq, heads, -1)
    return out, pool


def resolve_paged_impl(kv_minor: int, impl: str = "auto",
                       v_width: Optional[int] = None) -> str:
    """Which implementation ``paged_attention`` runs for a pool whose
    minor dim (a token's row: ``2*head_dim``, or a latent row padded to
    whole lane tiles, of which the values are the first ``v_width``) is
    ``kv_minor``: ``"tpu"`` or ``"xla"``.

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
    aligned = kv_minor % 128 == 0 and (v_width or 0) % 128 == 0
    if impl == "auto":
        return "tpu" if aligned and backend_platform() == "tpu" else "xla"
    if impl == "tpu" and not aligned:
        raise ValueError(
            f"paged_attention impl='tpu' needs a pool row of whole lane "
            f"tiles, 2*head_dim % 128 == 0 (got {kv_minor}, values "
            f"{v_width}); use impl='auto' or 'xla' for this shape")
    if impl not in ("tpu", "xla"):
        raise ValueError(f"unknown paged attention impl: {impl!r}")
    return impl


def paged_attention(q, kv_pages, block_tables, lengths, *, layer=0,
                    window=None, live=None,
                    sm_scale: Optional[float] = None,
                    v_width: Optional[int] = None,
                    new_rows: Optional[jax.Array] = None,
                    impl: str = "auto"):
    """Backend-dispatched paged decode attention over layer ``layer`` of
    the stacked pool (see module docstring and
    :func:`resolve_paged_impl`); under a ``window`` (scalar, traced or
    not) only the last ``window`` positions of each row.

    ``live`` [rows] bool says which rows hold a request: the others are
    not read and come back as zeros, from either implementation.  The
    serving engine's rows are live where ``block_tables[:, 0] != 0``
    (page 0 is scratch; models/gpt.py ``Block`` derives the mask once
    for this kernel and the expert kernel).  ``None``: every row is
    read.  ``v_width``: the pool holds latent rows (module docstring);
    the kernel then wants ``v_width`` in whole lane tiles too.

    ``q`` may be ``[rows, T, heads, head_dim]``, ``T`` query positions a
    row of which the LAST is at ``lengths - 1`` (a step that verifies a
    draft), with ``new_rows`` ``[rows, T, kv_heads, row]`` (required
    then): the query of position ``lengths - T + t`` sees the positions
    up to its own, under a window the last ``window`` of them, and all
    ``T`` rows are written first; the output is ``[rows, T, heads,
    head_dim]``.

    ``new_rows`` [rows, kv_heads, row] (None: the pool is only read, and
    the result is the output alone): the step's token of every row,
    which ``lengths`` already counts.  It is written to layer ``layer``
    at position ``lengths - 1`` before anything is read, and the result
    is ``(out, pool)``: by the kernel itself, for the rows it keeps, or
    by ``write_kv_pages`` in front of the gather (module docstring, WHO
    WRITES)."""
    impl = resolve_paged_impl(kv_pages.shape[-1], impl, v_width)
    fn = paged_attention_tpu if impl == "tpu" else paged_attention_xla
    return fn(q, kv_pages, block_tables, lengths, layer=layer,
              window=window, live=live, sm_scale=sm_scale, v_width=v_width,
              new_rows=new_rows)
