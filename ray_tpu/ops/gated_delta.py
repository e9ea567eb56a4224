"""The gated delta rule (Gated DeltaNet): a linear-attention layer whose
memory is one matrix a head, not a cache that grows with the context.

Per head, with ``q, k`` in R^dk (``k`` of unit length), ``v`` in R^dv, a
decay ``alpha = exp(g)`` in (0, 1] and a write strength ``beta``::

    S' = alpha_t S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          S in R^(dk x dv)
    o_t = S_t^T q_t

The decay is a scalar a head and step (Gated DeltaNet: ``g [.., H]``)
or a vector over the head's ``dk`` key channels (Kimi Delta Attention:
``g [.., H, dk]``, ``S' = Diag(alpha_t) S_{t-1}``): every form takes
either, told apart by ``g``'s rank, and a vector decay equal in every
channel gives what the scalar gives (tests/test_kimi_linear.py).

Three forms that must agree (tests/test_olmo_hybrid.py):

  - ``gated_delta_recurrent``: the equations token by token
    (``lax.scan``).  The oracle; never on the served path.
  - ``gated_delta_chunked``: a prompt in chunks of ``CHUNK`` tokens (the
    WY form): inside a chunk the rank-one writes are folded into one
    triangular system, between chunks the state is carried by a scan of
    ``S / CHUNK`` steps, every step a handful of matrix products.  Linear
    in the prompt's length.  Positions at or past a row's ``length``
    leave the state untouched (``alpha = 1``, ``beta = 0``): right-pad
    that attention never sees would otherwise be absorbed.
  - ``gdn_decode``: one token of every row of a decode batch, on the
    model's ONE stacked state leaf, in place.  On the TPU a Pallas
    kernel (``gdn_decode``); elsewhere the same arithmetic in XLA.

STATE LAYOUT.  The stacked leaf is ``[linear layers, entries, dk,
heads * dv]`` float32: a head's matrix is the ``dv`` lanes from ``h *
dv`` of every one of the ``dk`` rows (``pack_state`` / ``unpack_state``
go to and from ``[.., heads, dk, dv]``).  Dictated by TPU tiling, as the
KV pool's layout is: ``[.., dk, dv]`` with ``dv = 192`` pads every row
to 256 lanes in HBM and VMEM alike, a third more bytes a step for a
kernel that does nothing but move the state; ``heads * dv`` (5760 at
Olmo-Hybrid-7B's sizes) is whole lane tiles.  The leaf is addressed,
never sliced: a reader names ``[layer, entry]``, as ``[layer, page]`` in
ops/paged_attention.py, and it rides the layer scan and the step scan as
loop-carried, donated state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import backend_platform

CHUNK = 64
# the tokens of a batch whose chunks are prepared at a time under a
# decay a channel (``gated_delta_chunked``)
SEGMENT_TOKENS = 2048
_HIGHEST = jax.lax.Precision.HIGHEST


def pack_state(state: jax.Array) -> jax.Array:
    """``[.., heads, dk, dv]`` -> the leaf's ``[.., dk, heads * dv]``."""
    *lead, h, dk, dv = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, dk, h * dv)


def unpack_state(state: jax.Array, heads: int) -> jax.Array:
    """The leaf's ``[.., dk, heads * dv]`` -> ``[.., heads, dk, dv]``."""
    *lead, dk, hdv = state.shape
    return jnp.moveaxis(state.reshape(*lead, dk, heads, hdv // heads),
                        -2, -3)


def gated_delta_recurrent(q, k, v, g, beta, state0=None):
    """The equations of the module docstring, one token at a time.

    q, k: [B, S, H, dk]; v: [B, S, H, dv]; g (log decay, <= 0): [B, S,
    H] or, a channel of the key its own, [B, S, H, dk]; beta: [B, S,
    H]; state0 [B, H, dk, dv] (None: zeros).  Returns ``(o [B, S,
    H, dv] float32, state [B, H, dk, dv] float32)``."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    over_dv = (..., None) if g.ndim == 4 else (..., None, None)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[over_dv]
        ks = jnp.einsum("bhk,bhkv->bhv", kt, s, precision=_HIGHEST)
        s = s + kt[..., None] * (bt[..., None] * (vt - ks))[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s, precision=_HIGHEST)

    state, o = jax.lax.scan(
        step, state0.astype(jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(a):
    """``(I - a)^-1`` for strictly lower-triangular ``a [.., C, C]``, by
    doubling: with the inverse of the diagonal blocks of size ``s`` in
    hand (``s = 1``: ones), the block of size ``2s`` is ``[[X11, 0],
    [X22 a21 X11, X22]]``, and all blocks of a size are done by two
    products of whole matrices, ``inv + inv (a * below) inv``, ``below``
    naming each block's lower-left quarter.  log2(C) rounds, as stable
    as forward substitution.  (The Neumann product ``(I + a)(I + a^2)
    ...`` is the same count of products and is NOT: the powers of a
    matrix with entries near 2, which ``beta`` in (0, 2) allows, grow
    by orders of magnitude before they vanish, and float32 cancels.)"""
    c = a.shape[-1]
    i, j = np.arange(c)[:, None], np.arange(c)[None, :]
    inv = jnp.eye(c, dtype=a.dtype)
    s = 1
    while s < c:
        below = (i // (2 * s) == j // (2 * s)) & (i % (2 * s) >= s) & (
            j % (2 * s) < s)
        inv = inv + jnp.matmul(
            jnp.matmul(inv, a * below.astype(a.dtype), precision=_HIGHEST),
            inv, precision=_HIGHEST)
        s *= 2
    return inv


def _channelwise_products(q, k, gc):
    """``(kk, qk) [.., C, C]`` of one chunk under a decay a key channel
    its own: ``kk_ij = sum_d k_i[d] k_j[d] G_i[d] / G_j[d]`` for ``j <=
    i`` (``gc = log G``, the running sum of ``g`` from the chunk's
    start), ``qk`` the same with ``q_i``; zeros above the diagonal.

    The ratio no longer factors out of the product, and the factored
    form ``(k_i G_i) . (k_j / G_j)`` leaves float32 inside one chunk: a
    channel that loses e^-11 a step is at e^-704 after 64.  So every
    pair is summed a channel at a time over the ratio itself (``[C, C,
    dk]``, its exponent never positive): one fused reduction, which the
    compiler does not write out.  (Sub-chunks of 16, the pairs inside
    one summed so and the pairs across two factored around the later
    one's start, which are matrix products again, were built and
    measured: 8.7 ms against 7.2 a prompt of 2,048 tokens at 32 heads of
    128, 26.5 against 23.5 at 8 x 1,024, with three times the
    temporaries; PERF.md section 6, PR 55.)  ``k`` and ``q`` ride the
    sum together, one behind the other along the ROW axis, so that the
    ratios are read once; stacked on an axis of their own the compiler
    writes the ratios out (3.9 GB of temporaries a prompt of 8,192
    tokens, compiled ahead of time for a v5e)."""
    c = k.shape[-2]
    both = jnp.concatenate([k, q], axis=-2)                  # [.., 2 C, dk]
    lower = np.tril(np.ones((c, c), bool))[..., None]
    out = jnp.sum(
        both[..., :, None, :] * k[..., None, :, :] * jnp.exp(jnp.where(
            np.concatenate([lower, lower]),
            jnp.concatenate([gc, gc], -2)[..., :, None, :]
            - gc[..., None, :, :], -jnp.inf)), -1)           # [.., 2 C, C]
    return out[..., :c, :], out[..., c:, :]


def _scan_chunks(state, w, u, qk, qg, k_out, g_last, over_dv):
    """The state through the chunks ``[B, H, n, ..]`` of a prompt, a
    handful of matrix products a chunk: ``(state, o [n, B, H, C, dv])``
    (``gated_delta_chunked``'s names; ``over_dv`` broadcasts a chunk's
    whole decay, a scalar or a vector over ``dk``, over the state)."""
    def step(state, xs):
        w_i, u_i, qk_i, qg_i, k_i, gl_i = xs
        v_new = u_i - jnp.matmul(w_i, state, precision=_HIGHEST)
        o_i = (jnp.matmul(qg_i, state, precision=_HIGHEST)
               + jnp.matmul(qk_i, v_new, precision=_HIGHEST))
        state = (state * jnp.exp(gl_i)[over_dv]
                 + jnp.einsum("bhck,bhcv->bhkv", k_i, v_new,
                              precision=_HIGHEST))
        return state, o_i

    return jax.lax.scan(
        step, state,
        tuple(jnp.moveaxis(a, 2, 0) for a in (w, u, qk, qg, k_out, g_last)))


def _channelwise_chunks(state, q, k, v, g, beta):
    """``_scan_chunks`` of chunks ``[B, H, n, C, ..]`` under a decay a
    channel ``g [B, H, n, C, dk]``, with what goes in front of it."""
    c = q.shape[-2]
    gc = jnp.cumsum(g, axis=-2)                      # log G_i, a channel
    kb = k * beta[..., None]
    kk, qk = _channelwise_products(q, k, gc)
    t = _unit_lower_inverse(-kk * beta[..., None] * jnp.tril(
        jnp.ones((c, c), jnp.float32), -1))
    w = jnp.matmul(t, kb * jnp.exp(gc), precision=_HIGHEST)
    u = jnp.matmul(t, v * beta[..., None], precision=_HIGHEST)
    g_last = gc[..., -1, :]
    return _scan_chunks(state, w, u, qk, q * jnp.exp(gc),
                        k * jnp.exp(g_last[..., None, :] - gc), g_last,
                        (..., None))


def gated_delta_chunked(q, k, v, g, beta, lengths=None, state0=None,
                        chunk: int = CHUNK):
    """A whole prompt, ``chunk`` tokens at a time; arguments and results
    as ``gated_delta_recurrent``, plus ``lengths [B]``: a row's real
    length (None: every position is real).  Positions at or past it
    leave the state as it was; their outputs mean nothing.

    Inside a chunk, with ``G_i`` the decay from the chunk's start to
    position ``i``: ``A_ij = -beta_i (k_i . k_j) G_i / G_j`` for ``j <
    i``, ``T = (I - A)^-1``, ``W = T (beta k G)``, ``U = T (beta v)``.
    Then, with the state ``S`` at the chunk's start, ``V' = U - W S`` are
    the values the writes really store, ``O = (q G) S + tril(q k^T G_i /
    G_j) V'`` and ``S <- G_last S + (k G_last / G)^T V'``.  Under a
    decay a channel (``g [B, S, H, dk]``) ``G`` is a vector that
    multiplies ``k``, ``q`` and the state's rows channel by channel, and
    the two decayed products are ``_channelwise_products``'; what goes
    in front of the chunk scan is then made ``SEGMENT_TOKENS`` of the
    batch's tokens at a time, an outer scan over the segments carrying
    the state (made for a whole wave at once it is 2 GB at 8,192 tokens,
    beside a serving engine that holds 13)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    channelwise = g.ndim == 4
    if lengths is not None:
        real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
        g = jnp.where(real[..., None] if channelwise else real, g, 0.0)
        beta = jnp.where(real, beta, 0.0)
    c = min(chunk, s)
    n = -(-s // c)
    # chunks a segment, and whole segments
    seg = max(1, min(n, SEGMENT_TOKENS // (b * c))) if channelwise else n
    n = -(-n // seg) * seg
    pad = n * c - s
    if pad:                 # the tail's g = beta = 0 writes nothing
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    # [B, H, n, C, ..]
    q, k, v = (jnp.moveaxis(a.reshape(b, n, c, h, -1), 3, 1)
               for a in (q, k, v))
    over_heads = lambda a: jnp.moveaxis(                     # noqa: E731
        a.reshape(b, n, c, h, *a.shape[3:]), 3, 1)
    g, beta = over_heads(g), over_heads(beta)
    start = lambda: (jnp.zeros((b, h, dk, dv), f32)          # noqa: E731
                     if state0 is None else state0).astype(f32)
    if channelwise:
        split = lambda a: jnp.moveaxis(a.reshape(            # noqa: E731
            b, h, n // seg, seg, *a.shape[3:]), 2, 0)
        state, o = jax.lax.scan(
            lambda state, xs: _channelwise_chunks(state, *xs), start(),
            tuple(split(a) for a in (q, k, v, g, beta)))
        o = o.reshape(n, *o.shape[2:])
    else:
        gc = jnp.cumsum(g, axis=-1)                  # log G_i
        # decay from j to i, for j <= i only (above the diagonal the
        # difference is positive and its exponential can overflow)
        lower = jnp.tril(jnp.ones((c, c), bool))
        ratio = jnp.exp(jnp.where(
            lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        kb = k * beta[..., None]
        kk = jnp.einsum("bhnik,bhnjk->bhnij", kb, k, precision=_HIGHEST)
        t = _unit_lower_inverse(-kk * ratio * jnp.tril(jnp.ones((c, c), f32),
                                                       -1))
        w = jnp.matmul(t, kb * jnp.exp(gc)[..., None], precision=_HIGHEST)
        u = jnp.matmul(t, v * beta[..., None], precision=_HIGHEST)
        qk = jnp.einsum("bhnik,bhnjk->bhnij", q, k,
                        precision=_HIGHEST) * ratio
        qg = q * jnp.exp(gc)[..., None]
        g_last = gc[..., -1]
        k_out = k * jnp.exp(g_last[..., None] - gc)[..., None]
        state, o = _scan_chunks(start(), w, u, qk, qg, k_out, g_last,
                                (..., None, None))
    # [n, B, H, C, dv] -> [B, S, H, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)
    return jnp.moveaxis(o, 1, 2)[:, :s], state


# ------------------------------------------------------------- decode step

def flat_rows(leaf, layer, entries):
    """A stacked leaf ``[layers, entries, a, b]`` as its entries in a
    row (a bitcast: the tiled minor pair stays) and each decode row's
    index among them."""
    n_layers, n_entries = leaf.shape[:2]
    return (leaf.reshape((n_layers * n_entries,) + leaf.shape[2:]),
            layer * n_entries + entries)


def write_rows(leaf, rows, layer, entries):
    """``leaf[layer, entries[i]] = rows[i]`` on a stacked leaf ``[layers,
    entries, a, b]``, ``rows [n, a, b]``: one ``dynamic_update_slice`` a
    row, in place when the leaf is loop-carried and donated (as
    ``write_kv_pages`` writes a prompt's pages: a row is megabytes).
    Rows that name the same entry (a wave's padding rows all name
    scratch) overwrite each other in order."""
    rows = rows.astype(leaf.dtype)

    def one(i, leaf):
        row = jax.lax.dynamic_index_in_dim(rows, i, keepdims=True)
        return jax.lax.dynamic_update_slice(
            leaf, row[None], (layer, entries[i], 0, 0))
    return jax.lax.fori_loop(0, rows.shape[0], one, leaf)


def gdn_decode_xla(q, k, v, g, beta, state, entries, live, *, layer=0):
    """One token a row, gather / update / scatter on the stacked leaf.
    Runs on every backend (the CPU's path and the kernel's oracle).  A
    row ``live`` leaves out stores back the bits it read."""
    heads = q.shape[1]
    flat, at = flat_rows(state, layer, entries)
    old = flat[at]                                   # [rows, dk, H*dv]
    o, new = gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        unpack_state(old, heads))
    new = jnp.where(live[:, None, None], pack_state(new), old)
    o = jnp.where(live[:, None, None], o[:, 0], 0.0)
    return o, flat.at[at].set(new).reshape(state.shape)


def _lane_group(dv: int) -> int:
    """Heads a lane-aligned slice of the state holds: the fewest whose
    ``dv`` lanes add up to whole tiles of 128."""
    return 128 // math.gcd(dv, 128)


def _tpu_kernel(q_t, k_t, v, alpha, beta, state, layer, ent, row, n,
                interpret: bool = False):
    """Pallas TPU kernel: one grid step a decode row, walking the
    COMPACTED list of live rows (``row`` [rows] their indices, ``ent``
    their state entries, ``n`` [1] how many; all scalar-prefetch
    operands, made by the caller from ``live``).  Step ``i`` names the
    block ``state[layer, ent[i]]`` for reading and for writing (the leaf
    is aliased to the output), so the pipeline fetches row ``i + 1``'s
    2.2 MB while row ``i`` is computed and row ``i - 1`` written back.
    Past ``n`` the lists repeat their last entry: a block index that
    does not change moves nothing, and the body is skipped, so a dead
    row issues no DMA and no vector work and its entry keeps its bits.
    (No live row at all: the lists name scratch entry 0.)

    ``q_t, k_t`` [rows, dk, heads] float32 (dk on sublanes: a head's
    vector is a lane of the tile, broadcast over its ``dv`` lanes of the
    state); ``v, alpha, beta`` [rows, 1, heads * dv] float32, the two
    gates repeated over each head's lanes by the caller.  A decay a key
    channel (``alpha [rows, dk, heads]``, laid out as ``k_t`` is) scales
    each ROW of a head's matrix by its own factor: the same body, the
    factor broadcast over lanes as ``k`` is, under its own name in a
    device trace (``kda_decode``).  All vector work is on lane-aligned
    slices of ``_lane_group`` heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, dk, heads = q_t.shape
    hdv = state.shape[-1]
    dv = hdv // heads
    hg = _lane_group(dv)
    width = hg * dv
    channelwise = alpha.shape[1:] == (dk, heads)

    def expand(x_t, first):
        """[dk, width]: head ``first + j``'s column over lanes ``j * dv
        .. (j + 1) * dv``."""
        out = jnp.broadcast_to(x_t[:, first:first + 1], (dk, width))
        if hg > 1:
            lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
            for j in range(1, hg):
                out = jnp.where(
                    lane >= j * dv, jnp.broadcast_to(
                        x_t[:, first + j:first + j + 1], (dk, width)), out)
        return out

    def kernel(layer_ref, ent_ref, row_ref, n_ref, q_ref, k_ref, v_ref,
               a_ref, b_ref, s_ref, o_ref, s_out):
        @pl.when(pl.program_id(0) < n_ref[0])
        def _():
            q_all, k_all = q_ref[...], k_ref[...]
            a_all = a_ref[...] if channelwise else None
            for p in range(heads // hg):
                lanes = slice(p * width, (p + 1) * width)
                kx, qx = expand(k_all, p * hg), expand(q_all, p * hg)
                s = s_ref[:, lanes] * (expand(a_all, p * hg) if channelwise
                                       else a_ref[:, lanes])
                ks = jnp.sum(kx * s, axis=0, keepdims=True)
                s = s + kx * (b_ref[:, lanes] * (v_ref[:, lanes] - ks))
                s_out[:, lanes] = s
                o_ref[:, lanes] = jnp.sum(qx * s, axis=0, keepdims=True)

    by_row = lambda i, layer, ent, row, n: (row[i], 0, 0)       # noqa: E731
    by_entry = lambda i, layer, ent, row, n: (layer[0], ent[i], 0, 0)  # noqa: E731
    vec = pl.BlockSpec((None, dk, heads), by_row)
    lanes = pl.BlockSpec((None, 1, hdv), by_row)
    entry = pl.BlockSpec((None, None, dk, hdv), by_entry)
    block_bytes = dk * hdv * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(rows,),
            in_specs=[vec, vec, lanes, vec if channelwise else lanes, lanes,
                      entry],
            out_specs=[lanes, entry]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, hdv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 9 (after the four scalar operands): the state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two blocks in flight each way, and the slices' temporaries
            vmem_limit_bytes=min(100 << 20, 6 * block_bytes + (8 << 20))),
        name="kda_decode" if channelwise else "gdn_decode",
        interpret=interpret,
    )(layer, ent, row, n, q_t, k_t, v, alpha, beta, state)


def gdn_decode_tpu(q, k, v, g, beta, state, entries, live, *, layer=0,
                   interpret: bool = False):
    rows, heads, _ = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    # compact the live rows to the front, in order; past them the last
    # live row again (row 0 / scratch entry 0 where none is live)
    live = live.astype(bool)
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = order[jnp.minimum(jnp.arange(rows), jnp.maximum(n - 1, 0))]
    ent = jnp.where(n > 0, entries.astype(jnp.int32)[order], 0)
    over_lanes = lambda a: jnp.repeat(                        # noqa: E731
        a.astype(f32), dv, axis=-1)[:, None]
    o, state = _tpu_kernel(
        jnp.swapaxes(q.astype(f32), 1, 2), jnp.swapaxes(k.astype(f32), 1, 2),
        v.astype(f32).reshape(rows, 1, heads * dv),
        (jnp.swapaxes(jnp.exp(g.astype(f32)), 1, 2) if g.ndim == 3
         else over_lanes(jnp.exp(g))), over_lanes(beta), state,
        jnp.asarray(layer, jnp.int32).reshape(1), ent, order, n.reshape(1),
        interpret=interpret)
    o = jnp.where(live[:, None, None], o.reshape(rows, heads, dv), 0.0)
    return o, state


def resolve_gdn_impl(heads: int, dv: int, impl: str = "auto") -> str:
    """``"tpu"`` (the Pallas kernel) on a TPU backend where the heads
    come in whole lane-aligned groups, else ``"xla"``."""
    if impl == "auto":
        return ("tpu" if backend_platform() == "tpu"
                and heads % _lane_group(dv) == 0 else "xla")
    if impl not in ("tpu", "xla"):
        raise ValueError(f"unknown gdn_decode impl: {impl!r}")
    return impl


def gdn_decode(q, k, v, g, beta, state, entries, live=None, *, layer=0,
               impl: str = "auto"):
    """One decode token of every row, on the stacked state, in place
    when the leaf is loop-carried and donated.

    q, k: [rows, H, dk] (``k`` of unit length); v: [rows, H, dv]; g
    (log decay): [rows, H] or, a key channel its own, [rows, H, dk];
    beta: [rows, H]; state: ``[layers, entries, dk, H *
    dv]`` float32; entries [rows]: each row's entry, distinct among the
    ``live`` rows; live [rows] bool (None: every row); layer: int or
    traced scalar.  Returns ``(o [rows, H, dv] float32, state)``.  A
    row ``live`` leaves out reads nothing, returns zeros, and its entry
    is bit for bit what it was."""
    if live is None:
        live = jnp.ones((q.shape[0],), bool)
    fn = (gdn_decode_tpu if resolve_gdn_impl(q.shape[1], v.shape[-1], impl)
          == "tpu" else gdn_decode_xla)
    return fn(q, k, v, g, beta, state, entries, live, layer=layer)
