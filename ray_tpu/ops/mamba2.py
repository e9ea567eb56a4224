"""The Mamba-2 selective state-space recurrence (SSD): a layer whose
memory of a sequence is one matrix a head with a SCALAR decay, not a
cache that grows with the context.

Per head ``h`` of group ``g = h // (heads / groups)``, with ``u`` in R^P
(the head's channels after the convolution), ``B, C`` in R^N (shared by
the heads of a group), a step ``delta > 0`` and ``A < 0``::

    a_t = exp(delta_t * A)
    H_t = a_t H_{t-1} + (delta_t u_t) (x) B_t            H in R^(P x N)
    y_t = H_t C_t                  (``+ D u_t`` is the caller's)

Three forms that must agree (tests/test_mamba2.py):

  - ``ssm_scan``: the equations token by token (``lax.scan``), float32.
    The oracle; never on the served path.
  - ``ssm_chunked``: a prompt in chunks of ``chunk`` tokens (the SSD
    form): inside a chunk the outputs are one masked ``(C B^T) * decay``
    product, between chunks the state is carried by a scan of ``S /
    chunk`` steps.  Positions at or past a row's ``length`` leave the
    state untouched (``delta = 0``: no decay, no write): right-pad that
    attention never sees would otherwise be absorbed.
  - ``ssm_decode``: one token of every row of a decode batch, on the
    model's ONE stacked state leaf, in place.  On the TPU a Pallas
    kernel (``ssm_decode``); elsewhere the same arithmetic in XLA.

STATE LAYOUT.  The stacked leaf is ``[ssm layers, entries, N, heads *
P]`` float32, ``ops/gated_delta.py``'s layout with the state size for
the keys' width: a head's matrix (transposed, ``[N, P]``) is the ``P``
lanes from ``h * P`` of every one of the ``N`` rows, so the update's
``B`` is a column broadcast over lanes, ``delta u`` and the decay are
lane rows as the projections produce them, and ``H C`` is a sum over
rows (``pack_state`` / ``unpack_state`` of that module go to and from
``[.., heads, N, P]``).  ``[.., heads, P, N]`` with ``P = 64`` would want
a reduction over lanes a head and a transposed ``delta u``.  The leaf is
addressed ``[layer, entry]``, never sliced, and rides the layer scan and
the step scan as loop-carried, donated state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import backend_platform
from ray_tpu.ops.gated_delta import flat_rows, pack_state, unpack_state

_HIGHEST = jax.lax.Precision.HIGHEST


def _over_heads(bc, heads: int):
    """``[.., G, N]`` of the groups -> ``[.., H, N]`` of their heads."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def ssm_scan(u, delta, a_neg, b, c, state0=None):
    """The equations of the module docstring, one token at a time.

    u: [B, S, H, P]; delta: [B, S, H] (after its softplus); a_neg: [H]
    (``A``, negative); b, c: [B, S, G, N]; state0 [B, H, N, P] (None:
    zeros).  Returns ``(y [B, S, H, P] float32, state [B, H, N, P]
    float32)``, ``y`` without the ``D u`` term."""
    f32 = jnp.float32
    u, delta, b, c = (x.astype(f32) for x in (u, delta, b, c))
    bsz, _, h, p = u.shape
    b, c = _over_heads(b, h), _over_heads(c, h)
    if state0 is None:
        state0 = jnp.zeros((bsz, h, b.shape[-1], p), f32)

    def step(s, xs):
        ut, dt, bt, ct = xs
        s = (s * jnp.exp(dt * a_neg)[..., None, None]
             + bt[..., :, None] * (dt[..., None] * ut)[..., None, :])
        return s, jnp.einsum("bhn,bhnp->bhp", ct, s, precision=_HIGHEST)

    state, y = jax.lax.scan(
        step, state0.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, delta, b, c)))
    return jnp.moveaxis(y, 0, 1), state


def ssm_chunked(u, delta, a_neg, b, c, lengths=None, state0=None,
                chunk: int = 128):
    """A whole prompt, ``chunk`` tokens at a time; arguments and results
    as ``ssm_scan``, plus ``lengths [B]``: a row's real length (None:
    every position is real).  Positions at or past it leave the state
    as it was; their outputs mean nothing.

    Inside a chunk, with ``G_i`` the log decay from the chunk's start to
    position ``i`` (inclusive) and ``x_j = delta_j u_j``: ``y_i = sum_{j
    <= i} exp(G_i - G_j) (C_i . B_j) x_j + exp(G_i) C_i H`` with ``H``
    the state at the chunk's start, and ``H <- exp(G_last) H + sum_j
    exp(G_last - G_j) B_j (x) x_j``.  All of a chunk's work is one scan
    step, so what is live at a time is one chunk's ``[B, H, chunk,
    chunk]`` decays, not the prompt's."""
    f32 = jnp.float32
    u, delta, b, c = (x.astype(f32) for x in (u, delta, b, c))
    bsz, s, h, p = u.shape
    n = b.shape[-1]
    if lengths is not None:
        delta = jnp.where(
            (jnp.arange(s)[None, :] < lengths[:, None])[..., None],
            delta, 0.0)
    q = min(chunk, s)
    pad = -s % q
    if pad:                     # the tail's delta = 0 writes nothing
        u, delta, b, c = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (u, delta, b, c))
    nc = (s + pad) // q
    chunks = lambda x: jnp.moveaxis(                           # noqa: E731
        x.reshape((bsz, nc, q) + x.shape[2:]), 1, 0)   # [nc, B, q, ..]
    lower = jnp.tril(jnp.ones((q, q), bool))
    if state0 is None:
        state0 = jnp.zeros((bsz, h, n, p), f32)

    def step(state, xs):
        ut, dt, bt, ct = xs          # [B, q, H, P], [B, q, H], [B, q, G, N]
        gc = jnp.cumsum(dt * a_neg, axis=1)                   # [B, q, H]
        gct = jnp.moveaxis(gc, 1, 2)                          # [B, H, q]
        # decay from j to i, for j <= i only (above the diagonal the
        # difference is positive and its exponential can overflow)
        decay = jnp.exp(jnp.where(
            lower, gct[..., :, None] - gct[..., None, :], -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bgij", ct, bt, precision=_HIGHEST)
        x = ut * dt[..., None]                                # [B, q, H, P]
        y = jnp.einsum("bhij,bjhp->bihp",
                       jnp.repeat(cb, h // cb.shape[1], axis=1) * decay, x,
                       precision=_HIGHEST)
        ch = _over_heads(ct, h)                               # [B, q, H, N]
        y = y + jnp.exp(gc)[..., None] * jnp.einsum(
            "bihn,bhnp->bihp", ch, state, precision=_HIGHEST)
        g_last = gct[..., -1]                                 # [B, H]
        carry = jnp.exp(g_last[:, None] - gc)                 # [B, q, H]
        state = (state * jnp.exp(g_last)[..., None, None]
                 + jnp.einsum("bjhn,bjhp->bhnp", _over_heads(bt, h),
                              x * carry[..., None], precision=_HIGHEST))
        return state, y

    state, y = jax.lax.scan(step, state0.astype(f32),
                            tuple(chunks(x) for x in (u, delta, b, c)))
    # [nc, B, q, H, P] -> [B, S, H, P]
    return jnp.moveaxis(y, 0, 1).reshape(bsz, nc * q, h, p)[:, :s], state


# ------------------------------------------------------------- decode step

def ssm_decode_xla(x, a, b, c, state, entries, live, *, layer=0):
    """One token a row, gather / update / scatter on the stacked leaf.
    Runs on every backend (the CPU's path and the kernel's oracle).  A
    row ``live`` leaves out stores back the bits it read."""
    heads = x.shape[1]
    flat, at = flat_rows(state, layer, entries)
    old = flat[at]                                   # [rows, N, H*P]
    s = unpack_state(old, heads)                     # [rows, H, N, P]
    bh, ch = _over_heads(b, heads), _over_heads(c, heads)
    s = (s * a[..., None, None]
         + bh.astype(jnp.float32)[..., :, None] * x[..., None, :])
    y = jnp.einsum("bhn,bhnp->bhp", ch.astype(jnp.float32), s,
                   precision=_HIGHEST)
    new = jnp.where(live[:, None, None], pack_state(s), old)
    y = jnp.where(live[:, None, None], y, 0.0)
    return y, flat.at[at].set(new).reshape(state.shape)


def _tpu_kernel(b_t, c_t, x, a, state, layer, ent, row, n,
                interpret: bool = False):
    """Pallas TPU kernel: one grid step a decode row, walking the
    COMPACTED list of live rows (``row`` [rows] their indices, ``ent``
    their state entries, ``n`` [1] how many; scalar-prefetch operands,
    made by the caller from ``live``), as ``ops/gated_delta.py``'s
    kernel does: step ``i`` names the block ``state[layer, ent[i]]`` for
    reading and for writing (the leaf is aliased to the output), so the
    pipeline fetches row ``i + 1``'s 4.2 MB while row ``i`` is computed
    and row ``i - 1`` written back.  Past ``n`` the lists repeat their
    last entry: a block index that does not change moves nothing, and
    the body is skipped, so a dead row issues no DMA and no vector work
    and its entry keeps its bits.

    ``b_t, c_t`` [rows, N, groups] float32 (N on sublanes: a group's
    vector is a lane of the tile, broadcast over its heads' lanes of the
    state); ``x`` (``delta u``), ``a`` (the decay, repeated over each
    head's lanes by the caller) [rows, 1, heads * P] float32.  All
    vector work is on lane-aligned slices of 128 lanes, each inside one
    group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, ns, groups = b_t.shape
    hp = state.shape[-1]
    group_lanes = hp // groups

    def kernel(layer_ref, ent_ref, row_ref, n_ref, b_ref, c_ref, x_ref,
               a_ref, s_ref, y_ref, s_out):
        @pl.when(pl.program_id(0) < n_ref[0])
        def _():
            b_all, c_all = b_ref[...], c_ref[...]
            for g in range(groups):
                bx = jnp.broadcast_to(b_all[:, g:g + 1], (ns, 128))
                cx = jnp.broadcast_to(c_all[:, g:g + 1], (ns, 128))
                for lo in range(g * group_lanes, (g + 1) * group_lanes, 128):
                    lanes = slice(lo, lo + 128)
                    s = (s_ref[:, lanes] * a_ref[:, lanes]
                         + bx * x_ref[:, lanes])
                    s_out[:, lanes] = s
                    y_ref[:, lanes] = jnp.sum(cx * s, axis=0, keepdims=True)

    by_row = lambda i, layer, ent, row, n: (row[i], 0, 0)       # noqa: E731
    by_entry = lambda i, layer, ent, row, n: (layer[0], ent[i], 0, 0)  # noqa: E731
    vec = pl.BlockSpec((None, ns, groups), by_row)
    lanes = pl.BlockSpec((None, 1, hp), by_row)
    entry = pl.BlockSpec((None, None, ns, hp), by_entry)
    block_bytes = ns * hp * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(rows,),
            in_specs=[vec, vec, lanes, lanes, entry],
            out_specs=[lanes, entry]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, hp), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the four scalar operands): the state
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two blocks in flight each way, and the slices' temporaries
            vmem_limit_bytes=min(100 << 20, 5 * block_bytes + (8 << 20))),
        name="ssm_decode", interpret=interpret,
    )(layer, ent, row, n, b_t, c_t, x, a, state)


def ssm_decode_tpu(x, a, b, c, state, entries, live, *, layer=0,
                   interpret: bool = False):
    rows, heads, p = x.shape
    f32 = jnp.float32
    # compact the live rows to the front, in order; past them the last
    # live row again (row 0 / scratch entry 0 where none is live)
    live = live.astype(bool)
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = order[jnp.minimum(jnp.arange(rows), jnp.maximum(n - 1, 0))]
    ent = jnp.where(n > 0, entries.astype(jnp.int32)[order], 0)
    y, state = _tpu_kernel(
        jnp.swapaxes(b.astype(f32), 1, 2), jnp.swapaxes(c.astype(f32), 1, 2),
        x.astype(f32).reshape(rows, 1, heads * p),
        jnp.repeat(a.astype(f32), p, axis=-1)[:, None], state,
        jnp.asarray(layer, jnp.int32).reshape(1), ent, order, n.reshape(1),
        interpret=interpret)
    y = jnp.where(live[:, None, None], y.reshape(rows, heads, p), 0.0)
    return y, state


def resolve_ssm_impl(heads: int, p: int, groups: int,
                     impl: str = "auto") -> str:
    """``"tpu"`` (the Pallas kernel) on a TPU backend where a group's
    heads fill whole tiles of 128 lanes, else ``"xla"``."""
    if impl == "auto":
        return ("tpu" if backend_platform() == "tpu"
                and (heads // groups * p) % 128 == 0 else "xla")
    if impl not in ("tpu", "xla"):
        raise ValueError(f"unknown ssm_decode impl: {impl!r}")
    return impl


def ssm_decode(x, a, b, c, state, entries, live=None, *, layer=0,
               impl: str = "auto"):
    """One decode token of every row, on the stacked state, in place
    when the leaf is loop-carried and donated.

    x: [rows, H, P] (``delta u``, float32); a: [rows, H] (the decay
    ``exp(delta A)``); b, c: [rows, G, N]; state: ``[layers, entries, N,
    H * P]`` float32; entries [rows]: each row's entry, distinct among
    the ``live`` rows; live [rows] bool (None: every row); layer: int or
    traced scalar.  Returns ``(y [rows, H, P] float32, state)``, ``y``
    without the ``D u`` term.  A row ``live`` leaves out reads nothing,
    returns zeros, and its entry is bit for bit what it was."""
    if live is None:
        live = jnp.ones((x.shape[0],), bool)
    fn = (ssm_decode_tpu if resolve_ssm_impl(
        x.shape[1], x.shape[2], b.shape[1], impl) == "tpu"
        else ssm_decode_xla)
    return fn(x, a, b, c, state, entries, live, layer=layer)
