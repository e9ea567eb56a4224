"""Flash attention as a Pallas TPU kernel (forward + backward).

Blockwise online-softmax attention in two kernels, ``flash_fwd`` and
``flash_bwd_dqkv``, that pay for the triangle a causal call needs and
visit each tile pair once.  Two levels of blocks:

  - The GRID walks spans of the two sequences (up to 1024 positions
    each): the reduction axis is innermost and ``"arbitrary"``, operands
    arrive as ``(1, span, head_dim)`` blocks, running statistics and
    accumulators live in VMEM scratch from the reduction's first span to
    its last.  No operand is mapped whole, so VMEM holds a few spans
    whatever the sequence length.  A span pair wholly above the diagonal
    does no work (``pl.when``) and its index map names the diagonal's
    span again, so no DMA is issued for it.
  - INSIDE a span pair the kernel walks ``block_q x block_k`` tiles in a
    loop that is unrolled when the kernel is traced: on a diagonal span
    pair the tiles the mask covers whole are left out by plain
    arithmetic, the ``iota``/compare/select mask is built only on the
    tiles the diagonal crosses, and a span pair below the diagonal
    builds none.  Adjacent tiles of a kind go to the MXU as one strip.
    (One pair a grid step was tried first: a step's fixed cost and its
    statistics outweigh a 256 x 256 tile's work; PERF.md section 6,
    PR 34.)

Every tile is computed TRANSPOSED, ``k @ q^T``: keys on sublanes,
queries on lanes.  The softmax statistics, lse and delta are then
lane-dense ``[1, block_q]`` rows, reductions over keys run down
sublanes, and none of the second products needs a score tile
transposed: the forward accumulates ``o^T += v^T @ p^T`` (``v^T`` made
once a span, the ``[head_dim, span]`` accumulator turned once at the
end), and the backward makes ``s^T``, ``p^T``, ``dp^T = v @ dO^T`` and
``ds^T`` ONCE a tile and feeds all three gradients from them:
``dv += p^T @ dO`` and ``dk += ds^T @ q`` as they stand,
``dq^T += k^T @ ds^T`` with ``k^T`` made once a kv strip.  lse and delta
travel as ``[batch*heads, 1, q_len]``: a trailing axis of 1 pads to 128
lanes in HBM as in VMEM.

What the backward keeps in scratch: under grid (bh, kv span, q span) the
kv side is resident, dk and dv ``[span_k, head_dim]`` float32 sum over a
kv span's q spans and are written at the last; dq^T of the head's WHOLE
q sequence, ``[q spans, head_dim, span_q]`` float32, sums across the kv
spans, and every visit writes its q span's dq block from the sum so far,
the last visit (the diagonal span under ``causal``) leaving the whole
sum.  That accumulator is what bounds the backward: ``plan_blocks``
holds it to ``DQ_ACC_BYTES`` (8,192 positions at heads of 128), and a
gradient asked for beyond it is refused by the kernel's name.

Operands reach the MXU in the dtype they arrive in (bf16 stays bf16),
products accumulate in float32; the probabilities and ``ds`` are cast to
the operands' dtype before the second products.  ``m``, ``l``, lse,
delta, the scores and the accumulators are float32.  The softmax scale
is folded into the tile's resident operand (q in ``flash_fwd``, k in
``flash_bwd_dqkv``, where dq then comes out scaled) where that is exact
— ``head_dim ** -0.5`` a power of two — and applied to the float32
scores otherwise.

Spans and tiles come from ``plan_blocks``, a function of the sequence
lengths (the probes that chose its targets read the same ones fastest at
both head sizes and operand dtypes), which also returns the share of
tile pairs a causal call visits and whether the backward's accumulator
fits.  A sequence that no lane-aligned block divides (ViT's 197) runs as
one tile, the whole sequence.

On the CPU backend the same kernels run under ``interpret=True`` so unit
tests exercise the identical code path (SURVEY.md §4 device-simulation
strategy).
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import backend_platform

_CompilerParams = pltpu.CompilerParams

NEG_INF = -1e30
LANES = 128

# contract the last dim of both operands: a @ b^T, native on the MXU
_NT = (((1,), (1,)), ((), ()))


def _interpret() -> bool:
    """Interpret mode only where the platform really is the CPU (unit
    tests).  Any other platform gets the compiled kernel, and a backend
    that fails to start raises instead of silently interpreting."""
    return backend_platform() == "cpu"


def _pick_block(seq: int, target: int) -> int:
    b = min(target, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


# --------------------------------------------------------------------------- #
# The plan: spans, score tiles and the pairs a call visits                    #
# --------------------------------------------------------------------------- #

class Tiling(NamedTuple):
    """One kernel's blocks.  The grid walks SPANS of the two sequences;
    inside a span pair the kernel walks (q tile, kv tile) pairs of
    ``block_q x block_k`` scores.  ``visited`` of ``total`` such pairs do
    work: all of them, or under ``causal`` those the mask does not cover
    whole."""
    span_q: int
    span_k: int
    block_q: int
    block_k: int
    visited: int
    total: int


class FlashPlan(NamedTuple):
    """``fused``: the backward's dq^T accumulator fits ``DQ_ACC_BYTES``;
    a call beyond it has a forward and no backward."""
    fwd: Tiling
    bwd: Tiling
    fused: bool


def _aligned_block(seq: int, target: int, step: int = LANES) -> int:
    """The largest multiple of ``step`` and of 128 up to ``target`` that
    divides ``seq``; the whole sequence where there is none."""
    step = math.lcm(step, LANES)
    for b in range(min(target, seq) // step * step, 0, -step):
        if seq % b == 0:
            return b
    return seq


def _tiling(q_len: int, kv_len: int, block_q: int, block_k: int,
            causal: bool) -> Tiling:
    nq, nk = q_len // block_q, kv_len // block_k
    if causal:
        # one span for both sequences, so that a span pair is wholly
        # above, on or wholly below the diagonal; a tile pair has an
        # unmasked element iff its first column is not past its last row
        span_q = span_k = _aligned_block(q_len, SPAN,
                                         math.lcm(block_q, block_k))
        visited = sum(min(nk, ((a + 1) * block_q - 1) // block_k + 1)
                      for a in range(nq))
    else:
        span_q = _aligned_block(q_len, SPAN, block_q)
        span_k = _aligned_block(kv_len, SPAN, block_k)
        visited = nq * nk
    return Tiling(span_q, span_k, block_q, block_k, visited, nq * nk)


def plan_blocks(q_len: int, kv_len: int, causal: bool,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None, *,
                head_dim: int) -> FlashPlan:
    """Spans and score tiles of the two kernels from what the call can
    observe, the share of tile pairs it visits, and whether its backward
    is the fused kernel's to run.

    The tiles are the largest lane-aligned divisors of the sequences up
    to the targets below, the spans likewise up to ``SPAN``.  Probes of
    the kernels alone on a v5e (PERF.md section 6, PRs 34 and 47) read
    the same targets fastest at ``head_dim`` 64 and 128, bf16 and
    float32, sequences of 1,024 to 4,096, causal and not, so the lengths
    are all the tiles read.  ``block_q`` / ``block_k`` given explicitly
    (tests) are the score tile of both kernels, cut to a divisor of the
    sequence as before.  ``fused`` reads ``q_len`` and ``head_dim``: the
    backward holds dq^T of a head's whole q sequence in float32.
    """
    fused = 4 * q_len * head_dim <= DQ_ACC_BYTES
    if block_q is not None or block_k is not None:
        t = _tiling(q_len, kv_len, _pick_block(q_len, block_q or q_len),
                    _pick_block(kv_len, block_k or kv_len), causal)
        return FlashPlan(t, t, fused)
    return FlashPlan(*(
        _tiling(q_len, kv_len, _aligned_block(q_len, tq),
                _aligned_block(kv_len, tk), causal)
        for tq, tk in _TARGETS), fused)


# a span's operands are whole in VMEM
SPAN = 1024
# (block_q, block_k) targets of fwd, bwd
_TARGETS = ((512, 512), (256, 256))
# what the backward may keep in VMEM as dq^T [head_dim, q_len] float32
# across a head's kv spans: 8,192 positions at heads of 128
DQ_ACC_BYTES = 4 * 2 ** 20


# --------------------------------------------------------------------------- #
# Shared pieces of the two bodies                                             #
# --------------------------------------------------------------------------- #

def _scale_is_exact(sm_scale: float) -> bool:
    """A power of two scales any float operand without rounding."""
    return sm_scale > 0 and math.frexp(sm_scale)[0] == 0.5


def _tile_pairs(span_q: int, span_k: int, block_q: int, block_k: int,
                diagonal: bool):
    """The (q tile, kv tile, masked) of a span pair that do work, by plain
    arithmetic when the kernel is traced: every pair of a span pair below
    the diagonal; on a DIAGONAL span pair (same rows as columns) not the
    tiles the causal mask covers whole, and ``masked`` on those the
    diagonal crosses."""
    return [(a, b, diagonal and b * block_k + block_k - 1 > a * block_q)
            for a in range(span_q // block_q)
            for b in range(span_k // block_k)
            if not diagonal or b * block_k <= a * block_q + block_q - 1]


def _strips(pairs, axis: int, sizes):
    """``_tile_pairs`` grouped by their q tile (``axis`` 0) or kv tile
    (1): [(outer slice, [(inner slice, masked)])], adjacent inner tiles
    of a kind as one strip, so an outer tile meets at most one strip the
    diagonal crosses and one it does not."""
    size, inner = sizes[axis], sizes[1 - axis]
    out = []
    for i, group in itertools.groupby(
            sorted(pairs, key=lambda pair: pair[axis]),
            key=lambda pair: pair[axis]):
        strips = []
        for masked, run in itertools.groupby(group, key=lambda pair: pair[2]):
            run = list(run)
            strips.append((slice(run[0][1 - axis] * inner,
                                 (run[-1][1 - axis] + 1) * inner), masked))
        out.append((slice(i * size, (i + 1) * size), strips))
    return out


def _on_spans(causal: bool, spans: int, qi, ki, body):
    """Run ``body(diagonal)`` for span pair (qi, ki) of a grid of ``spans``
    a sequence: not at all above the diagonal, and a pair below it does
    not build a mask (nor is traced where one span holds the sequence:
    the kernels' text is what a process start pays for)."""
    if not causal:
        body(False)
        return
    pl.when(ki == qi)(lambda: body(True))
    if spans > 1:
        pl.when(ki < qi)(lambda: body(False))


def _scores_t(k, q, rows: slice, cols: slice, masked: bool,
              sm_scale: Optional[float]):
    """The transposed score tile ``k @ q^T`` [keys, queries] in float32;
    ``sm_scale`` None where an operand came scaled.  ``masked``: the tile
    of a diagonal span pair whose first query is ``rows.start`` and first
    key ``cols.start`` loses what lies above the diagonal."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if sm_scale is not None:
        st = st * sm_scale
    if masked:
        ahead = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                 - jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
        st = jnp.where(ahead >= cols.start - rows.start, st, NEG_INF)
    return st


def _last_span(causal: bool, qi):
    """The last kv span a q span needs, under grid (bh, q span, kv span)."""
    return qi if causal else pl.num_programs(2) - 1


# --------------------------------------------------------------------------- #
# Forward                                                                     #
# --------------------------------------------------------------------------- #

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                sm_scale: float, causal: bool, spans: int,
                block_q: int, block_k: int, span_pair=None):
    """``span_pair``: the step's ``(qi, ki)`` where the caller read them
    (the interpreter has no ``program_id`` inside a ``pl.when``)."""
    span_q, span_k = q_ref.shape[1], k_ref.shape[1]
    qi, ki = span_pair or (pl.program_id(1), pl.program_id(2))
    fold = _scale_is_exact(sm_scale)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(diagonal: bool):
        pairs = _tile_pairs(span_q, span_k, block_q, block_k, diagonal)
        vt = v_ref[0].T                                     # [d, span_k]
        for rows, strips in _strips(pairs, 0, (block_q, block_k)):
            q = q_ref[0, rows, :]
            if fold:
                q = q * sm_scale
            st = [_scores_t(k_ref[0, cols, :], q, rows, cols, masked,
                            None if fold else sm_scale)
                  for cols, masked in strips]
            # ONE softmax update a q tile for all it sees of the span
            m = m_ref[:, rows]
            m_new = functools.reduce(
                jnp.maximum, [jnp.max(x, axis=0, keepdims=True) for x in st],
                m)
            alpha = jnp.exp(m - m_new)
            pt = [jnp.exp(x - m_new) for x in st]
            l_ref[:, rows] = l_ref[:, rows] * alpha + sum(
                jnp.sum(x, axis=0, keepdims=True) for x in pt)
            acc_ref[:, rows] = acc_ref[:, rows] * alpha + sum(
                jax.lax.dot(vt[:, cols], x.astype(vt.dtype),
                            preferred_element_type=jnp.float32)
                for x, (cols, _) in zip(pt, strips))
            m_ref[:, rows] = m_new

    _on_spans(causal, spans, qi, ki, body)

    @pl.when(ki == _last_span(causal, qi))
    def _():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).T.astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def _fwd_kernel_to_lens(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                        **static):
    """``_fwd_kernel`` under ``q_lens``: a query span that starts at or
    past its row's length (``lens_ref``, one a ``bh`` row in SMEM) does
    no arithmetic, and its output block and lse are written as ZEROS,
    once, never left as the buffers held them: whoever caches or reads a
    position past a prompt's end finds a finite value there."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    real = qi * q_ref.shape[1] < lens_ref[pl.program_id(0)]

    @pl.when(real)
    def _():
        _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, **static,
                    span_pair=(qi, ki))

    @pl.when(jnp.logical_not(real) & (ki == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        lse_ref[...] = jnp.zeros(lse_ref.shape, lse_ref.dtype)


def _specs(t: Tiling, d: int, causal: bool):
    """Block specs under grid (bh, q span, kv span): q-side operands,
    kv-side operands, [1, span_q] rows.  A kv span above the diagonal
    names the diagonal's span again, so nothing is fetched for it."""
    kv_span = (lambda i, j: jnp.minimum(j, i)) if causal else (lambda i, j: j)
    return (pl.BlockSpec((1, t.span_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, t.span_k, d),
                         lambda b, i, j: (b, kv_span(i, j), 0)),
            pl.BlockSpec((1, 1, t.span_q), lambda b, i, j: (b, 0, i)))


def _specs_to_lens(t: Tiling, d: int):
    """``_specs`` of a causal forward under ``q_lens`` (the lengths are
    the index maps' last argument): q in, k/v in, o out, lse out.  A
    query span past its row's length names what its row's last real
    span left resident, that span's q and the diagonal's keys and
    values, so nothing is fetched for it."""
    def last(b, lens):
        return jnp.maximum(lens[b] - 1, 0) // t.span_q

    def q_span(b, i, j, lens):
        return b, jnp.minimum(i, last(b, lens)), 0

    def kv_span(b, i, j, lens):
        return b, jnp.where(i > last(b, lens), last(b, lens),
                            jnp.minimum(j, i)), 0
    return (pl.BlockSpec((1, t.span_q, d), q_span),
            pl.BlockSpec((1, t.span_k, d), kv_span),
            pl.BlockSpec((1, t.span_q, d), lambda b, i, j, lens: (b, i, 0)),
            pl.BlockSpec((1, 1, t.span_q), lambda b, i, j, lens: (b, 0, i)))


_SEMANTICS = _CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q3, k3, v3, causal: bool, sm_scale: float, t: Tiling,
         interpret: bool, lens=None):
    """-> o [bh, q_len, d], lse [bh, 1, q_len] float32.  ``lens`` [bh]
    int32 (causal only; None: the call as it always was, operand for
    operand): each row's real length, a scalar-prefetch operand of
    ``_fwd_kernel_to_lens``."""
    bh, q_len, d = q3.shape
    kv_len = k3.shape[1]
    static = dict(sm_scale=sm_scale, causal=causal, spans=q_len // t.span_q,
                  block_q=t.block_q, block_k=t.block_k)
    grid = (bh, q_len // t.span_q, kv_len // t.span_k)
    scratch_shapes = [
        pltpu.VMEM((1, t.span_q), jnp.float32),    # m
        pltpu.VMEM((1, t.span_q), jnp.float32),    # l
        pltpu.VMEM((d, t.span_q), jnp.float32),    # o^T
    ]
    if lens is None:
        qspec, kspec, row = _specs(t, d, causal)
        kernel, operands = _fwd_kernel, (q3, k3, v3)
        call = dict(grid=grid, in_specs=[qspec, kspec, kspec],
                    out_specs=[qspec, row], scratch_shapes=scratch_shapes)
    else:
        qspec, kspec, ospec, row = _specs_to_lens(t, d)
        kernel, operands = _fwd_kernel_to_lens, (lens, q3, k3, v3)
        call = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[qspec, kspec, kspec], out_specs=[ospec, row],
            scratch_shapes=scratch_shapes))
    return pl.pallas_call(
        functools.partial(kernel, **static),
        out_shape=[jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, 1, q_len), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="flash_fwd",
        **call,
    )(*operands)


# --------------------------------------------------------------------------- #
# Backward                                                                    #
# --------------------------------------------------------------------------- #

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                sm_scale: float, causal: bool, spans: int,
                block_q: int, block_k: int):
    """Grid (bh, kv span, q span): the kv side is resident, dk and dv
    accumulate over a kv span's q spans; ``dq_acc`` [q spans, d, span_q]
    holds dq^T of the head's WHOLE q sequence across the kv spans."""
    span_k, span_q = k_ref.shape[1], q_ref.shape[1]
    ki, qi = pl.program_id(1), pl.program_id(2)
    # one q span (the training cell): a static index, no address arithmetic
    q_at = qi if dq_acc.shape[0] > 1 else 0
    fold = _scale_is_exact(sm_scale)

    @pl.when(qi == (ki if causal else 0))
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(ki == 0)
    def _():
        dq_acc[q_at] = jnp.zeros(dq_acc.shape[1:], jnp.float32)

    def body(diagonal: bool):
        pairs = _tile_pairs(span_q, span_k, block_q, block_k, diagonal)
        for cols, tiles in _strips(pairs, 1, (block_q, block_k)):
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            if fold:
                k = k * sm_scale
            kt = k.T                                        # [d, block_k]
            dk, dv = dk_acc[cols, :], dv_acc[cols, :]
            for rows, masked in tiles:
                q, do = q_ref[0, rows, :], do_ref[0, rows, :]
                st = _scores_t(k, q, rows, cols, masked,
                               None if fold else sm_scale)
                pt = jnp.exp(st - lse_ref[0, :, rows])
                dv = dv + jax.lax.dot(pt.astype(do.dtype), do,
                                      preferred_element_type=jnp.float32)
                dpt = jax.lax.dot_general(v, do, _NT,
                                          preferred_element_type=jnp.float32)
                dst = (pt * (dpt - delta_ref[0, :, rows])).astype(q.dtype)
                dk = dk + jax.lax.dot(dst, q,
                                      preferred_element_type=jnp.float32)
                dq_acc[q_at, :, rows] += jax.lax.dot(
                    kt, dst, preferred_element_type=jnp.float32)
            dk_acc[cols, :], dv_acc[cols, :] = dk, dv

    _on_spans(causal, spans, qi, ki, body)

    # every visit writes the q span's block from the sum so far: the last
    # one (the diagonal's under ``causal``, the last kv span's otherwise)
    # leaves the whole sum.  ds was left unscaled: dq = scale * ds @ k,
    # the scale in k already where it folds
    @pl.when(ki <= qi if causal else True)
    def _():
        dqt = dq_acc[q_at]
        if not fold:
            dqt = dqt * sm_scale
        dq_ref[0] = dqt.T.astype(dq_ref.dtype)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        # q is as it came: dk = scale * ds^T @ q
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, causal: bool, sm_scale: float,
         plan: FlashPlan, interpret: bool):
    bh, q_len, d = q3.shape
    kv_len = k3.shape[1]
    if not plan.fused:
        raise NotImplementedError(
            f"flash_bwd_dqkv keeps dq^T of a head's whole q sequence in "
            f"VMEM: {q_len} x {d} float32 is {4 * q_len * d} bytes, over "
            f"DQ_ACC_BYTES = {DQ_ACC_BYTES}")
    t = plan.bwd
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]                    # as lse
    # under grid (bh, kv span, q span) a pair above the diagonal names
    # the diagonal's q span, so nothing is fetched for it (nor written:
    # the diagonal's visit fills the dq block it names)
    q_span = (lambda i, j: jnp.maximum(i, j)) if causal else (lambda i, j: i)
    qspec = pl.BlockSpec((1, t.span_q, d),
                         lambda b, j, i: (b, q_span(i, j), 0))
    row = pl.BlockSpec((1, 1, t.span_q),
                       lambda b, j, i: (b, 0, q_span(i, j)))
    kspec = pl.BlockSpec((1, t.span_k, d), lambda b, j, i: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal,
                          spans=q_len // t.span_q,
                          block_q=t.block_q, block_k=t.block_k),
        grid=(bh, kv_len // t.span_k, q_len // t.span_q),
        in_specs=[qspec, kspec, kspec, qspec, row, row],
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[
            pltpu.VMEM((q_len // t.span_q, d, t.span_q), jnp.float32),  # dq^T
            pltpu.VMEM((t.span_k, d), jnp.float32),    # dk
            pltpu.VMEM((t.span_k, d), jnp.float32),    # dv
        ],
        # dq^T sums over the kv spans, dk and dv over the q spans
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dqkv",
    )(q3, k3, v3, do3, lse, delta)


# --------------------------------------------------------------------------- #
# custom-vjp wrapper                                                          #
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, sm_scale, plan, interpret):
    o, _ = _fwd(q3, k3, v3, causal, sm_scale, plan.fwd, interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, sm_scale, plan, interpret):
    o, lse = _fwd(q3, k3, v3, causal, sm_scale, plan.fwd, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, sm_scale, plan, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    return _bwd(q3, k3, v3, o3, lse, do3, causal, sm_scale, plan, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_to_lens(q3, k3, v3, lens, sm_scale, plan, interpret):
    """The causal forward alone, query spans past ``lens`` skipped."""
    o, _ = _fwd(q3, k3, v3, True, sm_scale, plan.fwd, interpret, lens)
    return o


def _no_gradient(*_):
    raise NotImplementedError(
        "flash_attention with q_lens is forward only (prefill): the "
        "backward kernels know nothing of the skipped query spans")


_flash_to_lens.defvjp(_no_gradient, _no_gradient)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    q_lens: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention on [B, S, H, D] / [B, Sk, H, D] inputs (heads equal;
    GQA expansion happens in ops.attention).  ``block_q`` / ``block_k``
    override ``plan_blocks`` (tests); compiled for the chip they must be
    multiples of 128 or the whole sequence.

    ``q_lens`` [B] int32 (causal, forward only): each row's REAL length.
    A query span (``plan_blocks``' ``span_q``) that starts at or past it
    is not computed and comes out as zeros; every position below the
    length comes out as without ``q_lens`` (its keys lie below it too).
    None is the call as it always was."""
    b, q_len, h, d = q.shape
    kv_len = k.shape[1]
    if causal and q_len != kv_len:
        raise ValueError(
            "causal flash attention requires q_len == kv_len (got "
            f"{q_len} vs {kv_len}); use ops.attention with q_offset for "
            "decode-style queries")
    if q_lens is not None and not causal:
        raise ValueError("q_lens skips query spans only: without the "
                         "causal mask a real query would still see the "
                         "keys past its row's length")
    scale = sm_scale if sm_scale is not None else d ** -0.5
    plan = plan_blocks(q_len, kv_len, causal, block_q, block_k, head_dim=d)
    if interpret is None:
        interpret = _interpret()

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    if q_lens is None:
        o3 = _flash(to3(q), to3(k), to3(v), causal, float(scale), plan,
                    bool(interpret))
    else:
        o3 = _flash_to_lens(to3(q), to3(k), to3(v),
                            jnp.repeat(q_lens.astype(jnp.int32), h),
                            float(scale), plan, bool(interpret))
    return o3.reshape(b, h, q_len, d).transpose(0, 2, 1, 3)
