"""Mixture-of-experts MLP with expert parallelism (Switch/Mixtral-style).

Expert parallelism is absent from the reference (SURVEY.md §2.6: "Expert
parallel (EP/MoE): absent").  TPU-first design: routing is a *dense*,
static-shape dispatch — top-k gating builds [tokens, experts, capacity]
one-hot dispatch/combine tensors and the expert FFNs run as one batched
einsum over the expert dimension.  Expert parameters carry the ``expert``
logical axis (sharded over the data axes by the default rule table,
ray_tpu/parallel/sharding.py), so under GSPMD the dispatch einsum lowers to
the expert all-to-all on ICI; no ragged host-side routing, everything stays
on the MXU with shapes known at compile time.

The router's load-balancing auxiliary loss (Switch Transformer eq. 4) is
exported via ``self.sow("intermediates", "moe_aux_loss", ...)``; the train
step collects and adds it (ray_tpu/train/step.py lm_loss_fn).
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.attention import backend_platform


class MoEMLP(nn.Module):
    """Drop-in SwiGLU MLP with ``n_experts`` experts and top-k routing.

    Input/output: [B, S, d_model].  Tokens overflowing an expert's capacity
    ``ceil(top_k * S * capacity_factor / n_experts)`` are dropped (their
    residual stream passes through unchanged), the standard static-shape
    TPU formulation.
    """

    n_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_jitter: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e, k = self.n_experts, self.top_k
        capacity = max(int(k * s * self.capacity_factor / e), 1)
        capacity = min(capacity, s * k)

        router = nn.DenseGeneral(
            e, axis=-1, use_bias=False, name="router",
            dtype=jnp.float32, param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)))
        logits = router(x.astype(jnp.float32))          # [B, S, E]
        if (self.router_jitter > 0.0 and not self.is_initializing()
                and self.has_rng("router")):
            # jitter only when the caller provides a "router" rng stream
            # (the default train step passes none — jitter then degrades to
            # deterministic routing instead of raising inside jit)
            noise = jax.random.uniform(
                self.make_rng("router"), logits.shape,
                minval=1.0 - self.router_jitter, maxval=1.0 + self.router_jitter)
            logits = logits * noise
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)   # [B, S, K]
        gate_vals = gate_vals / jnp.clip(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        # Position of each (token, slot) in its expert's queue, in
        # slot-major order so a token's first choice wins capacity first.
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # [B,S,K,E]
        slot_major = onehot.transpose(0, 2, 1, 3).reshape(b, k * s, e)
        pos = jnp.cumsum(slot_major, axis=1) - 1.0                 # [B,KS,E]
        pos = (pos * slot_major).sum(-1).reshape(b, k, s).transpose(0, 2, 1)
        pos = pos.astype(jnp.int32)
        within_cap = pos < capacity                                # [B, S, K]

        keep = onehot * within_cap[..., None]                      # [B,S,K,E]
        pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        # dispatch: [B, S, E, C]; combine adds the gate weights.
        dispatch = jnp.einsum("bske,bskc->bsec", keep, pos_onehot)
        combine = jnp.einsum("bsk,bske,bskc->bsec",
                             gate_vals, keep, pos_onehot)

        w_gate = self.param(
            "w_gate", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "expert_in", "expert_mlp")),
            (e, d, self.d_ff), self.param_dtype)
        w_up = self.param(
            "w_up", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "expert_in", "expert_mlp")),
            (e, d, self.d_ff), self.param_dtype)
        w_down = self.param(
            "w_down", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("expert", "expert_mlp", "expert_in")),
            (e, self.d_ff, d), self.param_dtype)

        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(self.dtype),
                               x.astype(self.dtype))
        gate_h = jnp.einsum("ebcd,edf->ebcf", expert_in,
                            w_gate.astype(self.dtype))
        up_h = jnp.einsum("ebcd,edf->ebcf", expert_in,
                          w_up.astype(self.dtype))
        expert_out = jnp.einsum("ebcf,efd->ebcd", nn.silu(gate_h) * up_h,
                                w_down.astype(self.dtype))
        y = jnp.einsum("bsec,ebcd->bsd", combine.astype(self.dtype),
                       expert_out)

        # Switch load-balancing loss: E * sum_e f_e * P_e, where f_e is the
        # fraction of tokens whose top-1 choice is e and P_e the mean router
        # probability for e.
        top1 = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
        f = jnp.mean(top1, axis=(0, 1))
        p = jnp.mean(probs, axis=(0, 1))
        aux = self.aux_loss_coef * e * jnp.sum(f * p)
        self.sow("intermediates", "moe_aux_loss", aux)
        return y.astype(x.dtype)


# Dropless experts ---------------------------------------------------------

# pairs (tokens x top_k) up to which each touched expert is read once for
# all rows and the gates pick; above it the pairs are sorted by expert and
# run as one grouped product a matrix.  The decode shape (33 rows, 198
# pairs) lies below: there the layer is bound by the experts' bytes, and
# XLA's TPU lowering of ``ragged_dot`` at so few rows is an all-experts
# product over the 198 pair rows; it becomes the grouped kernel from some
# hundreds of rows (PERF.md, PR 26).  Below it the TPU runs the
# ``moe_experts_decode`` kernel, which reads only the experts that live
# rows chose (PERF.md, PR 27); elsewhere every expert is multiplied
DENSE_PAIRS_MAX = 512
# the top-k that threshold was measured at.  What the small-batch
# formulation costs follows the ROWS (each touched expert is read once
# for all of them), so a batch of no more rows than that threshold is at
# top-6 takes it whatever its top-k: 65 decode rows at top-22 are 1,430
# pairs, and XLA's TPU lowering of their ``ragged_dot`` is an
# all-experts product over all 1,430 pair rows, 1 TFLOP a matrix a layer
# (tests/test_chip_compile.py, ISSUE 44)
DENSE_PAIRS_TOP_K = 6

# pairs above which the grouped formulation runs a chunk of tokens at a
# time: its sorted copies of the pairs' rows are ``[pairs, d]`` each (a
# prefill wave of 4 x 8192 tokens at top-6 is 197k pairs, 805 MB a copy
# at d 2048)
RAGGED_PAIRS_MAX = 1 << 17

# a layer that holds a SHARE of the router's experts runs its sorted
# pairs a slab of this many times the share's own pairs at a time
# (``held_slab``): room for a wave that sends this chip more than its
# share before a second slab has to run, in an ODD number of tiles of
# pair rows: XLA's TPU ``ragged_dot`` over a slab of 512, 1,024 or 1,536
# rows against 16 small groups took 20-30% longer than over 640-1,280 or
# 1,792 (jaxlib 0.9.0, libtpu 0.0.34 on a v5e; PERF.md section 6, PR 56:
# what room and tile were chosen from; the oddness is that compiler's,
# to be read again when it changes)
SLAB_HEADROOM = 1.25
SLAB_TILE = 256

# the kernel's double-buffered blocks of the three matrices may take this
# much VMEM before the expert width is tiled (a v5e core has 128 MiB;
# SmallThinker's expert is 11.8 MB, 23.6 MB double-buffered)
_KERNEL_WEIGHTS_VMEM = 40 << 20


# the collection an expert layer sows ``dropless_experts``' count of
# pair rows into, for a caller that makes it mutable (the engine's
# prefill programs where the model holds a share of its experts)
PAIR_ROWS = "moe_pair_rows"

# an expert's activation by name ("relu2": Nemotron's squared ReLU)
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
        "relu2": lambda h: jnp.square(jax.nn.relu(h))}


def route_top_k(logits: jax.Array, k: int, *, scoring: str = "softmax",
                bias=None, scale: float = 1.0):
    """``(gates [N, k] float32, experts [N, k] int32)`` of float32 router
    logits ``[N, E]``.  ``"softmax"``: the ``k`` largest, softmax over
    those (softmax over all, top-k, renormalised, gives the same
    numbers).  ``"sigmoid"``: scores ``s = sigmoid(logits)``; the choice
    is the ``k`` largest of ``s + bias`` (``bias [E]``, a selection bias
    that never reaches the gates); the gates are the chosen ``s``,
    renormalised to sum to one, times ``scale``."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        top, idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top, axis=-1)
        return (gates if scale == 1.0 else gates * scale), idx
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return top / (top.sum(-1, keepdims=True) + 1e-20) * scale, idx


def touched_experts(experts: jax.Array, live, n_experts: int):
    """``(ids [min(E, N * k)] int32, count [] int32)``: the experts that
    at least one row of ``live`` (``[N]`` bool; None: every row) chose in
    ``experts [N, k]``, ascending, padded by repeating the last one.
    ``count`` is what ``LLMEngine._expert_load`` counts for the layer."""
    n, k = experts.shape
    # an id outside 0..E-1 (a pair whose expert is held elsewhere)
    # matches nothing
    chosen = experts[..., None] == jnp.arange(n_experts)       # [N, k, E]
    if live is not None:
        chosen = chosen & live[:, None, None]
    hit = chosen.any(axis=(0, 1))                               # [E]
    count = hit.sum().astype(jnp.int32)
    # slot s holds the s-th touched id: a compare and a sum, no sort
    slot = jnp.arange(min(n_experts, n * k))[:, None]
    place = hit & (jnp.cumsum(hit) - 1 == slot)                 # [S, E]
    ids = (place * jnp.arange(n_experts)).sum(axis=1).astype(jnp.int32)
    return jnp.where(slot[:, 0] < count, ids, ids.max()), count


def small_batch(pairs: int, rows: int = None) -> bool:
    """Whether ``pairs`` (rows x top_k) take the formulation in which
    each touched expert is read once for all rows: few pairs, or few
    ``rows`` at any top-k (``DENSE_PAIRS_TOP_K``)."""
    return pairs <= DENSE_PAIRS_MAX or (
        rows is not None and rows <= DENSE_PAIRS_MAX // DENSE_PAIRS_TOP_K)


def expert_kernel_applies(pairs: int, d: int, f: int,
                          rows: int = None) -> bool:
    """Whether ``pairs`` (``rows`` x top_k) on STACKED experts of widths
    ``d``, ``f`` run the Pallas kernel: the small-batch formulation, on
    the TPU, at lane-aligned widths (a choice by shape and platform, as
    ``paged_attention`` makes it)."""
    return (small_batch(pairs, rows) and d % 128 == 0 and f % 128 == 0
            and backend_platform() == "tpu")


def _width_tile(d: int, f: int, itemsize: int) -> int:
    """The widest slice of an expert's ``f`` (a multiple of 128 that
    divides it) whose double-buffered blocks fit the VMEM budget."""
    tiles = [t for t in range(f, 0, -128) if f % t == 0]
    fits = [t for t in tiles
            if 2 * 3 * d * t * itemsize <= _KERNEL_WEIGHTS_VMEM]
    return (fits or tiles[-1:])[0]


def _experts_kernel(x, combine, ids, count, layer, w_gate, w_up, w_down,
                    fn) -> jax.Array:
    """Pallas TPU kernel of the small-pair formulation: grid over the
    slots of ``ids`` (and slices of the expert width), the block of each
    matrix picked by ``(layer, ids[slot])`` out of the STACKED weights
    ``[L, E, d, f]`` / ``[L, E, f, d]``, which stay whole in HBM.  Slots
    past ``count`` repeat the block before them, so nothing is fetched
    for them, and their arithmetic is skipped.  Rounding as the
    all-experts formulation: bf16 operands, float32 accumulation, the
    activation product rounded, scaled by the float32 gate, rounded for
    the down product, summed over experts in float32, rounded once.

    x [N, d] (N a multiple of 16); combine [N, E] float32; ids [S],
    count [1], layer [1] int32 (scalar-prefetch operands).  ``w_gate``
    None: an expert that is not gated, ``down(fn(up x))``, two matrices
    an expert."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    e, f = w_up.shape[1], w_up.shape[3]
    slots = ids.shape[0]
    tf = _width_tile(d, f, x.dtype.itemsize)
    nf = f // tf
    gated = w_gate is not None

    def kernel(ids_ref, count_ref, layer_ref, x_ref, c_ref, *refs):
        wg_ref = refs[0] if gated else None
        wu_ref, wd_ref, o_ref, acc_ref = refs[-4:]
        s, j = pl.program_id(0), pl.program_id(1)

        @pl.when((s == 0) & (j == 0))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(s < count_ref[0])
        def _():
            xv = x_ref[...]
            if gated:
                gate_h = jnp.dot(xv, wg_ref[...],
                                 preferred_element_type=jnp.float32)
            up_h = jnp.dot(xv, wu_ref[...],
                           preferred_element_type=jnp.float32)
            # this expert's column of the gates, picked by a lane mask
            lane = jax.lax.broadcasted_iota(jnp.int32, (n, e), 1)
            gate = jnp.sum(jnp.where(lane == ids_ref[s], c_ref[...], 0.0),
                           axis=1, keepdims=True)               # [N, 1]
            h = up_h.astype(xv.dtype).astype(jnp.float32)
            h = (fn(gate_h.astype(xv.dtype).astype(jnp.float32)) * h
                 if gated else fn(h))
            h = h.astype(xv.dtype).astype(jnp.float32) * gate
            acc_ref[...] += jnp.dot(h.astype(xv.dtype), wd_ref[...],
                                    preferred_element_type=jnp.float32)

        @pl.when((s == slots - 1) & (j == nf - 1))
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    def weights(down: bool):
        def index(s, j, ids, count, layer):
            # a slot past the count names the block fetched last
            j = jnp.where(s < count[0], j, nf - 1)
            return (layer[0], ids[s]) + ((j, 0) if down else (0, j))
        return pl.BlockSpec((None, None) + ((tf, d) if down else (d, tf)),
                            index)

    whole = lambda *_: (0, 0)                                   # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,                  # ids, count, layer
            grid=(slots, nf),
            in_specs=[pl.BlockSpec((n, d), whole),
                      pl.BlockSpec((n, e), whole),
                      *[weights(False)] * (1 + gated), weights(True)],
            out_specs=pl.BlockSpec((n, d), whole),
            scratch_shapes=[pltpu.VMEM((n, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_KERNEL_WEIGHTS_VMEM + (16 << 20)),
        name="moe_experts_decode",
    )(ids, count, layer, x, combine, *([w_gate] if gated else []), w_up,
      w_down)


def held_slab(pairs: int, share: float) -> int:
    """The pair rows the grouped products run over at a time where a
    layer holds ``share`` of the router's experts: a function of the
    ``pairs`` (tokens x top_k) and the share alone.  The share's pairs
    with ``SLAB_HEADROOM``, in an odd number of ``SLAB_TILE``s, not
    below the rows from which XLA's ``ragged_dot`` is the grouped
    product (``DENSE_PAIRS_MAX``), never more than the pairs; all of
    them where the layer holds every expert."""
    if share >= 1.0:
        return pairs
    tiles = max(-(-int(pairs * share * SLAB_HEADROOM) // SLAB_TILE),
                -(-DENSE_PAIRS_MAX // SLAB_TILE)) | 1
    return min(pairs, tiles * SLAB_TILE)


def runs_in_slabs(pairs: int, rows: int, share: float) -> bool:
    """Whether ``pairs`` (``rows`` x top_k) of a layer that holds
    ``share`` of its experts take the grouped formulation a slab at a
    time: not a small batch, and a slab smaller than the pairs."""
    return not small_batch(pairs, rows) and held_slab(pairs, share) < pairs


def _pair_rows(x, gates, rows, k, sizes, w_gate, w_up, w_down, fn):
    """The sorted pairs ``rows`` (ids ``token * k + choice``, by expert,
    ``sizes [E]`` of them an expert) through their experts, scaled by
    their gates: gather, the grouped products, the scaling.  ``[len(
    rows), d]``; a row behind the last group is undefined."""
    xs = jnp.take(x, rows // k, axis=0)
    up_h = jax.lax.ragged_dot(xs, w_up, sizes)
    h = (fn(jax.lax.ragged_dot(xs, w_gate, sizes)) * up_h
         if w_gate is not None else fn(up_h))
    out = jax.lax.ragged_dot(h, w_down, sizes)
    return out * jnp.take(gates.reshape(-1), rows)[:, None].astype(out.dtype)


def _add_rows(y, tokens, rows):
    """``y [N, d]`` float32 with ``rows [S, d]`` added to its rows
    ``tokens [S]`` (a token may occur more than once), as a 0/1
    selection product: exact products, float32 sums.  Its work grows
    with ``N * S``; on a v5e it still costs no more than a float32
    scatter-add of the same rows at the widest call a cell makes (8,192
    tokens, a slab of 5,376: 3,551 against 3,648 us for the expert
    section) and 3-20% less below that (PERF.md section 6, PR 56, the
    review round); a call of more tokens would want it tiled."""
    mine = jnp.arange(y.shape[0], dtype=tokens.dtype)[:, None] == tokens
    return y + jnp.dot(mine.astype(rows.dtype), rows,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


@jax.custom_vjp
def _no_gradient(operands):
    """``operands`` as they are, for a computation that has no
    reverse-mode gradient: asked for one, it says why (JAX's own error
    names a ``while_loop``, not the layer that emitted it)."""
    return operands


def _no_gradient_fwd(operands):
    raise NotImplementedError(
        "dropless_experts(share < 1): a layer that holds a share of its "
        "experts (DroplessMoE.held) runs its pairs a slab at a time under "
        "a traced trip count, which has no reverse-mode gradient; a layer "
        "that is trained holds every expert (held=None: one pass, no loop)")


_no_gradient.defvjp(_no_gradient_fwd, lambda *_: None)


@functools.partial(jax.jit, static_argnames=("k", "slab", "act"))
def _in_slabs(x, gates, w_gate, w_up, w_down, order, sizes, layer, *,
              k: int, slab: int, act: str):
    """``(sum [N, d], slabs run)``: the sorted pairs ``order`` (``sizes
    [E]`` held ones an expert, the others behind them) through their
    experts a slab of ``slab`` rows at a time, the ``ceil(held pairs /
    slab)`` slabs the held pairs fill in ONE loop body under that traced
    count; each slab's rows added to their tokens in float32, rounded
    once.  ``layer`` (or None): the weights are a stack's whole leaves,
    and the body picks the layer.  Jitted on its own so that a program
    of several expert layers of one shape traces and lowers this body
    once: traced an unrolled layer, it cost a replica ~0.2 s a layer a
    prefill program of set-up (PERF.md section 6, PR 56)."""
    n, d = x.shape
    pairs = order.shape[0]
    ends = jnp.cumsum(sizes)
    held_pairs = ends[-1]
    slabs = -(-held_pairs // slab)
    # whole slabs: the pad's rows lie behind every held pair
    order = jnp.pad(order, (0, -pairs % slab))

    def run_slab(i, y):
        lo = i * slab
        rows = jax.lax.dynamic_slice_in_dim(order, lo, slab)
        within = lambda a: jnp.clip(a, lo, lo + slab)   # noqa: E731
        ws = w_gate, w_up, w_down
        if layer is not None:
            ws = (w if w is None else w[layer] for w in ws)
        out = _pair_rows(x, gates, rows, k,
                         within(ends) - within(ends - sizes), *ws,
                         ACTS[act])
        # (a row behind the last held pair is undefined, not zero)
        out = jnp.where((lo + jnp.arange(slab) < held_pairs)[:, None],
                        out, 0)
        return _add_rows(y, rows // k, out)

    y = jax.lax.fori_loop(0, slabs, run_slab,
                          jnp.zeros((n, d), jnp.float32))
    return y.astype(x.dtype), slabs


def dropless_experts(x, gates, experts, w_gate, w_up, w_down, *,
                     act: str = "silu", live=None, layer=None,
                     partial: bool = False, share: float = 1.0):
    """``(sum, rows)``: ``sum_j gates[t, j] * down_e(act(gate_e x_t) *
    up_e x_t)`` with ``e = experts[t, j]``, for EVERY pair ``(t, j)``: no capacity, nothing
    dropped whatever the imbalance.  ``w_gate`` None: an expert that is
    not gated, ``down_e(act(up_e x_t))``, in every formulation; ``act``
    ``"relu2"`` is ``relu(.)^2``.

    x [N, d]; gates, experts [N, k]; w_gate, w_up [E, d, f]; w_down
    [E, f, d] (already in the compute dtype), or with ``layer`` (an int32
    scalar, traced or not) a layer stack's whole leaves ``[L, E, ...]``,
    of which layer ``layer`` is read (by the decode kernel in place; by
    the slab loop inside its body, so that a scanned stack's layer is
    not copied out to be carried into the loop).  Two formulations of the same sum,
    chosen by the static number of pairs and rows (``small_batch``).
    In a small batch the gates pick among experts that are each read
    once for all rows (``live [N]`` bool: only those rows count and the
    others come out zero; None: every row): of stacked weights, on the
    TPU at
    lane-aligned widths, the ``moe_experts_decode`` kernel reads the
    ``touched_experts`` in place; otherwise, and as the kernel's oracle,
    all experts are multiplied (a kernel fed a scan's slice of the
    stack would have XLA copy the layer's experts out first, and no
    gradient is defined through it: it is the decode path).  In a
    larger one, a sort by expert and three ``jax.lax.ragged_dot`` (on
    the TPU a
    grouped-matmul kernel that reads each touched expert once and
    computes only the pairs' rows; a row ``live`` leaves out sends its
    pairs to the id ``E`` below, so nothing is read or multiplied for
    it and it comes out zero; above ``RAGGED_PAIRS_MAX`` pairs, a chunk
    of tokens after the other).

    ``experts`` index the ``E`` experts of the weights GIVEN.  With
    ``partial`` an id may be ``E``: a pair whose expert is not among
    them (``DroplessMoE.held``: it is some other chip's), dropped before
    anything is read or multiplied for it (a scatter drops an index out
    of range; the sort puts such pairs behind every group).

    ``share`` (static; ``DroplessMoE`` states ``held / n_experts``): the
    part of the router's experts that the weights given are.  The
    grouped formulation runs the sorted pairs a slab of ``held_slab``
    rows at a time.  At share 1 that is one slab of all the pairs: one
    pass, no loop, differentiable.  Below it the pairs of experts held
    elsewhere sort behind every group, and only the ``ceil(held pairs /
    slab)`` slabs that held pairs fill are run, in one loop body under
    that traced count (no reverse-mode gradient: ``jax.grad`` raises and
    says so): the slab's rows are
    gathered, multiplied with each group's size clipped to the slab,
    scaled, and added to their tokens in float32, rounded once at the
    end (the one pass sums a token's rounded rows in the output dtype);
    nothing ``[N * k, d]`` wide exists.  No pair held: no slab runs and
    the sum is exactly zero.

    ``rows``: ``[pairs, pair rows run]`` int32, what the grouped
    products were given and what they ran over (slabs run x slab; the
    pairs themselves in one pass, zeros in a small batch: constants
    there, no equation of the program)."""
    n, d = x.shape
    e, f = w_up.shape[-3], w_up.shape[-1]
    k = experts.shape[1]
    fn = ACTS[act]
    gated = w_gate is not None
    if n * k > RAGGED_PAIRS_MAX and n % 2 == 0:
        halves = lambda a: a.reshape(2, n // 2, *a.shape[1:])  # noqa: E731
        y, rows = jax.lax.map(
            lambda xs: dropless_experts(*xs[:3], w_gate, w_up, w_down,
                                        act=act, live=xs[3], layer=layer,
                                        partial=partial, share=share),
            (halves(x), halves(gates), halves(experts),
             None if live is None else halves(live)))
        return y.reshape(n, d), rows.sum(axis=0)
    kernel = layer is not None and expert_kernel_applies(n * k, d, f, n)
    # a stack's layer is picked where it is read: by the kernel, inside
    # the slab loop's body, else here
    in_loop = (layer is not None and not kernel
               and runs_in_slabs(n * k, n, share))
    if layer is not None and not kernel and not in_loop:
        w_gate, w_up, w_down = (w if w is None else w[layer]
                                for w in (w_gate, w_up, w_down))
    if small_batch(n * k, n):
        if live is not None:
            gates = jnp.where(live[:, None], gates, 0.0)
        # combine[n, e]: the token's gate for expert e, 0 if not chosen
        combine = jnp.zeros((n, e), jnp.float32).at[
            jnp.arange(n)[:, None], experts].add(gates)
        if kernel:
            ids, count = touched_experts(experts, live, e)
            pad = (0, -n % 16), (0, 0)             # whole bf16 sublane tiles
            y = _experts_kernel(
                jnp.pad(x, pad), jnp.pad(combine, pad), ids,
                count.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
                w_gate, w_up, w_down, fn)[:n]
        else:
            up_h = jnp.einsum("nd,edf->enf", x, w_up)
            h = (fn(jnp.einsum("nd,edf->enf", x, w_gate)) * up_h if gated
                 else fn(up_h))
            h = h.astype(jnp.float32) * combine.T[:, :, None]
            y = jnp.einsum("enf,efd->nd", h.astype(x.dtype), w_down)
        return y, np.zeros((2,), np.int32)
    if live is not None:
        # a row that holds no request sends its pairs where ``partial``
        # sends another chip's: behind every group, nothing read for them
        experts, partial = jnp.where(live[:, None], experts, e), True
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)              # pairs by expert
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    pairs, slab = n * k, held_slab(n * k, share)
    if slab == pairs:                                   # one pass
        out = _pair_rows(x, gates, order, k, sizes, w_gate, w_up, w_down,
                         fn)                            # [N*k, d]
        if partial:      # rows behind the last group belong to no expert
            out = jnp.where((jnp.arange(pairs) < sizes.sum())[:, None],
                            out, 0)
        back = jnp.argsort(order)                       # pair -> row
        y = jnp.take(out, back, axis=0).reshape(n, k, d).sum(axis=1)
        return y, np.full((2,), pairs, np.int32)
    y, slabs = _in_slabs(*_no_gradient((x, gates, w_gate, w_up, w_down)),
                         order, sizes, layer if in_loop else None,
                         k=k, slab=slab, act=act)
    return y, jnp.stack([jnp.int32(pairs), slabs * slab])


class DroplessMoE(nn.Module):
    """Top-k expert layer that computes every (token, chosen expert)
    pair.  The router is a method of its own so that a block can route
    from another tensor than the experts read (``router_logits(h)`` with
    the attention's input, then ``__call__(m, logits)``); called with no
    logits it routes from its own input.  Sows ``expert_idx`` (``[B, S,
    k]`` int32) into ``intermediates`` for whoever counts the load.

    ``held`` (None: all): this layer holds the weights of ``held``
    experts, ids ``held_first .. held_first + held - 1`` of the
    ``n_experts`` the router scores, as one chip of an expert-parallel
    group does.  It routes over all ``n_experts`` and computes the pairs
    that chose an expert it holds; the others' terms are left out of its
    sum.  ``expert_idx`` then counts in the held experts' own numbering,
    ``held`` for a pair that went elsewhere.  The share ``held /
    n_experts`` is what ``dropless_experts`` sizes its slabs by; for a
    caller that makes the ``PAIR_ROWS`` collection mutable the layer
    sows ``rows`` there: ``[pairs, pair rows run]`` of the call."""

    d_model: int
    n_experts: int
    d_ff: int
    top_k: int = 2
    act: str = "silu"
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    scoring: str = "softmax"           # route_top_k
    route_scale: float = 1.0
    held: Optional[int] = None
    held_first: int = 0
    # False: an expert is down(act(up x)), two matrices and no w_gate
    gated: bool = True

    def setup(self):
        d, f = self.d_model, self.d_ff
        e = self.n_experts if self.held is None else self.held
        self.router = nn.DenseGeneral(
            self.n_experts, axis=-1, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype,
            # the TPU multiplies float32 in bf16 passes unless told not to
            precision=jax.lax.Precision.HIGHEST,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", None)))
        if self.scoring == "sigmoid":
            # drawn away from zero, so that folding it into the gates is
            # a different function (a checkpoint's is learned)
            self.select_bias = self.param(
                "e_score_correction_bias", nn.with_logical_partitioning(
                    nn.initializers.normal(0.1), (None,)),
                (self.n_experts,), jnp.float32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        self.w_gate = self.param(
            "w_gate", nn.with_logical_partitioning(
                init, ("expert", "expert_in", "expert_mlp")),
            (e, d, f), self.param_dtype) if self.gated else None
        self.w_up = self.param(
            "w_up", nn.with_logical_partitioning(
                init, ("expert", "expert_in", "expert_mlp")),
            (e, d, f), self.param_dtype)
        self.w_down = self.param(
            "w_down", nn.with_logical_partitioning(
                init, ("expert", "expert_mlp", "expert_in")),
            (e, f, d), self.param_dtype)

    def router_logits(self, h: jax.Array) -> jax.Array:
        """float32 logits ``[B, S, E]`` (float32 input, weights and
        accumulation: a near-tie of the k-th expert decides a whole
        expert's contribution)."""
        return self.router(h.astype(jnp.float32))

    def __call__(self, x: jax.Array, logits=None, live=None, stacked=None,
                 layer=None) -> jax.Array:
        """``live [B]`` bool: the rows that hold a request (None: all).
        ``stacked``: the whole ``(w_gate, w_up, w_down)`` leaves ``[L, E,
        ...]`` of the layer stack this layer is scanned in, and ``layer``
        its index there.  The decode kernel reads them in place; handed
        this layer's slice of the scan, a custom call would make XLA
        copy the layer's experts out first (PERF.md, PR 26), and so
        would the slab loop of a layer that holds a share (PR 56)."""
        b, s, d = x.shape
        if logits is None:
            logits = self.router_logits(x)
        gates, experts = route_top_k(
            logits.reshape(b * s, -1), self.top_k, scoring=self.scoring,
            bias=(self.select_bias if self.scoring == "sigmoid" else None),
            scale=self.route_scale)
        if self.held is not None:
            experts = experts - self.held_first
            here = (experts >= 0) & (experts < self.held)
            experts = jnp.where(here, experts, self.held)
            gates = jnp.where(here, gates, 0.0)
        self.sow("intermediates", "expert_idx",
                 experts.reshape(b, s, self.top_k))
        dt = self.dtype
        weights = self.w_gate, self.w_up, self.w_down
        pairs = b * s * self.top_k
        share = 1.0 if self.held is None else self.held / self.n_experts
        if (stacked is not None and layer is not None
                and stacked[1].dtype == dt
                and (expert_kernel_applies(pairs, d, self.d_ff, b * s)
                     or runs_in_slabs(pairs, b * s, share))):
            weights = stacked
        else:           # this layer's own slice, as the scan hands it over
            layer = None
        if live is not None:
            live = jnp.repeat(live, s)
        y, rows = dropless_experts(
            x.reshape(b * s, d).astype(dt), gates, experts,
            *(w if w is None else w.astype(dt) for w in weights),
            act=self.act, live=live,
            layer=layer, partial=self.held is not None, share=share)
        if self.is_mutable_collection(PAIR_ROWS):
            self.sow(PAIR_ROWS, "rows", jnp.asarray(rows))
        return y.reshape(b, s, d).astype(x.dtype)


class LatentMoE(nn.Module):
    """Routed experts that work in a LATENT narrower than the model
    (Nemotron 3's LatentMoE), around ``DroplessMoE``::

        out = W_out sum_e gate_e down_e(act(up_e (W_in x))) + shared(x)

    The router and the shared expert (not gated either, ``shared_d_ff``
    wide) read the full ``d_model``; only the routed experts see the
    ``latent`` width, so an expert's two matrices are ``latent x d_ff``.
    ``held`` / ``held_first`` are ``DroplessMoE``'s: this layer holds
    that share of the routed experts, and the projections, the router
    and the shared expert whole."""

    d_model: int
    latent: int
    n_experts: int
    d_ff: int
    shared_d_ff: int
    top_k: int = 2
    act: str = "relu2"
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    scoring: str = "sigmoid"
    route_scale: float = 1.0
    held: Optional[int] = None
    held_first: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, live=None, stacked=None,
                 layer=None) -> jax.Array:
        """``stacked``, ``layer``: ``DroplessMoE.__call__``'s (the routed
        experts' whole leaves and this layer's index among them)."""
        def dense(features, axes, name):
            return nn.DenseGeneral(
                features, axis=-1, use_bias=False, name=name,
                dtype=self.dtype, param_dtype=self.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), axes))
        fn = ACTS[self.act]
        moe = DroplessMoE(self.latent, self.n_experts, self.d_ff,
                          top_k=self.top_k, act=self.act, dtype=self.dtype,
                          param_dtype=self.param_dtype, scoring=self.scoring,
                          route_scale=self.route_scale, held=self.held,
                          held_first=self.held_first, gated=False,
                          name="moe")
        with jax.named_scope("latent_moe"):
            # one buffer of the layer's input for its three readers
            # (router, latent projection, shared expert).  Without the
            # barrier the TPU compiler recomputes the norm inside each
            # reader's fusion, here from a residual sum that took the
            # previous mixer's float32 accumulator and there from its
            # rounded copy, so the router read activations 2.4e-3 of
            # its logits away from any array of the program (PERF.md
            # section 6, PR 44); sown, for a check of the router's
            # arithmetic against what it read
            x = jax.lax.optimization_barrier(x)
            self.sow("intermediates", "router_in", x)
            logits = moe.router_logits(x)
            routed = moe(dense(self.latent, ("embed", "mlp"), "w_in")(x),
                         logits, live, stacked, layer)
            shared = dense(self.d_model, ("mlp", "embed"), "shared_down")(
                fn(dense(self.shared_d_ff, ("embed", "mlp"), "shared_up")(x)))
            return dense(self.d_model, ("mlp", "embed"), "w_out")(
                routed) + shared
