"""Flagship decoder-only transformer (LLaMA-style: RMSNorm/RoPE/SwiGLU/GQA).

TPU-first design notes:
  - params carry *logical* axis names via ``nn.with_logical_partitioning``;
    ray_tpu.parallel.sharding maps them to mesh axes (DP/FSDP/TP/SP from one
    rule table — the capability matrix the reference lacks, SURVEY.md §2.6).
  - layers run under ``lax.scan`` (one compiled block, O(1) compile time in
    depth) with optional remat (HBM <-> FLOPs trade).
  - attention dispatches to the Pallas flash kernel, plain XLA einsum, or
    ring attention over the mesh's ``context`` axis for long sequences.
  - decode uses a KV cache held in the flax ``cache`` collection
    (``decode`` is a module attribute, so it stays static under remat/scan);
    the paged KV pool is one stacked leaf there, carried through the layer
    scan and updated in place (never a scanned variable).
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh

from ray_tpu.models.configs import (MAMBA_KINDS, POOL_KINDS, STATE_KINDS,
                                    TransformerConfig)
from ray_tpu.ops.attention import repeat_kv, xla_attention
from ray_tpu.ops.layers import apply_rope, rope_frequencies
from ray_tpu.ops.moe import PAIR_ROWS
from ray_tpu.parallel.sharding import LOGICAL_RULES, ShardingRules, with_sharding


def _dense(features, logical_axes, name=None, use_bias=False,
           param_dtype=jnp.float32, dtype=jnp.bfloat16):
    return nn.DenseGeneral(
        features=features, axis=-1, use_bias=use_bias, name=name,
        dtype=dtype, param_dtype=param_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.lecun_normal(), logical_axes))


# "no window" as a window: larger than any context, small enough that
# ``position - NO_WINDOW`` stays inside int32
NO_WINDOW = 1 << 30


def window_mask(q_pos, k_pos, window=None):
    """Boolean ``[B, 1, Q, K]``: key ``j`` is visible to the query at
    absolute position ``p`` iff ``j <= p`` and, under a ``window`` (a
    scalar, traced or not), ``j > p - window``.  ``q_pos`` [B, Q],
    ``k_pos`` [K]."""
    q_pos = q_pos[:, None, :, None]
    k_pos = k_pos[None, None, None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return mask


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale",
                           nn.with_logical_partitioning(
                               nn.initializers.ones_init(), ("norm",)),
                           (x.shape[-1],), jnp.float32)
        from ray_tpu.ops.layers import rms_norm
        return rms_norm(x, scale, self.eps)


def _branch(cfg: TransformerConfig, y):
    """A residual branch's output as the stream takes it: times the
    model's ``residual_multiplier`` where it states one."""
    r = cfg.residual_multiplier
    return y if r == 1.0 else y * jnp.asarray(r, y.dtype)


class MLP(nn.Module):
    """SwiGLU feed-forward (shared by the decoder, encoder, and T5).

    FORMAT BREAK (round 1): extracting this submodule renamed parameter
    paths ``block_i/w_gate`` -> ``block_i/mlp/w_gate`` (same under scan).
    Checkpoints written before that refactor need their keys re-nested
    under ``mlp/`` to load; no shim is kept since no pre-break checkpoint
    left the repo."""

    cfg: TransformerConfig
    d_ff: Optional[int] = None             # None -> cfg.d_ff

    @nn.compact
    def __call__(self, y):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        gate = _dense(d_ff, ("embed", "mlp"), "w_gate",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(y)
        up = _dense(d_ff, ("embed", "mlp"), "w_up",
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype)(y)
        return _dense(cfg.d_model, ("mlp", "embed"), "w_down",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
            nn.silu(gate) * up)


def _rematted(block_cls, cfg: TransformerConfig):
    """``block_cls`` checkpointed under ``cfg.remat_policy``."""
    # the remat ladder, least to most memory (scaling-book recipe:
    # pick the most-saving policy that still fits HBM):
    #   nothing    — full recompute (fits 1B on one 16 GiB chip)
    #   block_outs — save each block's attn/mlp outputs (named
    #                checkpoints below): residual stream reconstructs
    #                without re-running attention, ~1.5 GiB at 1B/b8
    #   dots       — save only no-batch-dim dot outputs (tiny)
    #   dots_all   — save every dot output (max memory, min recompute)
    policies = {
        "nothing": None,
        "block_outs": jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_out"),
        "dots": jax.checkpoint_policies
        .dots_with_no_batch_dims_saveable,
        "dots_all": jax.checkpoint_policies.dots_saveable,
    }
    if cfg.remat_policy not in policies:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; "
            f"choose one of {sorted(policies)}")
    return nn.remat(block_cls, prevent_cse=False,
                    policy=policies[cfg.remat_policy])


def stack_layers(block_cls, cfg: TransformerConfig, ctor_kwargs, x,
                 call_args, *, remat: Optional[bool] = None,
                 cache: bool = False, name: str = "blocks",
                 n_layers: Optional[int] = None, carry=None,
                 first_layer: int = 0):
    """Apply ``n_layers`` (default cfg.n_layers) blocks under the repo's
    standard stacking: remat per cfg.remat (HBM<->FLOPs), one
    ``lax.scan``'d block when cfg.scan_layers (O(1) compile time in
    depth). Must be called from a parent's ``@nn.compact`` __call__.
    Blocks are invoked ``mdl(x, *call_args)``.

    ``carry`` is state that rides the stack beside ``x`` as LOOP-CARRIED
    state, never as a scanned (sliced-in, stacked-out) variable: the
    paged KV pool ``[n_layers, pages, ...]``, which every block updates
    in place at its own layer index.  Blocks are then invoked
    ``mdl(x, *call_args, carry, layer)`` and return ``(x, carry)``;
    ``layer`` is a scanned ``arange`` under scan, a Python int unrolled.
    Returns ``(x, carry)`` when a carry is given.

    Where the layers differ in kind (``cfg.layers_differ``: per-layer
    rotation and window layouts) ONE block is still scanned and every
    block is handed its layer index (``first_layer`` + its place in this
    stack), with or without a carry: the kinds differ only in two
    scalars a layer (rotate or not, window or none), which the block
    looks up by that index, so the parameters stay one stacked tree, the
    compile time stays O(1) in depth, and a model cut in depth keeps
    its published layouts.  Scanning one period instead would need a
    second, nested parameter tree for the same arithmetic.
    """
    if n_layers is None:
        n_layers = cfg.n_layers
    if remat is None:
        remat = cfg.remat
    if remat:
        block_cls = _rematted(block_cls, cfg)
    if cfg.scan_layers:
        variable_axes = {"params": 0, "intermediates": 0, PAIR_ROWS: 0}
        if cache:
            variable_axes["cache"] = 0
        layers = jnp.arange(first_layer, first_layer + n_layers,
                            dtype=jnp.int32)
        if carry is None and not cfg.layers_differ:
            init, layers = x, None

            def body(mdl, x, _):
                return mdl(x, *call_args), None
        elif carry is None:
            init = x

            def body(mdl, x, layer):
                return mdl(x, *call_args, None, layer), None
        else:
            init = (x, carry)

            def body(mdl, x_carry, layer):
                return mdl(x_carry[0], *call_args, x_carry[1], layer), None
        # no broadcast variable and no broadcast output here, so flax
        # need not trace the body a second time, under partial
        # evaluation, to check them constant: that pass was half of
        # every program's tracing, and walks a loop inside the body
        # (``Block._chunked``) twice more.  ``init`` keeps it: the pass
        # draws on the parameters' rng, so without it a seed would give
        # other weights than it did
        stacked = block_cls(cfg, **ctor_kwargs, name=name)
        out, _ = nn.scan(
            body,
            variable_axes=variable_axes,
            split_rngs={"params": True},
            length=n_layers,
            metadata_params={nn.PARTITION_NAME: None},
            check_constancy_invariants=stacked.is_initializing(),
        )(stacked, init, layers)
        return out
    for i in range(n_layers):
        block = block_cls(cfg, **ctor_kwargs, name=f"{name[:-1]}_{i}")
        if carry is not None:
            x, carry = block(x, *call_args, carry, first_layer + i)
        elif cfg.layers_differ:
            x = block(x, *call_args, None, first_layer + i)
        else:
            x = block(x, *call_args)
    return x if carry is None else (x, carry)


# float32 scores ``[rows, heads, T, T]`` of a prefill wave beyond this
# many bytes are computed a group of rows at a time (8 prompts of 2048
# tokens at 30 heads are 4 GB of them, beside 12 GB of weights and pool),
# and where ONE row's pass it (32 heads at 8192 tokens are 8.6 GB) a
# block of its queries at a time
_PREFILL_SCORE_BYTES = 1 << 30


def _prefill_attend(q, k, v, sm_scale=None, lengths=None):
    """Causal attention of each row of a prefill wave over itself.
    ``xla_attention`` as it is where the wave's scores fit
    ``_PREFILL_SCORE_BYTES``; else the same over the largest groups of
    rows that do, one after the other (``lax.map``); and where ONE row's
    do not, no scores at all: on the TPU the flash kernel
    (``ops/flash_attention.py``: the masked half skipped; values
    narrower than the keys ride zero-padded to their width), elsewhere
    blocks of that row's queries, each against the keys up to its causal
    edge (unrolled: a block's key span is static).  Materialised, such a
    row's softmax fusions ran at a twentieth of the chip's bandwidth
    (PERF.md section 6, PR 37).

    ``lengths`` [B] (None: every position is real): each row's real
    length.  The flash kernel leaves out the query spans past it, whose
    output is zeros; the other paths compute the right-pad too, finite
    values that nobody reads.  A real position's output is the same
    either way: under the causal mask it sees no key past itself."""
    b, t, h, d = q.shape
    row_bytes = 4 * h * t * t
    if row_bytes > _PREFILL_SCORE_BYTES:
        from ray_tpu.ops.attention import attention, resolve_impl
        if resolve_impl("auto") == "flash" and t % 128 == 0:
            wide = jnp.pad(v, [(0, 0)] * 3 + [(0, d - v.shape[-1])])
            return attention(q, k, wide, causal=True, sm_scale=sm_scale,
                             impl="flash", q_lens=lengths)[..., :v.shape[-1]]
        blocks = 2
        while row_bytes // blocks > _PREFILL_SCORE_BYTES and t % (
                2 * blocks) == 0:
            blocks *= 2
        bq = t // blocks
        pos = jnp.arange(t)

        def row(qkv):
            q, k, v = (a[None] for a in qkv)
            return jnp.concatenate([
                xla_attention(
                    q[:, lo:lo + bq], k[:, :lo + bq], v[:, :lo + bq],
                    causal=False, sm_scale=sm_scale,
                    mask=(pos[None, :lo + bq] <= pos[lo:lo + bq, None]
                          )[None, None])
                for lo in range(0, t, bq)], axis=1)[0]
        return jax.lax.map(row, (q, k, v))
    group = max(1, min(b, _PREFILL_SCORE_BYTES // row_bytes))
    while b % group:
        group -= 1
    if group == b:
        return xla_attention(q, k, v, causal=True, sm_scale=sm_scale)
    split = lambda a: a.reshape(b // group, group, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(
        lambda qkv: xla_attention(*qkv, causal=True, sm_scale=sm_scale),
        (split(q), split(k), split(v)))
    return out.reshape(b, *out.shape[2:])


class Attention(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    # prefix-cache suffix prefill (serve/llm_engine.py): T > 1 windows
    # may start at nonzero positions over pages already holding a cached
    # prompt prefix, so attention must read back through the pool
    # instead of being causal over its own window only
    prefix_attend: bool = False
    # a paged call of T > 1 positions a row is a decode step that
    # verifies a draft (serve/llm_engine.py), not a prompt: written and
    # read by the paged decode call, each query over the pool up to its
    # own position
    verify: bool = False
    # a block outside the stack (``MTPModule``'s): global attention
    # without rotation, whatever the layouts say of its pool layer
    lone: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 pool=None, layer=None, live=None, lengths=None, part=None):
        """``pool`` (paged decode, serve/llm_engine.py paged mode): the
        model's ONE stacked KV page pool ``[layers, pages, kv_heads,
        page_size, 2*head_dim]`` (GPT declares it; see
        ops/paged_attention.py) and this block's ``layer`` index into
        it.  Returns ``(out, pool)`` then: the pool is passed through,
        updated in place, never sliced.  ``live`` [rows] bool (``Block``
        makes it): the rows that hold a request, the only ones a decode
        step reads pages for.  ``lengths`` [rows] (a prompt wave): the
        rows' real lengths (``_prefill_attend``).

        ``part`` (``Block``'s chunked prefill, which runs the token-wise
        work a chunk of positions at a time and the attention whole):
        ``"project"`` maps ``x`` to the rotated ``(q, k, v)``,
        ``"attend"`` takes that triple as ``x`` and returns ``(heads'
        outputs [B, T, heads * head_dim], pool)``, ``"output"`` maps
        those to the layer's output.  None is the three in a row."""
        if part == "output":
            return self._output(x)
        qkv = x if part == "attend" else self._project(
            x, cos, sin, positions, layer)
        if part == "project":
            return qkv
        out, pool_out = self._attend(*qkv, positions, block_tables, pool,
                                     layer, live, lengths)
        if part == "attend":
            return out, pool_out
        out = self._output(out)
        return out if pool is None else (out, pool_out)

    def projected(self, b: int, t: int):
        """The shapes of what ``part="project"`` makes of ``[b, t, d]``."""
        cfg = self.cfg
        return tuple(jax.ShapeDtypeStruct((b, t, h, cfg.head_dim), cfg.dtype)
                     for h in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads))

    def _project(self, x, cos, sin, positions, layer):
        cfg = self.cfg
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = _dense((h, hd), ("embed", "heads", "head_dim"), "wq",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        k = _dense((kvh, hd), ("embed", "kv", "head_dim"), "wk",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        v = _dense((kvh, hd), ("embed", "kv", "head_dim"), "wv",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        if cfg.qk_norm_per_head:    # over each head's head_dim alone
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        elif cfg.qk_norm:     # over the whole projection, heads unsplit
            q = RMSNorm(cfg.norm_eps, name="q_norm")(
                q.reshape(*q.shape[:2], h * hd)).reshape(q.shape)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(
                k.reshape(*k.shape[:2], kvh * hd)).reshape(k.shape)
        rotate, _ = self._layer_kind(layer)
        if cfg.rope_theta is None or self.lone:
            pass                            # no layer rotates, or not this
        elif rotate is None:
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        else:       # this layer's entry of rope_layout: 0 -> no positions
            q = jnp.where(rotate, apply_rope(q, cos, sin, positions), q)
            k = jnp.where(rotate, apply_rope(k, cos, sin, positions), k)
        return q, k, v

    def _attend(self, q, k, v, positions, block_tables, pool, layer, live,
                lengths):
        _, window = self._layer_kind(layer)
        if pool is not None:
            out, pool = self._decode_attend_paged(
                q, k, v, positions, block_tables, pool, layer, window,
                live, lengths)
        elif self.decode:
            out = self._decode_attend(q, k, v, positions, window)
        else:
            out = self._train_attend(q, k, v, window)
        return out.reshape(*out.shape[:2], -1), pool

    def _output(self, out):
        cfg = self.cfg
        return _dense(cfg.d_model, ("heads_embed", "embed"), "wo",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)

    def _layer_kind(self, layer):
        """``(rotate, window)`` of layer ``layer`` (an int, or the traced
        index of the layer scan): ``rotate`` is None where every layer
        rotates, else this layer's entry of ``cfg.rope_layout`` as a
        bool; ``window`` is None where the model has no window, else
        this layer's (``NO_WINDOW`` for a global layer of a model whose
        other layers have one).  A config without layouts never reads
        ``layer``."""
        cfg = self.cfg
        rotate = window = None
        if self.lone:
            return rotate, window
        if cfg.rope_layout is not None:
            rotate = jnp.asarray(cfg.rope_layout, jnp.bool_)[layer]
        if cfg.window_layout is not None:
            window = jnp.where(
                jnp.asarray(cfg.window_layout, jnp.bool_)[layer],
                jnp.int32(cfg.sliding_window), jnp.int32(NO_WINDOW))
        elif cfg.sliding_window:
            window = jnp.int32(cfg.sliding_window)
        return rotate, window

    def _window_over(self, window, span: int):
        """``window`` where a key span of ``span`` positions (static) can
        reach past it, else None: where the window cannot bite, the
        program lowers as a model without a window does."""
        w = self.cfg.sliding_window
        return window if w and span > w else None

    def _window_attend(self, q, k, v, window):
        """Causal attention of a span over itself under a window; no
        kernel here takes one (ROADMAP B2): masked einsum."""
        pos = jnp.arange(q.shape[1])
        return xla_attention(q, k, v, causal=False,
                             sm_scale=self.cfg.attention_multiplier,
                             mask=window_mask(pos[None, :], pos, window))

    def _train_attend(self, q, k, v, window=None):
        cfg = self.cfg
        window = self._window_over(window, q.shape[1])
        if window is not None:
            if cfg.attention_impl in ("ring", "ulysses"):
                raise ValueError("a sliding window over a sequence longer "
                                 "than it needs attention_impl xla/auto")
            return self._window_attend(q, k, v, window)
        impl = cfg.attention_impl
        if impl in ("ring", "ulysses"):
            if self.mesh is None:
                raise ValueError(f"{impl} attention requires a mesh")
            if impl == "ring":
                # the ring accumulator needs matched head counts
                if cfg.n_kv_heads != cfg.n_heads:
                    k = repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
                    v = repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
                from ray_tpu.ops.ring_attention import ring_attention
                return ring_attention(q, k, v, mesh=self.mesh, causal=True,
                                      sm_scale=cfg.attention_multiplier)
            # ulysses handles GQA natively (KV all-to-all stays at kv_heads);
            # only expand when kv_heads doesn't divide the context axis
            ctx = self.mesh.shape.get("context", 1)
            if cfg.n_kv_heads % ctx:
                k = repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
                v = repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
            from ray_tpu.ops.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, mesh=self.mesh, causal=True,
                                     sm_scale=cfg.attention_multiplier)
        from ray_tpu.ops.attention import attention, resolve_impl
        impl = resolve_impl(impl)
        if impl == "flash" and self.mesh is not None \
                and self.mesh.size > 1:
            return self._kernel_attend_sharded(q, k, v)
        return attention(q, k, v, causal=True, impl=impl,
                         sm_scale=cfg.attention_multiplier)

    def _kernel_attend_sharded(self, q, k, v):
        """A Pallas (Mosaic) kernel is one opaque custom call: GSPMD
        cannot partition it, and lowering it with sharded operands on a
        multi-chip TPU mesh is refused outright.  So the kernel runs per
        shard under shard_map on the activations' own layout — batch
        over the data axes, heads over ``tensor`` — which needs no
        communication: attention is independent per (batch, head)."""
        import functools

        from jax import shard_map

        from ray_tpu.ops.attention import attention
        from ray_tpu.parallel.sharding import logical_spec
        cfg, mesh = self.cfg, self.mesh
        if mesh.shape.get("context", 1) > 1:
            raise ValueError(
                "attention_impl='flash' needs the whole sequence on "
                "each shard; with context parallelism use 'ring' or "
                "'ulysses'")
        if cfg.n_kv_heads != cfg.n_heads:
            # equal head counts, so q/k/v share one head sharding
            k = repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
            v = repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
        spec = logical_spec(("batch", None, "heads", None), mesh,
                            self.rules)
        fn = functools.partial(attention, causal=True, impl="flash",
                               sm_scale=cfg.attention_multiplier)
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    def _decode_attend(self, q, k, v, positions, window=None):
        """Write K/V into the cache at per-row positions and attend under
        a position mask.

        ``positions`` [B, T] are the absolute positions of the q tokens;
        each row's T positions must be contiguous starting at
        ``positions[:, 0]`` but ROWS MAY SIT AT DIFFERENT OFFSETS — the
        property continuous batching needs (serve/llm_engine.py: each
        batch row is an independent request mid-decode).  The uniform
        case (Generator.generate) is positions = full(pos); when
        positions is None the scalar cache index drives a uniform step,
        the pre-slot behavior."""
        cfg = self.cfg
        b = q.shape[0]
        ck = self.variable("cache", "k", jnp.zeros,
                           (b, cfg.max_seq_len, cfg.n_kv_heads, cfg.head_dim),
                           cfg.dtype)
        cv = self.variable("cache", "v", jnp.zeros,
                           (b, cfg.max_seq_len, cfg.n_kv_heads, cfg.head_dim),
                           cfg.dtype)
        idx = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
        if self.is_initializing():
            # shape-only pass: leave the cache untouched (flax convention —
            # a cache write here would leave index advanced before decoding)
            return xla_attention(q, k, v, causal=True)
        if positions is None:
            positions = idx.value + jnp.broadcast_to(
                jnp.arange(q.shape[1]), (b, q.shape[1]))

        def _row_write(cache_row, new_row, p):
            return jax.lax.dynamic_update_slice(cache_row, new_row, (p, 0, 0))

        write_pos = positions[:, 0]
        ck.value = jax.vmap(_row_write)(ck.value, k.astype(cfg.dtype),
                                        write_pos)
        cv.value = jax.vmap(_row_write)(cv.value, v.astype(cfg.dtype),
                                        write_pos)
        idx.value = jnp.max(positions) + 1
        # key j is visible to the query at absolute position p iff j <= p
        # (equivalent to the old q_offset causal mask when rows align)
        # and, under a window, j > p - window
        mask = window_mask(positions, jnp.arange(cfg.max_seq_len),
                           self._window_over(window, cfg.max_seq_len))
        return xla_attention(q, ck.value, cv.value, causal=False, mask=mask,
                             sm_scale=cfg.attention_multiplier)

    def _decode_attend_paged(self, q, k, v, positions, block_tables,
                             pool, layer, window=None, live=None,
                             lengths=None):
        """Paged-pool decode: write this call's K/V into the rows' pages
        of layer ``layer``, then attend over only the occupied pages
        (ops/paged_attention.py).  Returns ``(out, pool)``.

        ``pool`` is the whole stacked pool and stays whole: the write is
        ``rows x T x kv_heads x 2*head_dim`` values at ``[layer, page, :,
        offset]`` (in place, the buffer being loop-carried and donated),
        the reads name ``[layer, page]``.  Nothing here may slice a
        layer out of it — that is a copy of the layer's pool a layer.

        ``positions`` [B, T] as in ``_decode_attend``; ``block_tables``
        [B, max_pages] maps each row's logical page (position // page_size)
        to a physical page in the shared pool.  Prompt prefill is the
        T > 1 case (windows start on a page boundary: write_kv_pages):
        the window is causal over itself (a prompt attends only to its
        own prefix), so no pool read is needed — the write below is the
        whole cache interaction.  What it writes past a real prompt's
        end (``lengths``, a wave's real lengths) is the right-pad's
        rows where they were computed and ZEROS where ``Block`` and the
        flash kernel skipped them: finite either way, which is what the
        decode kernel needs of a row it multiplies by a masked
        probability of 0, and overwritten by decode writes before any
        length makes it visible.

        ``window`` (this layer's, traced; None without one): decode
        reads only the pages that hold the last ``window`` positions,
        and a T > 1 span longer than the window is masked by it.
        ``live`` [rows] bool (None: every row): decode reads no page of
        a row it leaves out, whose output is zeros.

        Who writes: a prompt's rows (T > 1) ``write_kv_pages``; a decode
        step's (T == 1, or under ``verify`` the T positions of a step
        that verifies a draft) ``paged_attention`` itself, handed the
        new rows:
        on the chip the kernel puts each row it keeps into its own tail
        page and a row it does not keep writes NOTHING; the XLA form
        scatters every row first, a dead one into the scratch page its
        table names, which nothing reads.
        """
        cfg = self.cfg
        if self.is_initializing():
            return xla_attention(q, k, v, causal=True), pool
        if positions is None or block_tables is None:
            raise ValueError("paged decode requires positions and "
                             "block_tables")
        from ray_tpu.ops.paged_attention import (gather_kv_pages,
                                                 paged_attention,
                                                 write_kv_pages)
        kv = jnp.concatenate([k, v], axis=-1)
        scale = cfg.attention_multiplier      # None: head_dim^-1/2
        if q.shape[1] == 1:
            out, pool = paged_attention(
                q[:, 0], pool, block_tables, positions[:, 0] + 1,
                new_rows=kv[:, 0], layer=layer, live=live, sm_scale=scale,
                window=self._window_over(
                    window, block_tables.shape[1] * pool.shape[3]))
            return out[:, None], pool
        if self.verify:
            # T positions a row in one decode call: the same kernel at T
            # queries, every row of them written by it, each query
            # masked (and windowed) from its own position
            return paged_attention(
                q, pool, block_tables, positions[:, -1] + 1, new_rows=kv,
                layer=layer, live=live, sm_scale=scale,
                window=self._window_over(
                    window, block_tables.shape[1] * pool.shape[3]))
        pool = write_kv_pages(pool, kv, block_tables, positions, layer=layer)
        if not self.prefix_attend:
            window = self._window_over(window, q.shape[1])
            if window is not None:
                return self._window_attend(q, k, v, window), pool
            return _prefill_attend(q, k, v, scale, lengths), pool
        # suffix prefill: the window's keys are NOT the whole story —
        # leading block-table entries hold a cached prompt prefix, so
        # gather the row's full logical span back out of the pool and
        # mask by absolute position (key j visible iff j <= query p).
        # Unallocated table entries point at scratch page 0, whose
        # garbage sits past every real query position.  Offset-0
        # windows reduce to the causal case (their own keys were just
        # scattered), so this path is correct for any offset.
        kvfull = gather_kv_pages(pool, block_tables, layer=layer)
        mask = window_mask(positions, jnp.arange(kvfull.shape[1]),
                           self._window_over(window, kvfull.shape[1]))
        return xla_attention(q, kvfull[..., :cfg.head_dim],
                             kvfull[..., cfg.head_dim:], causal=False,
                             sm_scale=scale, mask=mask), pool


def _rope_interleaved(x, cos, sin, positions=None):
    """Rotation of the pairs ``(2j, 2j+1)``: the even dims are moved in
    front of the odd ones and the halves rotated (``apply_rope``).  The
    result is a fixed permutation of the in-place rotation's, the same
    for queries and keys, so every score is what it would be."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                      cos, sin, positions)


def _widen(a, width: int):
    """``a`` padded with zeros to ``width`` along its last axis."""
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])])


def absorbed_attention(cfg: TransformerConfig, q, wkv_b, pool, block_tables,
                       lengths, *, layer=0, live=None, new_rows=None):
    """One query a row over the latent rows its pages hold, ABSORBED
    (``LatentAttention``): ``q [B, heads, dn + dr]`` rotated already,
    ``wkv_b [r, heads, dn + dv]``, ``pool`` the stacked latent pool ->
    ``[B, heads, dv]``.  ``q_lat = q_nope Wuk^T``; the paged kernel takes
    ``[q_lat | q_rope | 0]`` against whole rows and returns ``sum_j p_j
    c_j``; ``Wuv`` maps that to the head's value.  With ``new_rows [B,
    r + dr]`` (the step's latent rows, which ``lengths`` counts) they
    are written first, ``[row | 0]``, and the result is ``(out, pool)``
    (``paged_attention``)."""
    from ray_tpu.ops.paged_attention import paged_attention
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_lat = jnp.einsum("bhd,rhd->bhr", q[..., :dn], wkv_b[..., :dn])
    got = paged_attention(
        _widen(jnp.concatenate([q_lat, q[..., dn:]], -1), pool.shape[-1]),
        pool, block_tables, lengths, layer=layer, live=live,
        sm_scale=cfg.head_dim ** -0.5, v_width=r,
        new_rows=(None if new_rows is None
                  else _widen(new_rows, pool.shape[-1])[:, None]))
    if new_rows is None:
        return jnp.einsum("bhr,rhd->bhd", got, wkv_b[..., dn:])
    return jnp.einsum("bhr,rhd->bhd", got[0], wkv_b[..., dn:]), got[1]


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA, no ``q_lora``).
    With ``y`` the block's normed input::

        q   = y Wq            heads x [q_nope dn | q_rope dr]
        ckv = y Wkva          [c' r | k_rope' dr]
        c   = RMSNorm(c');  k_rope = rope(k_rope'), ONE for all heads
                            (no rotation of either where the model has
                            none, ``rope_theta`` None: Kimi Linear's)
        [k_nope dn | v dv] = c Wkvb   a head
        s = (q_nope . k_nope + rope(q_rope) . k_rope) / sqrt(dn + dr)

    What a token leaves in the cache is ``[c | k_rope]`` (``r + dr``
    values, after the norm and the rotation), the same bytes for keys
    and values; nothing per head is stored.  Three paths:

    - plain (training, a whole forward, the dense-cache decode of
      ``Generator``) and paged PREFILL over a wave's own keys: EXPANDED,
      keys and values a head from ``c Wkvb``;
    - paged DECODE: ABSORBED.  ``q_lat = q_nope Wuk^T`` (``Wkvb`` split a
      head into ``Wuk [r, dn]`` and ``Wuv [r, dv]``), scores ``[q_lat |
      q_rope] . row``, ``o_lat = sum_j p_j c_j`` (the row's first ``r``),
      ``o = o_lat Wuv``: the kernel sees ``heads`` queries on ONE KV head
      whose keys are the whole row and whose values are its first ``r``.

    The pool's row is ``cfg.cache_row_width`` wide, zeros past ``r +
    dr`` (ops/paged_attention.py layout note)."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    prefix_attend: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 pool=None, layer=None, live=None, lengths=None, part=None):
        """``part`` as in ``Attention.__call__``; what ``"project"`` hands
        ``"attend"`` is ``(q, k, v, row)``: the prompt's keys and values
        a head EXPANDED already (token-wise work as the projections are)
        and the latent row ``[c | k_rope]`` the pages take."""
        cfg = self.cfg
        h, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        if part == "output":
            return self._output(x)
        if part == "attend":
            q, k, v, row = x
            pool = self._write_rows(row, positions, block_tables, pool, layer)
            out = _prefill_attend(q, k, v, lengths=lengths)
            return out.reshape(*out.shape[:2], h * dv), pool
        q = _dense((h, dn + dr), ("embed", "heads", "head_dim"), "wq",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        ckv = _dense(r + dr, ("embed", "head_dim"), "wkv_a",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        wkv_b = self.param(
            "wkv_b", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2)),
                ("head_dim", "heads", "head_dim")),
            (r, h, dn + dv), cfg.param_dtype).astype(cfg.dtype)
        rope = _rope_interleaved if cfg.rope_interleave else apply_rope
        c = RMSNorm(cfg.norm_eps, name="kv_norm")(ckv[..., :r])
        if cos is None:     # no layer rotates (cfg.rope_theta None): the
            k_rope = ckv[..., r:]       # row's last dr are plain key dims
        else:
            k_rope = rope(ckv[..., None, r:], cos, sin, positions)[:, :, 0]
            q = jnp.concatenate(
                [q[..., :dn], rope(q[..., dn:], cos, sin, positions)], -1)
        if part == "project":
            return (q, *self._expand(c, k_rope, wkv_b),
                    jnp.concatenate([c, k_rope], -1))

        if pool is not None:
            out, pool = self._decode_attend_paged(
                q, c, k_rope, wkv_b, positions, block_tables, pool, layer,
                live, lengths)
        elif self.decode:
            out = self._attend_cached(q, c, k_rope, wkv_b, positions)
        else:
            out = xla_attention(q, *self._expand(c, k_rope, wkv_b),
                                causal=True)
        out = self._output(out.reshape(*out.shape[:2], h * dv))
        return out if pool is None else (out, pool)

    def _output(self, out):
        cfg = self.cfg
        return _dense(cfg.d_model, ("heads_embed", "embed"), "wo",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)

    def projected(self, b: int, t: int):
        """The shapes of what ``part="project"`` makes of ``[b, t, d]``."""
        cfg = self.cfg
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        return tuple(jax.ShapeDtypeStruct((b, t) + tail, cfg.dtype)
                     for tail in ((cfg.n_heads, dn + dr),
                                  (cfg.n_heads, dn + dr),
                                  (cfg.n_heads, cfg.v_head_dim),
                                  (cfg.kv_lora_rank + dr,)))

    def _expand(self, c, k_rope, wkv_b):
        """``(k [B, T, heads, dn + dr], v [B, T, heads, dv])`` of latent
        rows ``c [B, T, r]``, ``k_rope [B, T, dr]``."""
        dn = self.cfg.qk_nope_head_dim
        kv = jnp.einsum("btr,rhe->bthe", c, wkv_b)
        k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                                  kv.shape[:3] + k_rope.shape[-1:])
        return jnp.concatenate([kv[..., :dn], k_rope], -1), kv[..., dn:]

    def _attend_cached(self, q, c, k_rope, wkv_b, positions):
        """``Attention._decode_attend`` for latent rows: a dense cache
        ``[B, max_seq_len, r + dr]`` written at per-row positions, every
        cached row expanded a call (the plain path of ``Generator``;
        serving decodes absorbed from the paged pool)."""
        cfg = self.cfg
        b, r = q.shape[0], cfg.kv_lora_rank
        cache = self.variable("cache", "latent", jnp.zeros,
                              (b, cfg.max_seq_len, r + k_rope.shape[-1]),
                              cfg.dtype)
        idx = self.variable("cache", "index", lambda: jnp.zeros((), jnp.int32))
        if self.is_initializing():
            return xla_attention(q, *self._expand(c, k_rope, wkv_b),
                                 causal=True)
        if positions is None:
            positions = idx.value + jnp.broadcast_to(
                jnp.arange(q.shape[1]), (b, q.shape[1]))
        row = jnp.concatenate([c, k_rope], -1).astype(cfg.dtype)
        cache.value = jax.vmap(
            lambda rows, new, p: jax.lax.dynamic_update_slice(
                rows, new, (p, 0)))(cache.value, row, positions[:, 0])
        idx.value = jnp.max(positions) + 1
        k, v = self._expand(cache.value[..., :r], cache.value[..., r:],
                            wkv_b)
        return xla_attention(
            q, k, v, causal=False,
            mask=window_mask(positions, jnp.arange(cfg.max_seq_len)))

    def _check_paged(self, positions, block_tables):
        """What either writer of latent rows needs of a paged call."""
        if positions is None or block_tables is None:
            raise ValueError("paged decode requires positions and "
                             "block_tables")
        if self.prefix_attend:
            raise ValueError(
                "suffix prefill over a latent pool: LatentAttention has "
                "no path that gathers a cached prefix's latent rows and "
                "expands them (kv_b) beside the window's own")

    def _write_rows(self, row, positions, block_tables, pool, layer):
        """``pool`` with the latent rows ``row [B, T, r + dr]`` written,
        ``[row | 0]``, to the rows' pages of layer ``layer``."""
        self._check_paged(positions, block_tables)
        from ray_tpu.ops.paged_attention import write_kv_pages
        return write_kv_pages(
            pool, _widen(row, pool.shape[-1])[:, :, None], block_tables,
            positions, layer=layer)

    def _decode_attend_paged(self, q, c, k_rope, wkv_b, positions,
                             block_tables, pool, layer, live, lengths=None):
        """``Attention._decode_attend_paged`` for latent rows: this
        call's rows ``[c | k_rope | 0]`` go into the rows' pages of layer
        ``layer``; a prompt (``T > 1``) is written by ``_write_rows`` and
        attends EXPANDED over its own keys (no pool read), a decode step
        ABSORBED over the occupied pages, its row written by the paged
        call itself (a row ``live`` leaves out: as in ``Attention``).
        Returns ``(out [B, T, heads, dv], pool)``."""
        cfg = self.cfg
        if self.is_initializing():
            return xla_attention(q, *self._expand(c, k_rope, wkv_b),
                                 causal=True), pool
        row = jnp.concatenate([c, k_rope], -1)
        if q.shape[1] > 1:
            pool = self._write_rows(row, positions, block_tables, pool, layer)
            return _prefill_attend(q, *self._expand(c, k_rope, wkv_b),
                                   lengths=lengths), pool
        self._check_paged(positions, block_tables)
        out, pool = absorbed_attention(
            cfg, q[:, 0], wkv_b, pool, block_tables, positions[:, 0] + 1,
            layer=layer, live=live, new_rows=row[:, 0])
        return out[:, None], pool


# positions of a prompt wave that ``Block`` runs its token-wise work on
# at a time (the flash kernel's span, ops/flash_attention.py SPAN): a
# wave longer than one chunk computes as many as its longest real
# prompt reaches
PREFILL_CHUNK = 1024


def _in_chunks(span: int) -> bool:
    """A span of whole chunks, more than one."""
    return span > PREFILL_CHUNK and span % PREFILL_CHUNK == 0


def prefill_positions(span: int, longest: int) -> int:
    """Of a prompt wave's ``span`` positions a row, those a model told
    the rows' real lengths does its token-wise work on, ``longest``
    being the longest of them: the chunks up to it where the span runs
    in chunks, else every one."""
    if not _in_chunks(span):
        return span
    return min(span, -(-longest // PREFILL_CHUNK) * PREFILL_CHUNK)


def _over_chunks(fn, operands, chunks, out, summed=None):
    """``fn`` over chunks ``0 .. chunks - 1`` (a traced count) of the
    position axis, in ONE compiled loop body: ``operands`` is a pytree
    of ``[B, T, ...]`` arrays, of which ``fn`` is handed ``[B,
    PREFILL_CHUNK, ...]`` slices and returns a pytree of such slices;
    ``out`` is that pytree as the ``[B, T, ...]`` shapes of the whole
    span (given, not traced for: ``fn`` is traced once, as the one pass
    it replaces was).  Returns the outputs, ZEROS where no chunk ran.
    With ``summed`` (a shape) ``fn`` returns ``(slices, an array of that
    shape)`` and the loop carries the arrays' sum over the chunks run
    beside the outputs: ``(outputs, sum)``."""
    def body(i, carry):
        out, total = carry
        part = fn(jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(
                a, i * PREFILL_CHUNK, PREFILL_CHUNK, axis=1),
            operands))
        if summed is not None:
            part, count = part
            total = total + count
        return jax.tree.map(
            lambda whole, part: jax.lax.dynamic_update_slice_in_dim(
                whole, part, i * PREFILL_CHUNK, axis=1),
            out, part), total
    out, total = jax.lax.fori_loop(
        0, chunks, body,
        (jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), out),
         None if summed is None else jnp.zeros(summed.shape, summed.dtype)))
    return out if summed is None else (out, total)


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    prefix_attend: bool = False
    # a layer of the dense prefix of a model whose other layers have
    # experts (cfg.first_dense_layers): its feed-forward is SwiGLU(d_ff)
    dense_ffn: bool = False
    verify: bool = False                   # see Attention
    # the block of ``MTPModule``, outside the stack: its attention is
    # global and does not rotate, and ``moe_stacked`` is a stack of its
    # own experts alone (index 0, whatever its pool ``layer``)
    lone: bool = False
    # a kda layer: the mixer is ``KimiDeltaAttention``, what rides as
    # ``pool`` the recurrent leaves ``(state, conv)`` and ``layer`` its
    # index into them, ``entries`` the rows' state entries
    kda: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 moe_stacked=None, lengths=None, pool=None, layer=None,
                 part=None, entries=None, moe_index=None):
        """With a paged KV ``pool`` (see Attention) returns ``(x, pool)``:
        the shape ``stack_layers`` carries it through the stack in.
        ``moe_stacked``: the layer stack's whole dropless expert leaves
        (GPT hands them down in decode; see ``DroplessMoE.__call__``).
        ``layer`` counts every layer (the pool's index); the stacked
        experts' index starts after the dense prefix, or is
        ``moe_index`` where the caller states it (a period's).

        ``lengths`` [B] (a prompt wave into the pool; None: every
        position is real): the rows' real lengths.  A wave longer than
        one ``PREFILL_CHUNK`` then does its token-wise work, everything
        but the attention itself, on the chunks its longest real prompt
        reaches and on no other (``_chunked``); any other call is one
        pass over the whole span.  ``part`` is ``_chunked``'s own: the
        section ``"before"`` or ``"after"`` the attention alone."""
        cfg = self.cfg
        experts = cfg.moe_experts > 0 and not self.dense_ffn
        # a row whose table starts at the scratch page holds no request
        # (serve/llm_engine.py): neither the decode attention kernel nor
        # the expert kernel reads anything for it
        live = None if block_tables is None else block_tables[:, 0] != 0
        # cfg.post_norm: each sub-layer reads x and its OUTPUT is normed
        attn_norm = RMSNorm(cfg.norm_eps, name="attn_norm")
        mlp_norm = RMSNorm(cfg.norm_eps, name="mlp_norm")
        moe = None
        if experts and cfg.moe_dropless:
            from ray_tpu.ops.moe import DroplessMoE
            moe = DroplessMoE(cfg.d_model, cfg.moe_experts, cfg.moe_d_ff,
                              top_k=cfg.moe_top_k, act=cfg.moe_act,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              scoring=cfg.moe_scoring,
                              route_scale=cfg.moe_route_scale,
                              held=cfg.moe_experts_held,
                              held_first=cfg.moe_held_first, name="moe")
        if self.kda:
            attn = KimiDeltaAttention(cfg, name="attn")
        elif cfg.kv_lora_rank:
            attn = LatentAttention(cfg, self.mesh, self.rules, self.decode,
                                   self.prefix_attend, name="attn")
        else:
            attn = Attention(cfg, self.mesh, self.rules, self.decode,
                             self.prefix_attend, self.verify, self.lone,
                             name="attn")
        routes_before = moe is not None and cfg.moe_router_pre_attn

        def before(x, positions, project: bool):
            """-> (the attention's input, or with ``project`` what its
            ``"project"`` part makes of it; the router's logits where
            it reads that input)."""
            y = x if cfg.post_norm else attn_norm(x)
            router_logits = moe.router_logits(y) if routes_before else None
            if project:
                y = attn(y, cos, sin, positions, layer=layer, part="project")
            return y, router_logits

        def after(x, y, router_logits, output: bool):
            """The block's output from its input ``x`` and the
            attention's output ``y`` (with ``output`` the heads', which
            the attention's ``"output"`` part maps first)."""
            if output:
                y = attn(y, cos, sin, part="output")
            if cfg.post_norm:
                y = attn_norm(y)
            y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
            x = x + _branch(cfg, y)
            y = x if cfg.post_norm else mlp_norm(x)
            if moe is not None:
                if cfg.layers_of("kda"):
                    # one buffer of the experts' input for its readers
                    # (router, experts, shared expert), as ``LatentMoE``
                    # keeps one: in this model's programs the TPU
                    # compiler otherwise recomputes the norm inside the
                    # router's fusion in float32, and the router reads
                    # activations 1.2e-3 of its logits away from the
                    # bfloat16 ones the experts read (PERF.md section 6,
                    # PR 55)
                    y = jax.lax.optimization_barrier(y)
                routed = moe(y, router_logits, live, moe_stacked,
                             moe_index if moe_index is not None
                             else None if layer is None else 0 if self.lone
                             else layer - cfg.first_dense_layers)
                if cfg.moe_shared_experts:    # every token, beside the sum
                    routed = routed + MLP(
                        cfg, cfg.moe_shared_experts * cfg.moe_d_ff,
                        name="shared_mlp")(y)
                y = routed
            elif experts:
                from ray_tpu.ops.moe import MoEMLP
                y = MoEMLP(cfg.moe_experts, cfg.moe_d_ff,
                           top_k=cfg.moe_top_k,
                           capacity_factor=cfg.moe_capacity_factor,
                           aux_loss_coef=cfg.moe_aux_coef,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           name="moe")(y)
            else:
                y = MLP(cfg, name="mlp")(y)
            if cfg.post_norm:
                y = mlp_norm(y)
            y = jax.ad_checkpoint.checkpoint_name(y, "mlp_out")
            return x + _branch(cfg, y)

        if part == "before":
            return before(x, positions, True)
        if part == "after":
            return after(*x, True)
        if (pool is not None and lengths is not None
                and _in_chunks(x.shape[1]) and not self.kda
                and not self.is_initializing()):
            return self._chunked(
                attn, routes_before, x, cos, sin, positions, block_tables,
                lengths, pool, layer, live,
                moe is not None and self.is_mutable_collection(PAIR_ROWS))
        y, router_logits = before(x, positions, False)
        if self.kda:
            y = attn(y, lengths, entries, pool, layer, live)
        else:
            y = attn(y, cos, sin, positions, block_tables, pool, layer, live,
                     lengths)
        if pool is not None:
            y, pool = y
        x = after(x, y, router_logits, False)
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        return x if pool is None else (x, pool)

    def _chunked(self, attn, routes_before: bool, x, cos, sin, positions,
                 block_tables, lengths, pool, layer, live, counted: bool):
        """A prompt wave of more than one chunk a row, told its real
        ``lengths``: the sections before and after the attention run a
        chunk of positions at a time, all rows at once, over ``ceil(max(
        lengths) / PREFILL_CHUNK)`` chunks, each section ONE loop body
        (a pure function of this layer's parameters: this block, unbound,
        applied to a chunk).  Between them the page write and the
        attention see the whole span, the pool outside both loops.  What
        no chunk computed is ZEROS: the projections of a skipped
        position (so the rows the pages get for it, finite), the heads'
        outputs there where the flash kernel skipped them too, and the
        block's output, which is the next block's input there.  A real
        position gets what one pass over the span gives it: the work is
        per token, experts included (a chunk's sort and grouped products
        hand a token what the wave's would).  ``counted``: the expert
        layer's count of pair rows (``DroplessMoE``, for a caller that
        asked for ``PAIR_ROWS``) rides the second loop and is sown from
        here."""
        cfg = self.cfg
        chunks = -(-jnp.max(lengths) // PREFILL_CHUNK)
        params = {"params": self.variables["params"]}
        block = Block(cfg, self.mesh, self.rules, self.decode,
                      self.prefix_attend, self.dense_ffn, self.verify,
                      self.lone, parent=None)
        projected, router_logits = _over_chunks(
            lambda cut: block.apply(params, cut[0], cos, sin, cut[1],
                                    layer=layer, part="before"),
            (x, positions), chunks,
            (attn.projected(*x.shape[:2]),
             jax.ShapeDtypeStruct(x.shape[:2] + (cfg.moe_experts,),
                                  jnp.float32) if routes_before else None))
        y, pool = attn(projected, cos, sin, positions, block_tables, pool,
                       layer, live, lengths, part="attend")

        def after(cut):
            out = block.apply(params, cut, cos, sin,
                              block_tables=block_tables, layer=layer,
                              part="after",
                              mutable=[PAIR_ROWS] if counted else False)
            return (out[0], sum(jax.tree.leaves(out[1]))) if counted else out
        x = _over_chunks(
            after, (x, y, router_logits), chunks,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((2,), jnp.int32) if counted else None)
        if counted:
            x, rows = x
            self.sow(PAIR_ROWS, "rows", rows)
        return x, pool


def _a_log_init(key, shape, dtype):
    """log of a decay rate uniform in [1, 16) (the family's own
    initialisation, as ``_dt_bias_init``): with it a head forgets over
    tens to thousands of tokens, as a trained one does."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """Inverse softplus of a step log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class LinearAttention(nn.Module):
    """Gated DeltaNet mixer (ops/gated_delta.py): projections, a causal
    depthwise convolution with SiLU over ``[q; k; v]``, unit-length
    ``q`` and ``k`` a head, the gated delta rule, a per-head RMSNorm of
    the output gated by ``SiLU(z)``, the output projection.

    What it remembers of a sequence is ``rec = (state, conv)``: the
    model's two stacked leaves ``[linear layers, entries, dk, heads *
    dv]`` float32 and ``[linear layers, entries, .., 128]`` (the
    convolution's last ``taps - 1`` inputs, flat in rows of 128 lanes: a
    minor pair ``[3, channels]`` would be tiled out to 16 rows, and one
    of ``[entries, 3 * channels]`` could not be merged with the layer
    axis without a copy), addressed ``[layer, entry]`` and passed
    through whole, as the KV pool is.  Without ``rec`` a call is
    a whole sequence from an empty state (training, a plain forward).
    With it: ``T > 1`` is a prompt from an empty state whose final
    state and tail are written to the rows' ``entries`` (``lengths``:
    each row's real length; right-pad past it is not absorbed), ``T ==
    1`` one decode step on the rows' entries, dead rows untouched."""

    cfg: TransformerConfig
    # ``KimiDeltaAttention``'s gates (see there)
    channelwise: bool = False

    @nn.compact
    def __call__(self, x, lengths=None, entries=None, rec=None, layer=None,
                 live=None):
        from ray_tpu.ops import gated_delta as gd
        cfg = self.cfg
        hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps = cfg.linear_conv_kernel
        b, t, _ = x.shape
        f32 = jnp.float32

        def proj(features, name, axes=("embed", "heads", "head_dim")):
            return _dense(features, axes[:1 + len(features)], name,
                          dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        u = jnp.concatenate([
            proj((hk, dk), "wq").reshape(b, t, hk * dk),
            proj((hk, dk), "wk").reshape(b, t, hk * dk),
            proj((hv, dv), "wv").reshape(b, t, hv * dv)], axis=-1)
        if self.channelwise:
            def low_rank(features, name):
                """``x`` through ``d_model -> linear_gate_rank ->
                features``, no bias and nothing between the two."""
                return _dense(
                    features, ("head_dim", "heads", "head_dim"), name + "_b",
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
                    proj((cfg.linear_gate_rank,), name + "_a",
                         ("embed", "head_dim")))
            z, a = low_rank((hv, dv), "wg"), low_rank((hk, dk), "wf")
            bw = proj((hv,), "wb")
        else:
            z = proj((hv, dv), "wg")
            a, bw = proj((hv,), "wa"), proj((hv,), "wb")
        vec = lambda init: nn.with_logical_partitioning(    # noqa: E731
            init, ("norm",))
        conv_w = self.param(
            "conv", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (None, "norm")),
            (taps, u.shape[-1]), cfg.param_dtype).astype(f32)
        a_log = self.param("A_log", vec(_a_log_init), (hv,), f32)
        dt_bias = self.param(
            "dt_bias", vec(_dt_bias_init) if not self.channelwise
            else nn.with_logical_partitioning(_dt_bias_init,
                                              ("heads", "norm")),
            (hk, dk) if self.channelwise else (hv,), f32)
        o_scale = self.param("o_norm", vec(nn.initializers.ones_init()),
                             (dv,), f32)

        decode_step = rec is not None and t == 1 \
            and not self.is_initializing()
        if decode_step:
            state, conv = rec
            flat, at = gd.flat_rows(conv, layer, entries)
            tail = flat[at].reshape(b, taps - 1, -1)
            window = jnp.concatenate([tail, u.astype(conv.dtype)], 1)
            if live is not None:      # a dead row keeps its tail
                tail = jnp.where(live[:, None, None], window[:, 1:], tail)
            else:
                tail = window[:, 1:]
            rec = (state, flat.at[at].set(
                tail.reshape((b,) + conv.shape[2:])).reshape(conv.shape))
        else:       # zeros before the sequence's start
            window = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        # the convolution as the sum of its taps' shifted products
        c = sum(window[:, j:j + t].astype(f32) * conv_w[j]
                for j in range(taps))
        c = nn.silu(c).astype(cfg.dtype)
        q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)
        q, k = (y.reshape(b, t, hk, dk).astype(f32) for y in (q, k))
        unit = lambda y: y * jax.lax.rsqrt(                 # noqa: E731
            jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        q = (unit(q) * dk ** -0.5).astype(cfg.dtype)
        k = unit(k).astype(cfg.dtype)
        if hv != hk:                  # value heads share a key head
            q, k = (jnp.repeat(y, hv // hk, axis=2) for y in (q, k))
        v = v.reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(bw.astype(f32)) * (
            2.0 if cfg.linear_allow_neg_eigval else 1.0)
        if self.channelwise:          # [b, t, heads, dk]: a channel its own
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                a.astype(f32) + dt_bias)
        else:
            g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(f32) + dt_bias)

        if decode_step:
            o, state = gd.gdn_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], rec[0], entries, live,
                                     layer=layer)
            o, rec = o[:, None], (state, rec[1])
        else:
            o, final = gd.gated_delta_chunked(q, k, v, g, beta, lengths)
            if rec is not None and not self.is_initializing():
                state, conv = rec
                if lengths is None:
                    lengths = jnp.full((b,), t, jnp.int32)
                # the last taps - 1 REAL inputs (zeros before the start),
                # picked by a 0/1 product: exact, and no gather
                pick = (jnp.arange(window.shape[1])[None, None, :]
                        == lengths[:, None, None]
                        + jnp.arange(taps - 1)[None, :, None])
                tail = jnp.einsum("bjt,btc->bjc", pick.astype(window.dtype),
                                  window)
                rec = (gd.write_rows(state, gd.pack_state(final), layer,
                                     entries),
                       gd.write_rows(conv, tail.reshape(
                           (b,) + conv.shape[2:]), layer, entries))
        # per-head RMSNorm (one weight of dv), gated
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * o_scale
        gate = jax.nn.sigmoid if self.channelwise else nn.silu
        o = (o * gate(z.astype(f32))).astype(cfg.dtype)
        out = _dense(cfg.d_model, ("heads_embed", "embed"), "wo",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
            o.reshape(b, t, hv * dv))
        return out if rec is None else (out, rec)


class KimiDeltaAttention(LinearAttention):
    """Kimi Delta Attention (arXiv:2510.26692; the kimi_linear family's
    linear layer): ``LinearAttention``'s mixer (the three convolutions
    are depthwise, so one over ``[q; k; v]``; ``rec`` the same leaves)
    with other gates, ``y`` the block's normed input::

        g    = -exp(A_log) softplus(f_b(f_a(y)) + dt_bias)   [heads, dk]
        beta = sigmoid(y Wb)                                 in (0, 1)
        o    = RMSNorm_dv(o) * sigmoid(g_b(g_a(y)))

    ``f`` and ``g`` are low-rank pairs through ``linear_gate_rank``,
    ``A_log`` one a head, ``dt_bias`` one a key channel: the decay is a
    VECTOR over the head's key channels (``S' = Diag(exp(g)) S``:
    ops/gated_delta.py takes it by ``g``'s rank), where Gated DeltaNet
    has one scalar a head, and the output gate a sigmoid, where it has
    SiLU.  As many value heads as key heads."""

    channelwise: bool = True


class LinearBlock(nn.Module):
    """A pre-norm block around ``LinearAttention``: ``h = x +
    GDN(Norm(x))``, ``out = h + MLP(Norm(h))``."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False

    @nn.compact
    def __call__(self, x, block_tables=None, lengths=None, entries=None,
                 rec=None, layer=None):
        cfg = self.cfg
        live = None if block_tables is None else block_tables[:, 0] != 0
        y = LinearAttention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, name="attn_norm")(x), lengths, entries,
            rec, layer, live)
        if rec is not None:
            y, rec = y
        x = x + jax.ad_checkpoint.checkpoint_name(y, "attn_out")
        y = MLP(cfg, name="mlp")(RMSNorm(cfg.norm_eps, name="mlp_norm")(x))
        x = x + jax.ad_checkpoint.checkpoint_name(y, "mlp_out")
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        return x if rec is None else (x, rec)


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer (ops/mamba2.py): one input projection to ``[z | x B
    C | dt]``, a causal depthwise convolution with a bias and SiLU over
    ``[x; B; C]``, the selective state-space recurrence with a scalar
    decay a head (``B``, ``C`` shared by the heads of a group), ``+ D
    u``, the output gated by ``SiLU(z)`` and THEN normalised over each
    group's channels, the output projection.

    What it remembers of a sequence is ``rec = (state, conv)`` as
    ``LinearAttention``'s is: the model's two stacked leaves ``[ssm
    layers, entries, N, heads * P]`` float32 and the convolution's last
    ``taps - 1`` inputs, flat in rows of 128 lanes, addressed ``[layer,
    entry]`` and passed through whole.  Without ``rec`` a call is a
    whole sequence from an empty state; with it ``T > 1`` is a prompt
    from an empty state whose final state and tail, as of each row's
    last REAL token (``lengths``), are written to the rows'
    ``entries``, and ``T == 1`` one decode step on the rows' entries,
    dead rows untouched."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, lengths=None, entries=None, rec=None, layer=None,
                 live=None):
        from ray_tpu.ops import gated_delta as gd
        from ray_tpu.ops import mamba2
        cfg = self.cfg
        h, p = cfg.mamba_heads, cfg.mamba_head_dim
        n, g, taps = cfg.ssm_state_size, cfg.mamba_groups, cfg.mamba_conv_kernel
        inner, bc = h * p, g * n
        b, t, _ = x.shape
        f32 = jnp.float32
        zxd = _dense(2 * inner + 2 * bc + h, ("embed", "mlp"), "in_proj",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        z, u, dt = jnp.split(zxd, [inner, 2 * inner + 2 * bc], axis=-1)
        vec = lambda init: nn.with_logical_partitioning(    # noqa: E731
            init, ("norm",))
        conv_w = self.param(
            "conv", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), (None, "norm")),
            (taps, inner + 2 * bc), cfg.param_dtype).astype(f32)
        conv_b = self.param("conv_bias", vec(nn.initializers.normal(0.1)),
                            (inner + 2 * bc,), f32)
        a_log = self.param("A_log", vec(_a_log_init), (h,), f32)
        dt_bias = self.param("dt_bias", vec(_dt_bias_init), (h,), f32)
        d_skip = self.param("D", vec(nn.initializers.ones_init()), (h,), f32)
        o_scale = self.param("norm", vec(nn.initializers.ones_init()),
                             (inner,), f32)

        decode_step = rec is not None and t == 1 \
            and not self.is_initializing()
        if decode_step:
            state, conv = rec
            # the rows' tails out of the stacked leaf and back ONE LAYER'S
            # SLAB at a time: over the leaf viewed flat
            # (``gated_delta.flat_rows``) the TPU compiler holds the whole
            # leaf in fast memory around every layer's scatter and copies
            # it there and back (69 MB at 36 layers of 73 entries: a third
            # of a decode step; PERF.md section 6, PR 50)
            slab = jax.lax.dynamic_index_in_dim(conv, layer, 0, False)
            tail = slab[entries].reshape(b, taps - 1, -1)
            window = jnp.concatenate([tail, u.astype(conv.dtype)], 1)
            if live is not None:      # a dead row keeps its tail
                tail = jnp.where(live[:, None, None], window[:, 1:], tail)
            else:
                tail = window[:, 1:]
            slab = slab.at[entries].set(tail.reshape((b,) + conv.shape[2:]))
            rec = (state,
                   jax.lax.dynamic_update_index_in_dim(conv, slab, layer, 0))
        else:       # zeros before the sequence's start
            window = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
        c = sum(window[:, j:j + t].astype(f32) * conv_w[j]
                for j in range(taps)) + conv_b
        c = nn.silu(c).astype(cfg.dtype)
        xs, bm, cm = jnp.split(c, [inner, inner + bc], axis=-1)
        xs = xs.reshape(b, t, h, p)
        bm, cm = bm.reshape(b, t, g, n), cm.reshape(b, t, g, n)
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias)
        a_neg = -jnp.exp(a_log)

        if decode_step:
            y, state = mamba2.ssm_decode(
                delta[:, 0, :, None] * xs[:, 0].astype(f32),
                jnp.exp(delta[:, 0] * a_neg), bm[:, 0], cm[:, 0], rec[0],
                entries, live, layer=layer)
            y, rec = y[:, None], (state, rec[1])
        else:
            with jax.named_scope("ssm_prefill"):
                y, final = mamba2.ssm_chunked(xs, delta, a_neg, bm, cm,
                                              lengths, chunk=cfg.mamba_chunk)
            if rec is not None and not self.is_initializing():
                state, conv = rec
                if lengths is None:
                    lengths = jnp.full((b,), t, jnp.int32)
                # the last taps - 1 REAL inputs (zeros before the start),
                # picked by a 0/1 product: exact, and no gather
                pick = (jnp.arange(window.shape[1])[None, None, :]
                        == lengths[:, None, None]
                        + jnp.arange(taps - 1)[None, :, None])
                tail = jnp.einsum("bjt,btc->bjc", pick.astype(window.dtype),
                                  window)
                # the update pinned row-major, as the leaf is: a one-row
                # ``write_rows`` is ONE update, no loop, and XLA's layout
                # assignment may then take the update's layout (N minor,
                # as the chunked form's product leaves it) for the whole
                # carried leaf: a copy of it in and out of the wave, 5.1
                # GB at 36 layers of 73 entries (PERF.md section 6, PR 50)
                final = with_layout_constraint(
                    gd.pack_state(final), Layout(major_to_minor=(0, 1, 2)))
                rec = (gd.write_rows(state, final, layer, entries),
                       gd.write_rows(conv, tail.reshape(
                           (b,) + conv.shape[2:]), layer, entries))
        y = y + d_skip[:, None] * xs.astype(f32)
        # gated, then an RMSNorm over each group's channels
        y = (y.reshape(b, t, inner) * nn.silu(z.astype(f32))).reshape(
            b, t, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + cfg.norm_eps)
        y = (y.reshape(b, t, inner) * o_scale).astype(cfg.dtype)
        out = _dense(cfg.d_model, ("mlp", "embed"), "out_proj",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(y)
        return out if rec is None else (out, rec)


class MambaBlock(nn.Module):
    """A pre-norm block around ``Mamba2Mixer``, as ``LinearBlock`` is
    around ``LinearAttention``: ``h = x + r Mamba2(Norm(x))``, ``out = h
    + r MLP(Norm(h))``, ``r`` the model's ``residual_multiplier`` (the
    granitemoehybrid family's layer; its feed-forward is the dense
    SwiGLU of ``d_ff``)."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False

    @nn.compact
    def __call__(self, x, block_tables=None, lengths=None, entries=None,
                 rec=None, layer=None):
        cfg = self.cfg
        live = None if block_tables is None else block_tables[:, 0] != 0
        with jax.named_scope("mamba_mixer"):
            y = Mamba2Mixer(cfg, name="mixer")(
                RMSNorm(cfg.norm_eps, name="mixer_norm")(x), lengths,
                entries, rec, layer, live)
        if rec is not None:
            y, rec = y
        x = x + _branch(cfg, jax.ad_checkpoint.checkpoint_name(y, "attn_out"))
        with jax.named_scope("mamba_mlp"):
            y = MLP(cfg, name="mlp")(
                RMSNorm(cfg.norm_eps, name="mlp_norm")(x))
        x = x + _branch(cfg, jax.ad_checkpoint.checkpoint_name(y, "mlp_out"))
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        return x if rec is None else (x, rec)


class MixerBlock(nn.Module):
    """A layer that is ONE mixer: ``x + Mixer(Norm(x))``, the mixer by
    ``kind``: ``"mamba2"`` (``Mamba2Mixer``; its carry the recurrent
    leaves), ``"latent_moe"`` (ops/moe.py ``LatentMoE``; no carry) or
    ``"attention_only"`` (``Attention``; its carry the KV pool)."""

    cfg: TransformerConfig
    kind: str = "mamba2"
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    prefix_attend: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 lengths=None, entries=None, carry=None, layer=None,
                 moe_stacked=None):
        """``moe_stacked`` (a latent_moe layer in decode): the routed
        experts' leaves stacked over periods, ``layer`` then the
        period's index (``DroplessMoE.__call__``)."""
        cfg = self.cfg
        live = None if block_tables is None else block_tables[:, 0] != 0
        y = RMSNorm(cfg.norm_eps, name="norm")(x)
        if self.kind == "mamba2":
            y = Mamba2Mixer(cfg, name="mixer")(y, lengths, entries, carry,
                                               layer, live)
        elif self.kind == "latent_moe":
            from ray_tpu.ops.moe import LatentMoE
            y = LatentMoE(
                cfg.d_model, cfg.moe_latent_size, cfg.moe_experts,
                cfg.moe_d_ff, cfg.moe_shared_d_ff, top_k=cfg.moe_top_k,
                act=cfg.moe_act, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, scoring=cfg.moe_scoring,
                route_scale=cfg.moe_route_scale, held=cfg.moe_experts_held,
                held_first=cfg.moe_held_first, name="mixer")(
                y, live, moe_stacked, layer)
        else:
            y = Attention(cfg, self.mesh, self.rules, self.decode,
                          self.prefix_attend, name="mixer")(
                y, cos, sin, positions, block_tables, carry, layer, live)
        if carry is not None:
            y, carry = y
        y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
        x = x + y
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        return x if carry is None else (x, carry)


def _own_experts_stacked(block):
    """A bound ``Block``'s own dropless experts as a stack of one,
    which the decode kernel reads in place (``DroplessMoE.__call__``:
    index 0)."""
    moe = nn.meta.unbox(block.variables["params"]["moe"])
    return tuple(w[None] for w in (moe["w_gate"], moe["w_up"],
                                   moe["w_down"]))


class Period(nn.Module):
    """One period of ``cfg.layer_types``: what ``stack_layers`` scans
    where the layers are of more than one block CLASS (their parameter
    trees differ, so one stacked ``Block`` cannot hold them).  The
    parameters are stacked over periods, one subtree ``layer_<j>`` a
    position in the period.  The carry is ``(pool, rec)``: the KV pool
    stacked over the layers that hold pages ONLY (``POOL_KINDS``) and
    the recurrent leaves stacked over the layers that hold a state
    entry (``STATE_KINDS``).  Each class names its carry (``_carry_of``;
    an expert layer has none); ``period`` (the scanned index) times the
    layers of that carry a period, plus the position's rank among them,
    is the layer's index into its leaf.

    Also a RUN of layers that no scan holds (``cfg.runs``: the whole
    periods a leading dense layer breaks, the partial period at the
    end), called once: ``kinds`` its classes, ``first`` the depth of
    its first layer (a layer before depth ``cfg.first_dense_layers`` has
    the dense feed-forward) and ``ahead`` the layers of each carry in front
    of it, which its indices go on from."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    prefix_attend: bool = False
    kinds: Optional[tuple] = None          # None: cfg.period
    first: int = 0
    ahead: tuple = (0, 0)

    @staticmethod
    def _carry_of(kind: str):
        """Which of the period's two carries a layer of class ``kind``
        reads and writes: 0 the pool, 1 the recurrent leaves, None."""
        return 0 if kind in POOL_KINDS else 1 if kind in STATE_KINDS else None

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 lengths=None, entries=None, moe_stacked=None, carry=None,
                 period=None):
        """``moe_stacked``: ``{position in the period: the expert leaves
        stacked over periods}`` (``GPT._moe_stacked``), so that the
        decode kernel reads a layer's experts in place."""
        cfg = self.cfg
        kinds = self.kinds or cfg.period
        carries = [None, None] if carry is None else list(carry)
        held = [self._carry_of(kind) for kind in kinds]
        seen = [0, 0]
        blocks = (cfg, self.mesh, self.rules, self.decode)
        for j, (kind, at) in enumerate(zip(kinds, held)):
            mine = layer = None
            if at is not None and carry is not None:
                mine = carries[at]
                layer = period * held.count(at) + seen[at]
                if self.ahead[at]:
                    layer = layer + self.ahead[at]
                seen[at] += 1
            stacked = (moe_stacked or {}).get(j)
            if kind in ("full_attention", "kda"):
                # an attention layer is not told the lengths: a period's
                # prompt waves stay one pass (its linear layers compute
                # every position).  Experts, where the model has them,
                # behind either mixer, their stacked leaves' index the
                # period's
                kda = kind == "kda"
                dense = self.first + j < cfg.first_dense_layers
                block = Block(*blocks, self.prefix_attend, dense, kda=kda,
                              name=f"layer_{j}")
                index = period if stacked else None
                if (self.kinds and mine is not None and cfg.moe_experts
                        and not dense and not self.is_initializing()):
                    # a layer of an unrolled run (as ``MTPModule``'s block)
                    stacked, index = _own_experts_stacked(block), 0
                x = block(x, cos, sin, positions, block_tables, stacked,
                          lengths if kda else None, mine, layer,
                          entries=entries, moe_index=index)
            elif kind in ("linear_attention", "mamba2_mlp"):
                block = LinearBlock if kind == "linear_attention" \
                    else MambaBlock
                x = block(*blocks, name=f"layer_{j}")(
                    x, block_tables, lengths, entries, mine, layer)
            else:
                x = MixerBlock(cfg, kind, *blocks[1:],
                               self.prefix_attend, name=f"layer_{j}")(
                    x, cos, sin, positions, block_tables, lengths, entries,
                    mine, period if stacked else layer, stacked)
            if mine is not None:
                x, carries[at] = x
        return x if carry is None else (x, tuple(carries))


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2), behind the stack::

        x_i = M [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]
        g_i = RMSNorm(Block(x)_i)

    ``h_i`` the stack's last hidden state at position i BEFORE its final
    norm, ``M`` 2 d_model -> d_model, the block one of the stack's kind
    (``Block.lone``: global attention without rotation, experts where
    the stack has them), the embedding and the head the model's own
    (``GPT`` looks the tokens up and ``output_logits`` reads ``g``): the
    logits at i are of token i + 2.  With a paged ``pool`` the block's
    K/V rows live in the pool's last layer, at position i, under the
    rows' own page tables, and the result is ``(g, pool)``."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    verify: bool = False

    @nn.compact
    def __call__(self, emb, hidden, cos, sin, positions=None,
                 block_tables=None, lengths=None, pool=None):
        cfg = self.cfg
        x = _dense(cfg.d_model, ("mlp", "embed"), "eh_proj",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
            jnp.concatenate([RMSNorm(cfg.norm_eps, name="enorm")(emb),
                             RMSNorm(cfg.norm_eps, name="hnorm")(hidden)],
                            axis=-1))
        block = Block(cfg, self.mesh, self.rules, self.decode, False, False,
                      self.verify, True, name="block")
        stacked = None
        if (pool is not None and cfg.moe_experts and cfg.moe_dropless
                and not self.is_initializing()):
            stacked = _own_experts_stacked(block)
        x = block(x, cos, sin, positions, block_tables, stacked, lengths,
                  pool, None if pool is None else cfg.n_layers)
        if pool is not None:
            x, pool = x
        x = RMSNorm(cfg.norm_eps, name="norm")(x)
        return x if pool is None else (x, pool)


def output_logits(cfg: TransformerConfig, params, hidden) -> jax.Array:
    """float32 logits of post-final-norm hidden states ``[..., d_model]``
    (what ``GPT.__call__(return_hidden=True)`` returns) under ``params``
    (GPT's tree): the head ``GPT.__call__`` applies, for a caller that
    needs it on a few positions only (serve/llm_engine.py: one row a
    prompt, not ``[wave, bucket, vocab]``)."""
    p = nn.meta.unbox(params)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", hidden,
                            p["embed"].astype(cfg.dtype))
    else:
        logits = jnp.einsum("...d,dv->...v", hidden,
                            p["lm_head"]["kernel"].astype(cfg.dtype))
    return _scaled_logits(cfg, logits)


def _scaled_logits(cfg: TransformerConfig, logits) -> jax.Array:
    """The head's products as float32 logits, divided by the model's
    ``logits_scaling`` where it states one."""
    logits = logits.astype(jnp.float32)
    return logits if cfg.logits_scaling == 1.0 \
        else logits / cfg.logits_scaling


def narrowed_logits(cfg: TransformerConfig, logits) -> jax.Array:
    """``_scaled_logits``' float32 logits back in ``cfg.dtype``, where
    that is the same numbers: they are ``cfg.dtype`` products widened,
    and a ``logits_scaling`` that is a power of two moves the exponent
    alone.  A reader behind a boundary the compiler cannot fuse the
    widening through (the sampler's conditional: serve/llm_engine.py
    ``_sample_fn``) takes them so, and the head then writes them at
    that width: as float32 they are twice the bytes.  Any other scaling
    leaves them float32."""
    exact = math.frexp(cfg.logits_scaling)[0] == 0.5
    return logits.astype(cfg.dtype) if exact else logits


class GPT(nn.Module):
    """Decoder-only LM.  ``__call__`` returns logits [B, S, vocab], or the
    post-final-norm hidden states [B, S, d_model] with
    ``return_hidden=True`` (the chunked-loss head path)."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    paged_pages: int = 0                   # >0: paged KV decode (see Attention)
    page_size: int = 64
    prefix_attend: bool = False            # suffix prefill over cached pages
    # entries of the recurrent leaves (see LinearAttention,
    # Mamba2Mixer) of a paged model with such layers; entry 0 is scratch
    state_entries: int = 0
    # a paged call of T > 1 positions a row is a decode step over a
    # draft, not a prompt (see Attention)
    verify: bool = False

    def _moe_stacked(self):
        """The scanned layer stack's dropless expert leaves ``[L, E, ...]``
        whole, for a decode-mode model (no gradient is taken through the
        kernel that reads them): they ride ``call_args`` into the scan
        body beside the layer index, as the KV pool rides the carry, so
        that no layer's experts are sliced out.  None where there is no
        such stack (no dropless experts, unrolled layers, initialising).
        A stack of periods: one triple a latent_moe position."""
        cfg = self.cfg
        if not (self.decode and cfg.moe_dropless and cfg.moe_experts
                and cfg.scan_layers) or self.is_initializing():
            return None
        leaves = lambda moe: (moe.get("w_gate"), moe["w_up"],  # noqa: E731
                              moe["w_down"])
        blocks = nn.meta.unbox(self.variables["params"]["blocks"])
        if cfg.period:       # by position in the period, stacked over them
            return {j: leaves(blocks[f"layer_{j}"]["mixer"]["moe"]
                              if kind == "latent_moe"
                              else blocks[f"layer_{j}"]["moe"])
                    for j, kind in enumerate(cfg.period)
                    if kind in ("latent_moe", "full_attention", "kda")}
        return leaves(blocks["moe"])

    def _stack_blocks(self, x, block_kwargs, call_args, **stack):
        """``stack_layers`` of ``Block`` over every layer.  Where the
        first ``cfg.first_dense_layers`` have a dense feed-forward and
        the others experts, two stacks: ``dense_blocks`` (a parameter
        tree of its own: the kinds differ in leaves, not in two
        scalars), then ``blocks``, the scanned expert stack, whose layer
        indices go on from the prefix's; a ``carry`` (the pool) rides
        through both."""
        cfg = self.cfg
        first = cfg.first_dense_layers
        if not first:
            return stack_layers(Block, cfg, block_kwargs, x, call_args,
                                **stack)
        carry = stack.pop("carry", None)
        x = stack_layers(Block, cfg, dict(block_kwargs, dense_ffn=True), x,
                         call_args, name="dense_blocks", n_layers=first,
                         carry=carry, **stack)
        if carry is not None:
            x, carry = x
        return stack_layers(Block, cfg, block_kwargs, x, call_args,
                            n_layers=cfg.n_layers - first,
                            first_layer=first, carry=carry, **stack)

    def _stack_periods(self, x, block_kwargs, call_args, lengths, entries,
                       moe_stacked, remat):
        """The layer stack of a model whose ``layer_types`` name more
        than one block class: ``Period`` is what is scanned (4 layers a
        step at Olmo-Hybrid's 3 + 1), and a paged decode model carries
        ``(pool, (state, conv))`` through it: the KV pool has the layers
        that hold pages only, the recurrent leaves (their shapes the
        recurrent class's own) those that hold a state entry.  Under
        ``cfg.layer_period`` the scan has an unrolled run of layers in
        front and another behind (``cfg.runs``; parameter subtrees
        ``head`` and ``tail``), and the carry and its indices go on
        through all three."""
        cfg = self.cfg
        head, n_periods, tail = cfg.runs
        p = len(cfg.period)
        kinds = cfg.layer_types[:cfg.n_layers]
        paged = bool(self.decode and self.paged_pages)

        def placed(first):
            """What tells a ``Period`` that starts at depth ``first``
            where it is: the layers of each carry in front of it."""
            return {} if not first else dict(first=first, ahead=tuple(
                sum(Period._carry_of(k) == at for k in kinds[:first])
                for at in (0, 1)))

        def run(name, first, count, x, carry):
            """The ``count`` layers from ``first``, unrolled."""
            if not count:
                return x if carry is None else (x, carry)
            block = _rematted(Period, cfg) if remat and not paged else Period
            return block(cfg, **block_kwargs, **placed(first),
                         kinds=kinds[first:first + count], name=name)(
                x, *call_args, lengths, entries if paged else None, None,
                carry, 0)

        def stack(x, carry):
            x = run("head", 0, head, x, carry)
            if carry is not None:
                x, carry = x
            x = stack_layers(
                Period, cfg, dict(block_kwargs, **placed(head)), x,
                call_args + ((lengths, entries, moe_stacked) if paged
                             else (lengths, None, None)),
                remat=remat and not paged, n_layers=n_periods,
                **({"carry": carry} if paged else {"cache": True}))
            if carry is not None:
                x, carry = x
            return run("tail", head + n_periods * p, tail, x, carry)

        if not paged:
            if self.decode and not self.is_initializing():
                raise ValueError(
                    "a layer with a recurrent state has no dense-cache "
                    "decode: its state lives in the paged engine's "
                    "entries (serve/llm_engine.py); Generator cannot run "
                    "it")
            return stack(x, None)
        n_pool = cfg.layers_of(*POOL_KINDS)
        n_state = cfg.layers_of(*STATE_KINDS)
        # a request's state in one recurrent layer, and its
        # convolution's last inputs over every channel convolved
        if cfg.layers_of(*MAMBA_KINDS):
            names = "ssm_state", "ssm_conv"
            entry = (cfg.ssm_state_size,
                     cfg.mamba_heads * cfg.mamba_head_dim)
            tail_width = (cfg.mamba_conv_kernel - 1) * (
                entry[1] + 2 * cfg.mamba_groups * cfg.ssm_state_size)
        else:
            names = "gdn_state", "gdn_conv"
            entry = (cfg.linear_key_head_dim,
                     cfg.linear_value_heads * cfg.linear_value_head_dim)
            tail_width = (cfg.linear_conv_kernel - 1) * (
                2 * cfg.linear_key_heads * cfg.linear_key_head_dim
                + cfg.linear_value_heads * cfg.linear_value_head_dim)
        ckv = self.variable(
            "cache", "kv_pages", jnp.zeros,
            (n_pool, self.paged_pages, cfg.cache_kv_heads, self.page_size,
             cfg.cache_row_width), cfg.dtype)
        cst = self.variable(
            "cache", names[0], jnp.zeros,
            (n_state, self.state_entries) + entry, jnp.float32)
        ccv = self.variable(
            "cache", names[1], jnp.zeros,
            (n_state, self.state_entries) + (
                (tail_width // 128, 128) if tail_width % 128 == 0
                else (1, tail_width)),
            cfg.dtype)
        if entries is None:
            entries = jnp.arange(x.shape[0], dtype=jnp.int32)
        x, (pool, (state, conv)) = stack(
            x, (ckv.value, (cst.value, ccv.value)))
        if not self.is_initializing():
            ckv.value, cst.value, ccv.value = pool, state, conv
        return x

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden: bool = False,
                 block_tables=None, lengths=None, state_rows=None,
                 mtp_hidden=None, return_prenorm: bool = False):
        """``mtp_hidden`` [B, T, d_model] (a model with ``cfg.mtp_layers``):
        run the multi-token-prediction module INSTEAD of the stack, on the
        stack's last hidden states before the final norm (what
        ``return_prenorm`` returns beside the normed ones, ``(hidden,
        prenorm)``, with ``return_hidden``) at ``positions``, ``tokens``
        then being the tokens one position on; the result is the module's
        normed output (``return_hidden``) or its logits through the
        model's head, of the tokens two positions on.

        ``lengths`` [B]: each row's REAL length in a ``T > 1`` call
        (None: every position is real).  A linear_attention layer needs
        it (right-pad that attention never sees would be absorbed by a
        recurrence); a ``Block`` writing a prompt wave into the pool
        leaves out the work past the longest of them (``Block``,
        ``_prefill_attend``), and what a row holds past its own is then
        finite and otherwise unspecified.  ``state_rows`` [B] (a model
        with linear_attention layers): each row's entry of the recurrent
        leaves (None: row i uses entry i)."""
        cfg = self.cfg
        embed = self.param(
            "embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        lookup = embed
        if self.mesh is not None and not self.decode:
            # explicit all-gather of the sharded table before the lookup:
            # left to itself the partitioner reshards the gather result
            # via an involuntary full rematerialization (replicate, then
            # repartition — a full-tensor broadcast on the step's hot
            # path).  Constraining the operand makes the same transfer
            # ONE clean all-gather and the gather itself local.
            lookup = with_sharding(self.mesh, embed, (None, None),
                                   self.rules)
        x = jnp.take(lookup, tokens, axis=0).astype(cfg.dtype)
        if cfg.embedding_multiplier != 1.0:
            # of the looked-up rows, not of the table: a tied head reads
            # the table as it is stored
            x = x * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        cos = sin = None
        if cfg.rope_theta is not None:
            cos, sin = rope_frequencies(cfg.rope_dim, cfg.max_seq_len,
                                        cfg.rope_theta)
        if cos is not None and self.mesh is not None and not self.decode:
            # the rope tables are tiny closure constants: pin them
            # replicated so the partitioner never invents a sharding for
            # them (they otherwise surface as involuntarily
            # rematerialized fake_parameters)
            cos = with_sharding(self.mesh, cos, (None, None), self.rules)
            sin = with_sharding(self.mesh, sin, (None, None), self.rules)

        do_remat = cfg.remat and not self.decode
        block_kwargs = dict(mesh=self.mesh, rules=self.rules,
                            decode=self.decode,
                            prefix_attend=self.prefix_attend)
        if self.verify:
            block_kwargs["verify"] = True
        # the paged pool: ONE stacked leaf for the whole model (below)
        ckv = self._pool() if (self.decode and self.paged_pages
                               and not cfg.period) else None
        if cfg.mtp_layers and (mtp_hidden is not None
                               or self.is_initializing()):
            mtp = MTPModule(cfg, self.mesh, self.rules, self.decode,
                            self.verify, name="mtp")
        if mtp_hidden is not None:
            x = mtp(x, mtp_hidden, cos, sin, positions, block_tables,
                    lengths, None if ckv is None else ckv.value)
            if ckv is not None:
                x, ckv.value = x
            return x if return_hidden else self._head(x, embed)
        call_args = (cos, sin, positions, block_tables,
                     self._moe_stacked(), lengths)
        if cfg.period:
            x = self._stack_periods(x, block_kwargs, call_args[:4], lengths,
                                    state_rows, call_args[4], do_remat)
        elif ckv is not None:
            # the pool's row is what the model's attention caches of a
            # token (K in [..., :hd], V in [..., hd:]; or one latent row
            # for all heads: cfg.cache_row_width, ops/paged_attention.py
            # layout note).  It rides the layer stack as loop-carried state
            # with a layer index, so each block writes its rows in place
            # and no layer's pool is ever sliced out, relaid or written
            # back.
            x, pool = self._stack_blocks(x, block_kwargs, call_args,
                                         remat=False, carry=ckv.value)
            if not self.is_initializing():
                ckv.value = pool
        else:
            x = self._stack_blocks(x, block_kwargs, call_args,
                                   remat=do_remat, cache=True)

        prenorm = x
        if cfg.mtp_layers and self.is_initializing():
            # shape-only: the module's parameters (no cache is written)
            mtp(jnp.take(lookup, tokens, axis=0).astype(cfg.dtype), x, cos,
                sin, positions, block_tables, None,
                None if ckv is None else ckv.value)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if return_hidden:
            # memory-lean loss path: the caller projects per sequence
            # chunk (ops/losses.py chunked_lm_loss) so [B, S, vocab]
            # logits never materialize
            return (x, prenorm) if return_prenorm else x
        return self._head(x, embed)

    def _pool(self):
        """The paged pool's cache variable (``__call__``): the stack's
        layers and, behind them, the prediction module's."""
        cfg = self.cfg
        return self.variable(
            "cache", "kv_pages", jnp.zeros,
            (cfg.n_layers + cfg.mtp_layers, self.paged_pages,
             cfg.cache_kv_heads, self.page_size, cfg.cache_row_width),
            cfg.dtype)

    def _head(self, x, embed):
        """float32 logits of normed hidden states (``__call__``'s own
        compact scope: the head's parameters are the model's)."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
        else:
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        if self.mesh is not None and not self.decode:
            logits = with_sharding(self.mesh, logits,
                                   ("batch", "seq", "act_vocab"), self.rules)
        return _scaled_logits(cfg, logits)
