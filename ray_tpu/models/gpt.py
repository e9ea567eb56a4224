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

from typing import Optional

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models.configs import TransformerConfig
from ray_tpu.ops.attention import repeat_kv, xla_attention
from ray_tpu.ops.layers import apply_rope, rope_frequencies
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


class MLP(nn.Module):
    """SwiGLU feed-forward (shared by the decoder, encoder, and T5).

    FORMAT BREAK (round 1): extracting this submodule renamed parameter
    paths ``block_i/w_gate`` -> ``block_i/mlp/w_gate`` (same under scan).
    Checkpoints written before that refactor need their keys re-nested
    under ``mlp/`` to load; no shim is kept since no pre-break checkpoint
    left the repo."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.cfg
        gate = _dense(cfg.d_ff, ("embed", "mlp"), "w_gate",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(y)
        up = _dense(cfg.d_ff, ("embed", "mlp"), "w_up",
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype)(y)
        return _dense(cfg.d_model, ("mlp", "embed"), "w_down",
                      dtype=cfg.dtype, param_dtype=cfg.param_dtype)(
            nn.silu(gate) * up)


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

    cfg.remat_layers splits the stack at the CALLER (two stack_layers
    calls, one rematted, one plain) — partial remat for configs with
    HBM headroom between "recompute everything" and "store everything".

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
        policy = policies[cfg.remat_policy]
        block_cls = nn.remat(block_cls, prevent_cse=False, policy=policy)
    if cfg.scan_layers:
        variable_axes = {"params": 0, "intermediates": 0}
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
        out, _ = nn.scan(
            body,
            variable_axes=variable_axes,
            split_rngs={"params": True},
            length=n_layers,
            metadata_params={nn.PARTITION_NAME: None},
        )(block_cls(cfg, **ctor_kwargs, name=name), init, layers)
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

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 pool=None, layer=None, live=None):
        """``pool`` (paged decode, serve/llm_engine.py paged mode): the
        model's ONE stacked KV page pool ``[layers, pages, kv_heads,
        page_size, 2*head_dim]`` (GPT declares it; see
        ops/paged_attention.py) and this block's ``layer`` index into
        it.  Returns ``(out, pool)`` then: the pool is passed through,
        updated in place, never sliced.  ``live`` [rows] bool (``Block``
        makes it): the rows that hold a request, the only ones a decode
        step reads pages for."""
        cfg = self.cfg
        h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = _dense((h, hd), ("embed", "heads", "head_dim"), "wq",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        k = _dense((kvh, hd), ("embed", "kv", "head_dim"), "wk",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        v = _dense((kvh, hd), ("embed", "kv", "head_dim"), "wv",
                   dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        rotate, window = self._layer_kind(layer)
        if rotate is None:
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        else:       # this layer's entry of rope_layout: 0 -> no positions
            q = jnp.where(rotate, apply_rope(q, cos, sin, positions), q)
            k = jnp.where(rotate, apply_rope(k, cos, sin, positions), k)

        if pool is not None:
            out, pool = self._decode_attend_paged(
                q, k, v, positions, block_tables, pool, layer, window,
                live)
        elif self.decode:
            out = self._decode_attend(q, k, v, positions, window)
        else:
            out = self._train_attend(q, k, v, window)
        out = out.reshape(*out.shape[:2], h * hd)
        out = _dense(cfg.d_model, ("heads_embed", "embed"), "wo",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(out)
        return out if pool is None else (out, pool)

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

    @staticmethod
    def _window_attend(q, k, v, window):
        """Causal attention of a span over itself under a window; no
        kernel here takes one (ROADMAP B2): masked einsum."""
        pos = jnp.arange(q.shape[1])
        return xla_attention(q, k, v, causal=False,
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
                return ring_attention(q, k, v, mesh=self.mesh, causal=True)
            # ulysses handles GQA natively (KV all-to-all stays at kv_heads);
            # only expand when kv_heads doesn't divide the context axis
            ctx = self.mesh.shape.get("context", 1)
            if cfg.n_kv_heads % ctx:
                k = repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
                v = repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
            from ray_tpu.ops.ulysses import ulysses_attention
            return ulysses_attention(q, k, v, mesh=self.mesh, causal=True)
        from ray_tpu.ops.attention import attention, resolve_impl
        impl = resolve_impl(impl)
        if impl in ("flash", "splash") and self.mesh is not None \
                and self.mesh.size > 1:
            return self._kernel_attend_sharded(q, k, v, impl)
        return attention(q, k, v, causal=True, impl=impl)

    def _kernel_attend_sharded(self, q, k, v, impl: str):
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
                f"attention_impl={impl!r} needs the whole sequence on "
                "each shard; with context parallelism use 'ring' or "
                "'ulysses'")
        if cfg.n_kv_heads != cfg.n_heads:
            # equal head counts, so q/k/v share one head sharding
            k = repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
            v = repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
        spec = logical_spec(("batch", None, "heads", None), mesh,
                            self.rules)
        fn = functools.partial(attention, causal=True, impl=impl)
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
        return xla_attention(q, ck.value, cv.value, causal=False, mask=mask)

    def _decode_attend_paged(self, q, k, v, positions, block_tables,
                             pool, layer, window=None, live=None):
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
        whole cache interaction, and right-pad garbage past
        a real prompt is overwritten by decode writes before any length
        mask makes it visible (same invariant as dense slot mode).

        ``window`` (this layer's, traced; None without one): decode
        reads only the pages that hold the last ``window`` positions,
        and a T > 1 span longer than the window is masked by it.
        ``live`` [rows] bool (None: every row): decode reads no page of
        a row it leaves out, whose output is zeros; its K/V is still
        written (to the scratch page its table names).
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
        pool = write_kv_pages(pool, jnp.concatenate([k, v], axis=-1),
                              block_tables, positions, layer=layer)
        if q.shape[1] == 1:
            out = paged_attention(
                q[:, 0], pool, block_tables, positions[:, 0] + 1,
                layer=layer, live=live, window=self._window_over(
                    window, block_tables.shape[1] * pool.shape[3]))
            return out[:, None], pool
        if not self.prefix_attend:
            window = self._window_over(window, q.shape[1])
            if window is not None:
                return self._window_attend(q, k, v, window), pool
            return xla_attention(q, k, v, causal=True), pool
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
                             kvfull[..., cfg.head_dim:],
                             causal=False, mask=mask), pool


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    rules: ShardingRules = LOGICAL_RULES
    decode: bool = False
    prefix_attend: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, block_tables=None,
                 moe_stacked=None, pool=None, layer=None):
        """With a paged KV ``pool`` (see Attention) returns ``(x, pool)``:
        the shape ``stack_layers`` carries it through the stack in.
        ``moe_stacked``: the layer stack's whole dropless expert leaves
        (GPT hands them down in decode; see ``DroplessMoE.__call__``)."""
        cfg = self.cfg
        # a row whose table starts at the scratch page holds no request
        # (serve/llm_engine.py): neither the decode attention kernel nor
        # the expert kernel reads anything for it
        live = None if block_tables is None else block_tables[:, 0] != 0
        y = RMSNorm(cfg.norm_eps, name="attn_norm")(x)
        moe = router_logits = None
        if cfg.moe_experts > 0 and cfg.moe_dropless:
            from ray_tpu.ops.moe import DroplessMoE
            moe = DroplessMoE(cfg.d_model, cfg.moe_experts, cfg.moe_d_ff,
                              top_k=cfg.moe_top_k, act=cfg.moe_act,
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              name="moe")
            if cfg.moe_router_pre_attn:
                router_logits = moe.router_logits(y)
        y = Attention(cfg, self.mesh, self.rules, self.decode,
                      self.prefix_attend, name="attn")(
            y, cos, sin, positions, block_tables, pool, layer, live)
        if pool is not None:
            y, pool = y
        y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
        x = x + y
        y = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
        if moe is not None:
            y = moe(y, router_logits, live, moe_stacked, layer)
        elif cfg.moe_experts > 0:
            from ray_tpu.ops.moe import MoEMLP
            y = MoEMLP(cfg.moe_experts, cfg.moe_d_ff, top_k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor,
                       aux_loss_coef=cfg.moe_aux_coef,
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       name="moe")(y)
        else:
            y = MLP(cfg, name="mlp")(y)
        y = jax.ad_checkpoint.checkpoint_name(y, "mlp_out")
        x = x + y
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
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
    return logits.astype(jnp.float32)


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

    def _moe_stacked(self):
        """The scanned layer stack's dropless expert leaves ``[L, E, ...]``
        whole, for a decode-mode model (no gradient is taken through the
        kernel that reads them): they ride ``call_args`` into the scan
        body beside the layer index, as the KV pool rides the carry, so
        that no layer's experts are sliced out.  None where there is no
        such stack (no dropless experts, unrolled layers, initialising)."""
        cfg = self.cfg
        if not (self.decode and cfg.moe_dropless and cfg.moe_experts
                and cfg.scan_layers) or self.is_initializing():
            return None
        moe = nn.meta.unbox(self.variables["params"]["blocks"]["moe"])
        return moe["w_gate"], moe["w_up"], moe["w_down"]

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden: bool = False,
                 block_tables=None):
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
        if self.mesh is not None and not self.decode:
            x = with_sharding(self.mesh, x, ("batch", "seq", "act_embed"),
                              self.rules)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        if self.mesh is not None and not self.decode:
            # the rope tables are tiny closure constants: pin them
            # replicated so the partitioner never invents a sharding for
            # them (they otherwise surface as involuntarily
            # rematerialized fake_parameters)
            cos = with_sharding(self.mesh, cos, (None, None), self.rules)
            sin = with_sharding(self.mesh, sin, (None, None), self.rules)

        do_remat = cfg.remat and not self.decode
        n_remat = (cfg.n_layers if cfg.remat_layers is None
                   else max(0, min(cfg.remat_layers, cfg.n_layers)))
        block_kwargs = dict(mesh=self.mesh, rules=self.rules,
                            decode=self.decode,
                            prefix_attend=self.prefix_attend)
        call_args = (cos, sin, positions, block_tables,
                     self._moe_stacked())
        if self.decode and self.paged_pages:
            # the paged KV pool: ONE stacked leaf for the whole model,
            # K in [..., :hd], V in [..., hd:] (layout dictated by TPU
            # tiling, ops/paged_attention.py layout note).  It rides the
            # layer stack as loop-carried state with a layer index, so
            # each block writes its rows in place and no layer's pool is
            # ever sliced out, relaid or written back.
            ckv = self.variable(
                "cache", "kv_pages", jnp.zeros,
                (cfg.n_layers, self.paged_pages, cfg.n_kv_heads,
                 self.page_size, 2 * cfg.head_dim), cfg.dtype)
            x, pool = stack_layers(Block, cfg, block_kwargs, x, call_args,
                                   remat=False, carry=ckv.value)
            if not self.is_initializing():
                ckv.value = pool
        elif do_remat and 0 < n_remat < cfg.n_layers:
            # partial remat: the first n_remat layers recompute in the
            # backward pass, the tail stores activations (uses the HBM
            # headroom "policy" selection can't reach)
            x = stack_layers(Block, cfg, block_kwargs, x,
                             call_args, remat=True,
                             cache=True, n_layers=n_remat)
            x = stack_layers(Block, cfg, block_kwargs, x,
                             call_args, remat=False,
                             cache=True, name="blocks_tail",
                             n_layers=cfg.n_layers - n_remat,
                             first_layer=n_remat)
        else:
            x = stack_layers(Block, cfg, block_kwargs, x,
                             call_args, remat=do_remat,
                             cache=True)

        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if return_hidden:
            # memory-lean loss path: the caller projects per sequence
            # chunk (ops/losses.py chunked_lm_loss) so [B, S, vocab]
            # logits never materialize
            return x
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
        else:
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            dtype=cfg.dtype, param_dtype=cfg.param_dtype)(x)
        if self.mesh is not None and not self.decode:
            logits = with_sharding(self.mesh, logits,
                                   ("batch", "seq", "act_vocab"), self.rules)
        return logits.astype(jnp.float32)
