"""SPMD pipeline parallelism: GPipe microbatching over a ``stage`` mesh axis.

The reference has no pipeline engine (SURVEY.md §2.6: "Pipeline parallel:
absent").  TPU-first design: instead of stage *processes* exchanging
activations over a network (the GPU/NCCL shape of PP), every device runs the
same compiled program under ``shard_map``; layer parameters are sharded over
the ``stage`` axis (each stage holds L/n_stages layers), and activations hop
stage→stage via ``jax.lax.ppermute`` on ICI/DCN inside one ``lax.scan`` —
the classic weight-stationary SPMD pipeline.  The whole loop is
differentiable, so the backward pipeline (reverse ppermute order) falls out
of autodiff; no 1F1B scheduler to hand-write.

Schedule: plain GPipe.  ``n_microbatches`` chunks flow through
``n_stages + n_microbatches - 1`` ticks; bubble fraction is
``(n_stages-1)/(n_stages+n_microbatches-1)`` — pick microbatches ≥ 4× stages
to amortize.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _stage_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def spmd_pipeline(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any,
                  x: jax.Array,
                  *,
                  mesh: Mesh,
                  n_microbatches: int,
                  axis_name: str = "stage",
                  batch_axes=("data", "fsdp")) -> jax.Array:
    """Run ``stage_fn`` as an ``n_stages``-deep pipeline over microbatches.

    stage_params: pytree whose leaves have leading dim ``n_stages`` (stage i
    holds slice i); sharded over ``axis_name`` by this wrapper.
    x: global [B, ...] batch; B must divide into ``n_microbatches``.
    stage_fn(params_slice, microbatch) -> microbatch-shaped output; applied
    once per stage, so a transformer's blocks stack as
    [n_stages, layers_per_stage, ...] with an inner scan in ``stage_fn``.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible into {n_microbatches} "
                         "microbatches")
    mb = b // n_microbatches
    xs = x.reshape(n_microbatches, mb, *x.shape[1:])

    batch_axes = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    x_spec = P(None, batch_axes, *([None] * (x.ndim - 1)))
    param_specs = jax.tree.map(
        lambda p: P(axis_name, *([None] * (p.ndim - 1))), stage_params)
    perm = _stage_perm(n_stages)

    def local(params, xs_local):
        # leading stage dim is length-1 locally; peel it off
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis_name)
        m = xs_local.shape[0]
        state0 = jnp.zeros_like(xs_local[0])
        out0 = jnp.zeros_like(xs_local)

        def tick(carry, t):
            state, outbuf = carry
            # stage 0 ingests microbatch t (clamped; masked-out when t >= m)
            feed = xs_local[jnp.clip(t, 0, m - 1)]
            state = jnp.where(idx == 0, feed, state)
            out = stage_fn(params, state)
            # the last stage finished microbatch t-(n_stages-1) this tick
            done = t - (n_stages - 1)
            write = jnp.logical_and(idx == n_stages - 1, done >= 0)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf,
                jnp.where(write, out,
                          jax.lax.dynamic_index_in_dim(
                              outbuf, jnp.clip(done, 0, m - 1), 0,
                              keepdims=False)),
                jnp.clip(done, 0, m - 1), 0)
            state = jax.lax.ppermute(out, axis_name, perm)
            return (state, outbuf), None

        (_, outbuf), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(m + n_stages - 1))
        # only the last stage's buffer is real; broadcast it to every stage
        # so the out_spec can treat the result as stage-replicated
        outbuf = jnp.where(idx == n_stages - 1, outbuf, jnp.zeros_like(outbuf))
        return jax.lax.psum(outbuf, axis_name)

    out = _shard_map(local, mesh=mesh,
                        in_specs=(param_specs, x_spec),
                        out_specs=x_spec, check_vma=False)(stage_params, xs)
    return out.reshape(b, *out.shape[2:])


def pipelined_lm_forward(cfg, mesh: Mesh, variables: Any, tokens: jax.Array,
                         *, n_microbatches: int, rules=None) -> jax.Array:
    """GPT forward with the block stack pipelined over the ``stage`` axis.

    Reuses the GPT modules functionally: embedding and head run replicated
    across stages (they shard over fsdp/tensor as usual); the scanned block
    params [L, ...] are regrouped to [n_stages, L/n_stages, ...] and each
    stage scans its local layers.  Requires ``cfg.scan_layers`` (stacked
    block params) and ``cfg.n_layers % n_stages == 0``.
    """
    import flax.linen as nn
    from ray_tpu.models.gpt import GPT, Block, RMSNorm
    from ray_tpu.ops.layers import rope_frequencies
    from ray_tpu.parallel.sharding import LOGICAL_RULES

    rules = rules or LOGICAL_RULES
    n_stages = mesh.shape.get("stage", 1)
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible into "
                         f"{n_stages} stages")
    if not cfg.scan_layers:
        raise ValueError("pipelining needs scan_layers=True (stacked params)")
    params = nn.meta.unbox(variables["params"])
    block_params = params["blocks"]
    per_stage = cfg.n_layers // n_stages
    staged = jax.tree.map(
        lambda p: p.reshape(n_stages, per_stage, *p.shape[1:]), block_params)

    embed = params["embed"]
    x = jnp.take(embed, tokens, axis=0).astype(cfg.dtype)
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    block = Block(cfg, mesh=None, rules=rules)

    def stage_fn(stage_p, h):
        def layer(carry, p):
            return block.apply({"params": p}, carry, cos, sin), None
        h, _ = jax.lax.scan(layer, h, stage_p)
        return h

    x = spmd_pipeline(stage_fn, staged, x, mesh=mesh,
                      n_microbatches=n_microbatches)

    x = RMSNorm(cfg.norm_eps).apply({"params": params["final_norm"]}, x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
    else:
        head = params["lm_head"]["kernel"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    return logits.astype(jnp.float32)
