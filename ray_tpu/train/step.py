"""Sharded train-state construction and the pjit train step.

This is the compute heart of JaxTrainer: where the reference's
DataParallelTrainer wires torch DDP around a user loop
(/root/reference/python/ray/train/data_parallel_trainer.py:329,
torch/config.py:29 — NCCL process groups), here the *entire* parallelism
strategy (DP/FSDP/TP/CP) is carried by shardings on one jitted step function
and XLA emits the ICI/DCN collectives.

Flow:
  1. ``jax.eval_shape`` the state constructor with params still boxed in
     ``nn.Partitioned`` metadata (optax state inherits the boxes),
  2. read logical PartitionSpecs off the abstract tree, map them through the
     rule table to mesh axes,
  3. jit the constructor with ``out_shardings`` so parameters are *born
     sharded* (no host-memory spike, no broadcast), and
  4. jit the step with donated state for in-place buffers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state as flax_train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.ops.losses import chunked_lm_loss, softmax_cross_entropy
from ray_tpu.parallel.sharding import (LOGICAL_RULES, ShardingRules,
                                       logical_spec, tree_mesh_shardings)

TrainState = flax_train_state.TrainState


def _decay_mask(params: Any) -> Any:
    """Weight-decay only matmul kernels / embeddings, by parameter *name* —
    ndim is unreliable once nn.scan stacks per-layer 1-D norm scales to 2-D."""
    def fn(path, _):
        keys = {k.key for k in path if hasattr(k, "key")}
        return bool(keys & {"kernel", "embed"})
    return jax.tree_util.tree_map_with_path(fn, params)


@dataclasses.dataclass
class OptimizerConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    decay_steps: int = 10000
    min_lr_ratio: float = 0.1
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    accum_steps: int = 1
    # "bfloat16" halves the first-moment buffer — the standard memory-lean
    # setting for fitting bigger models per chip (second moment stays fp32)
    mu_dtype: str = "float32"
    # "adafactor" replaces AdamW's two full-size moments with factored
    # row/col statistics (Shazeer & Stern) — the TPU-native memory-lean
    # optimizer (T5 heritage) that fits ~1B params on a 16 GiB chip
    optimizer: str = "adamw"

    def make(self) -> optax.GradientTransformation:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.learning_rate, self.warmup_steps,
            max(self.decay_steps, self.warmup_steps + 1),
            self.learning_rate * self.min_lr_ratio)
        if self.optimizer == "adafactor":
            # optax applies adafactor's weight_decay_rate as a RAW per-step
            # multiplicative decay (not lr-scaled, unlike adamw's decoupled
            # decay): passing 0.1 would shrink kernels by 10% per step and
            # collapse the model.  Approximate decoupled decay with
            # lr * weight_decay, the AdamW-equivalent magnitude at peak lr.
            decay = (self.weight_decay * self.learning_rate
                     if self.weight_decay else None)
            tx = optax.chain(
                optax.clip_by_global_norm(self.grad_clip),
                optax.adafactor(schedule, min_dim_size_to_factor=128,
                                weight_decay_rate=decay,
                                weight_decay_mask=_decay_mask),
            )
            if self.accum_steps > 1:
                tx = optax.MultiSteps(tx, self.accum_steps)
            return tx
        tx = optax.chain(
            optax.clip_by_global_norm(self.grad_clip),
            optax.adamw(schedule, b1=self.b1, b2=self.b2,
                        weight_decay=self.weight_decay,
                        mu_dtype=self.mu_dtype,
                        mask=_decay_mask),
        )
        if self.accum_steps > 1:
            tx = optax.MultiSteps(tx, self.accum_steps)
        return tx


def _lm_loss_body(batch: Dict[str, jax.Array],
                  head: Callable) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Shared next-token plumbing: slice tokens/mask, run the model via
    ``head(inputs, mask, targets) -> (loss, denom, mutated)``, thread the
    MoE routers' sown aux losses (ray_tpu/ops/moe.py) into the total."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
    loss, denom, mutated = head(inputs, mask, targets)
    metrics = {"loss": loss, "tokens": denom}
    aux_leaves = [jnp.sum(a) for path, a in jax.tree_util.tree_leaves_with_path(
        mutated.get("intermediates", {})) if "moe_aux_loss" in str(path)]
    if aux_leaves:
        aux = sum(aux_leaves)
        loss = loss + aux
        metrics["moe_aux_loss"] = aux
        metrics["loss"] = loss
    return loss, metrics


def lm_loss_fn(apply_fn: Callable, params: Any, batch: Dict[str, jax.Array],
               z_loss: float = 0.0
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token LM loss. batch: {"tokens": [B, S+1] or [B, S], "mask"?}."""
    def head(inputs, mask, targets):
        logits, mutated = apply_fn({"params": params}, inputs,
                                   mutable=["intermediates"])
        loss, denom = softmax_cross_entropy(logits, targets, mask, z_loss)
        return loss, denom, mutated

    return _lm_loss_body(batch, head)


def lm_loss_chunked_fn(apply_fn: Callable, params: Any,
                       batch: Dict[str, jax.Array],
                       z_loss: float = 0.0,
                       chunk_size: int = 256,
                       head_weight: Optional[Callable] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token LM loss with the chunked projection head
    (ops/losses.py chunked_lm_loss): the logits tensor's peak HBM drops
    by ~S/chunk_size, enabling larger per-chip batches. Same batch
    contract as lm_loss_fn; the model must support
    ``apply(..., return_hidden=True)`` (GPT does).

    ``head_weight(params) -> (weight, transpose_weight)`` selects the
    projection weight. The default follows GPT's naming — an untied
    ``lm_head`` Dense, else the tied ``embed`` table — and raises for
    models that match neither; pass an explicit selector (e.g. via
    functools.partial) for other architectures.
    """
    def head(inputs, mask, targets):
        hidden, mutated = apply_fn({"params": params}, inputs,
                                   mutable=["intermediates"],
                                   return_hidden=True)
        raw = nn.meta.unbox(params)
        if head_weight is not None:
            weight, transpose = head_weight(raw)
        elif "lm_head" in raw:
            weight, transpose = raw["lm_head"]["kernel"], False
        elif "embed" in raw:
            weight, transpose = raw["embed"], True
        else:
            raise ValueError(
                "lm_loss_chunked_fn could not find the projection head "
                "(no 'lm_head' or 'embed' in params); pass head_weight=")
        loss, denom = chunked_lm_loss(hidden, weight, targets, mask,
                                      z_loss, chunk_size,
                                      transpose_weight=transpose)
        return loss, denom, mutated

    return _lm_loss_body(batch, head)


def trace_state_shardings(build_state, example_batch, mesh: Mesh,
                          rules: ShardingRules, batch_axes=("batch",)):
    """Trace the state abstractly and map its logical PartitionSpecs to
    mesh shardings.  Returns (state_shardings, batch_sharding) — the
    contract both the fused and the split step below build on."""
    if example_batch is None:
        raise ValueError("example_batch is required to trace shapes")
    abstract = jax.eval_shape(build_state, jax.random.PRNGKey(0),
                              example_batch)
    logical = nn.get_partition_spec(abstract)
    state_shardings = tree_mesh_shardings(logical, mesh, rules)

    # optimizer states that don't mirror the param's shape (adafactor's
    # factored v_row/v_col, scalar counters) still inherit the param's
    # logical spec from the boxed metadata; a spec longer than the leaf's
    # rank is invalid — replicate those
    def _fit_rank(sh, leaf):
        ndim = getattr(leaf, "ndim", None)
        if ndim is not None and hasattr(sh, "spec") and len(sh.spec) > ndim:
            return NamedSharding(mesh, PartitionSpec())
        return sh

    state_shardings = jax.tree.map(_fit_rank, state_shardings,
                                   nn.meta.unbox(abstract))
    batch_sharding = jax.tree.map(
        lambda _: NamedSharding(mesh, logical_spec(batch_axes, mesh, rules)),
        example_batch)
    return state_shardings, batch_sharding


def _born_sharded(build_state, step, example_batch, mesh: Mesh,
                  rules: ShardingRules, batch_axes=("batch",)):
    """Shared construction: trace the state abstractly, read logical
    PartitionSpecs, jit init (born sharded) and step (donated state).
    The two functions' own names (``train_init``, ``train_step``) are
    the programs' names in a profiler trace (``jit_<name>`` on the
    device's ``XLA Modules`` line)."""
    state_shardings, batch_sharding = trace_state_shardings(
        build_state, example_batch, mesh, rules, batch_axes)
    repl = NamedSharding(mesh, PartitionSpec())
    init_fn = jax.jit(build_state, out_shardings=state_shardings)
    step_fn = jax.jit(
        step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=(state_shardings, repl),
        donate_argnums=(0,),
    )
    return init_fn, step_fn, state_shardings, batch_sharding


def _lm_train_parts(model: nn.Module, optimizer: Optional[OptimizerConfig],
                    loss_fn: Callable, z_loss: Optional[float],
                    init_inputs: Optional[Callable]):
    """What the fused and the split step are both built on:
    ``train_init(rng, batch) -> TrainState`` and
    ``loss_and_grads(state, batch) -> ((loss, metrics), grads)``.
    ``z_loss=None`` takes the model config's."""
    tx = (optimizer or OptimizerConfig()).make()
    if z_loss is None:
        z_loss = getattr(getattr(model, "cfg", None), "z_loss", 0.0)

    def train_init(rng, batch) -> TrainState:
        if init_inputs is not None:
            variables = model.init(rng, *init_inputs(batch))
        else:
            variables = model.init(rng, batch["tokens"][:, :-1])
        return TrainState.create(apply_fn=model.apply,
                                 params=variables["params"], tx=tx)

    def loss_and_grads(state: TrainState, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(state.apply_fn, p, batch, z_loss),
            has_aux=True)(state.params)

    return train_init, loss_and_grads


def make_sharded_train(model: nn.Module,
                       mesh: Mesh,
                       optimizer: Optional[OptimizerConfig] = None,
                       rules: ShardingRules = LOGICAL_RULES,
                       loss_fn: Callable = lm_loss_fn,
                       example_batch: Optional[Dict[str, jax.Array]] = None,
                       z_loss: Optional[float] = None,
                       init_inputs: Optional[Callable] = None):
    """The fused step: one jit.  Returns (init_fn, step_fn,
    state_shardings, batch_sharding).

    ``init_fn(rng, batch) -> TrainState`` born sharded over ``mesh``;
    ``step_fn(state, batch) -> (state, metrics)`` jitted with donated state.
    ``init_inputs(batch) -> args tuple`` overrides how model.init is called
    (default: next-token LM convention, ``batch["tokens"][:, :-1]``).
    """
    train_init, loss_and_grads = _lm_train_parts(
        model, optimizer, loss_fn, z_loss, init_inputs)

    def train_step(state: TrainState, batch
                   ) -> Tuple[TrainState, Dict[str, Any]]:
        (loss, metrics), grads = loss_and_grads(state, batch)
        new_state = state.apply_gradients(grads=grads)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    return _born_sharded(train_init, train_step, example_batch, mesh, rules,
                         batch_axes=("batch", None))


def make_grad_apply_step(model: nn.Module,
                         mesh: Mesh,
                         optimizer: Optional[OptimizerConfig] = None,
                         rules: ShardingRules = LOGICAL_RULES,
                         loss_fn: Callable = lm_loss_fn,
                         example_batch: Optional[Dict[str, jax.Array]] = None,
                         z_loss: Optional[float] = None,
                         init_inputs: Optional[Callable] = None):
    """The split step: :func:`make_sharded_train`'s arguments, two jits.
    Returns ``(init_fn, grad_fn, apply_fn, state_shardings,
    batch_sharding)``:

      - ``grad_fn(state, batch) -> (grads, metrics)`` — jitted forward +
        backward, grads land in the params' shardings,
      - ``apply_fn(state, grads) -> state`` — jitted optimizer update
        with donated state.

    The split exists so a *host-plane* reduction can run between the
    two (the gang loop, train/sharded/executor.py): ``sync_gradients``
    sees materialized per-rank gradients, and with ``async_op=True`` the
    ring overlaps the host-side work between issue and fence.  The fused
    step stays the right call when the reduction is compiled into the
    graph instead.
    """
    train_init, loss_and_grads = _lm_train_parts(
        model, optimizer, loss_fn, z_loss, init_inputs)
    state_shardings, batch_sharding = trace_state_shardings(
        train_init, example_batch, mesh, rules, batch_axes=("batch", None))
    param_shardings = state_shardings.params
    repl = NamedSharding(mesh, PartitionSpec())

    # like ``train_init``, the two functions' names are the programs'
    # names in a profiler trace (``jit_train_grad``, ``jit_train_apply``)
    def train_grad(state, batch):
        (loss, metrics), grads = loss_and_grads(state, batch)
        return grads, dict(metrics)

    def train_apply(state, grads):
        return state.apply_gradients(grads=grads)

    init_fn = jax.jit(train_init, out_shardings=state_shardings)
    grad_fn = jax.jit(train_grad,
                      in_shardings=(state_shardings, batch_sharding),
                      out_shardings=(param_shardings, repl))
    apply_fn = jax.jit(train_apply,
                       in_shardings=(state_shardings, param_shardings),
                       out_shardings=state_shardings,
                       donate_argnums=(0,))
    return init_fn, grad_fn, apply_fn, state_shardings, batch_sharding


def classification_loss_fn(logits: jax.Array, labels: jax.Array
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Softmax CE + accuracy for label classification (vision models)."""
    one_hot = jax.nn.one_hot(labels, logits.shape[-1])
    loss = jnp.mean(optax.softmax_cross_entropy(logits, one_hot))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": acc}


class TrainStateBN(TrainState):
    """TrainState plus mutable normalization statistics (BatchNorm)."""

    batch_stats: Any = None


def make_vision_train(model: nn.Module,
                      mesh: Mesh,
                      optimizer: Optional[OptimizerConfig] = None,
                      rules: ShardingRules = LOGICAL_RULES,
                      example_batch: Optional[Dict[str, jax.Array]] = None):
    """make_sharded_train for image classifiers with BatchNorm state.

    batch: {"image": [B, H, W, C], "label": [B]}.  Same born-sharded
    construction as make_sharded_train; the step threads ``batch_stats``
    through the jitted update (cf. flax imagenet example semantics, built
    on this repo's sharding rules).
    """
    optimizer = optimizer or OptimizerConfig()
    tx = optimizer.make()
    if example_batch is None:
        raise ValueError("example_batch is required to trace shapes")

    def train_init(rng, batch) -> TrainStateBN:
        variables = model.init(rng, batch["image"])
        return TrainStateBN.create(
            apply_fn=model.apply, params=variables["params"], tx=tx,
            batch_stats=variables.get("batch_stats", {}))

    def train_step(state: TrainStateBN, batch):
        def lf(p):
            logits, mutated = state.apply_fn(
                {"params": p, "batch_stats": state.batch_stats},
                batch["image"], mutable=["batch_stats"])
            loss, metrics = classification_loss_fn(logits, batch["label"])
            return loss, (metrics, mutated.get("batch_stats", {}))

        (loss, (metrics, new_stats)), grads = jax.value_and_grad(
            lf, has_aux=True)(state.params)
        new_state = state.apply_gradients(grads=grads).replace(
            batch_stats=new_stats)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    # batch leaves have mixed rank (image rank-4, label rank-1): shard dim 0
    # only, trailing dims stay unsharded implicitly
    return _born_sharded(train_init, train_step, example_batch, mesh, rules,
                         batch_axes=("batch",))
