"""Gang executor: the sharded-training flagship loop.

End-to-end wiring of the planes the repo has been building
(docs/train_sharded.md):

  - gang spawn through :class:`~ray_tpu.train.worker_group.WorkerGroup`
    + ``jax.distributed`` bootstrap (JaxConfig),
  - the layout planner's mesh/specs compiled into a SPLIT train step —
    ``grad_fn`` (jitted fwd+bwd) / host-plane
    ``sync_gradients(quantize="int8", async_op=True)`` / ``apply_fn``
    (jitted optimizer, donated state) — so cross-runtime data
    parallelism rides the DCN collective plane while fsdp/tp stay
    compiled into the step,
  - ICI-mesh registration with the PR 16 topology schedule when the
    gang shares one jax.distributed runtime,
  - sharded checkpoints through the object-transfer plane: each rank
    puts its leaf partition, refs land in the GCS KV, restore stripes
    the partitions back in and walks a fallback chain when shards died
    with a node.

Elasticity is inherited from DataParallelTrainer's gang recovery
(docs/fault_tolerance.md): a preempted node fails the incarnation, the
driver harvests the newest checkpoint and restarts the gang; lost work
is bounded by ``checkpoint_interval`` (+1 interval per checkpoint lost
to an ungraceful kill, see CONFIG.sharded_ckpt_keep).
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.train.sharded.layout import ShardingConfig
from ray_tpu.train.step import make_grad_apply_step

_KV_PREFIX = "shardckpt"


# ---------------------------------------------------------------------------
# sharded checkpoints over the object-transfer plane
# ---------------------------------------------------------------------------

def _kv_key(tag: str, step: int, rank) -> str:
    return f"{_KV_PREFIX}/{tag}/{step}/{rank}"


def _gcs():
    from ray_tpu.runtime import core_worker as cw
    return cw.get_global_worker().gcs


def save_sharded_checkpoint(state, *, tag: str, step: int, rank: int,
                            world: int, keep_alive: List[Any]) -> None:
    """Put this rank's leaf partition and register the ref in the GCS KV.

    The state's flat leaves are partitioned round-robin across ranks
    (leaf i belongs to rank ``i % world``), so checkpoint bytes spread
    ~evenly over the gang's nodes and a restore stripes from every node
    at once.  ``keep_alive`` must outlive the checkpoint's usefulness:
    dropping the ref frees the shard (owner refcount).
    """
    import jax
    import numpy as np

    import ray_tpu

    leaves = jax.tree_util.tree_leaves(state)
    mine = {i: np.asarray(leaf) for i, leaf in enumerate(leaves)
            if i % world == rank}
    ref = ray_tpu.put({"step": step, "rank": rank, "leaves": mine})
    keep_alive.append(ref)
    from ray_tpu.runtime import core_worker as cw
    node = cw.get_global_worker().node_id
    _gcs().kv_put(_kv_key(tag, step, rank),
                  pickle.dumps({"ref": ref, "node": node,
                                "n_leaves": len(leaves)}))


def make_checkpoint_meta(*, tag: str, step: int, world: int,
                         chain: List[int]) -> Dict[str, Any]:
    """The rank-0 report checkpoint: no tensor bytes, just the KV
    coordinates plus the fallback chain of earlier checkpointed steps
    (newest first)."""
    return {"kind": "sharded_kv", "tag": tag, "step": step,
            "world": world, "chain": list(chain)}


class ShardRestoreError(RuntimeError):
    """Every checkpoint in the chain had at least one unrecoverable
    shard."""


def restore_sharded_checkpoint(meta: Dict[str, Any], state):
    """Rebuild ``state`` from a sharded checkpoint, walking the chain.

    Pulls every rank's partition (striped, multi-source: each shard
    lives on whichever node put or inherited it — the PR 5 pull engine
    and the PR 15 evacuation/orphan-fetch paths do the finding),
    reassembles the flat leaf list, and device_puts each leaf with the
    live state's sharding.  Returns ``(state, step)``; falls back one
    chain entry per missing shard set.
    """
    import jax

    import ray_tpu
    from ray_tpu._private.config import CONFIG

    tag, world = meta["tag"], meta["world"]
    treedef = jax.tree_util.tree_structure(state)
    shardings = [x.sharding for x in jax.tree_util.tree_leaves(state)]
    gcs = _gcs()
    errors = []
    for step in meta["chain"]:
        try:
            parts = []
            for rank in range(world):
                raw = gcs.kv_get(_kv_key(tag, step, rank))
                if raw is None:
                    raise ShardRestoreError(
                        f"step {step}: no KV entry for rank {rank}")
                parts.append(pickle.loads(raw))
            payloads = ray_tpu.get(
                [p["ref"] for p in parts],
                timeout=CONFIG.sharded_ckpt_pull_timeout_s)
            leaves_np: Dict[int, Any] = {}
            for payload in payloads:
                leaves_np.update(payload["leaves"])
            n = parts[0]["n_leaves"]
            if sorted(leaves_np) != list(range(n)):
                raise ShardRestoreError(
                    f"step {step}: leaf partitions incomplete "
                    f"({len(leaves_np)}/{n})")
            leaves = [jax.device_put(leaves_np[i], shardings[i])
                      for i in range(n)]
            return jax.tree_util.tree_unflatten(treedef, leaves), step
        except Exception as e:  # noqa: BLE001 — walk the chain
            errors.append(f"step {step}: {type(e).__name__}: {e}")
    raise ShardRestoreError(
        "no checkpoint in the chain was restorable: " + "; ".join(errors))


# ---------------------------------------------------------------------------
# ICI registration (PR 16 topology schedule)
# ---------------------------------------------------------------------------

def maybe_register_ici(mesh, *, axis: str = "data",
                       group_name: Optional[str] = None) -> bool:
    """Register the gang's mesh with the collective topology schedule
    when the contract holds: a multi-process jax runtime where every
    process holds exactly one local device on ``axis`` (then the
    intra-slice level of the hierarchical allreduce folds into one
    in-graph psum — docs/collective.md).  Returns whether registration
    happened; separate-runtime gangs (each worker its own device world)
    decline, their cross-worker reduction IS the host ring."""
    import jax

    from ray_tpu._private.config import CONFIG
    from ray_tpu.util import collective as col

    group_name = group_name or os.environ.get(
        "RAY_TPU_TRAIN_COLLECTIVE_GROUP", "")
    if not group_name or not col.is_group_initialized(group_name):
        return False
    if not CONFIG.collective_topology:
        return False
    if jax.process_count() <= 1 or mesh.shape.get(axis, 1) <= 1:
        return False
    # the in-graph reducer assembles a global array from ONE local
    # shard, so the contract is exactly one addressable device in the
    # mesh per process (collective.register_ici_mesh)
    local = [d for d in mesh.devices.flat
             if d.process_index == jax.process_index()]
    if len(local) != 1:
        return False
    col.register_ici_mesh(mesh, axis=axis, group_name=group_name)
    return True


# ---------------------------------------------------------------------------
# the canned sharded train loop + trainer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedRunConfig:
    """Everything the gang loop needs, picklable into train_loop_config."""

    sharding: ShardingConfig = dataclasses.field(
        default_factory=ShardingConfig)
    model: str = "tiny"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    num_workers: int = 2
    steps: int = 8
    batch_per_worker: int = 4
    seq_len: int = 64
    checkpoint_interval: int = 2
    quantize: Optional[str] = "int8"
    async_grad_sync: bool = True
    register_ici: bool = True
    learning_rate: float = 1e-3
    optimizer: str = "adamw"
    seed: int = 0
    # slow-step throttle for chaos tests (seconds of host sleep per
    # step), so an injected preemption reliably lands mid-run
    step_sleep_s: float = 0.0
    # leave one GCS-KV breadcrumb per executed (rank, step, pid): the
    # chaos test and the bench's preemption leg count re-executed steps
    # exactly (lost work <= checkpoint_interval)
    kv_breadcrumbs: bool = False
    # per-worker peak FLOPs for the goodput ledger's MFU column
    # (0 = unknown: the ledger reports time buckets only)
    peak_flops: float = 0.0


def _synth_batch(cfg, vocab: int, rank: int, step: int):
    """Deterministic per-(rank, step) token batch: DP ranks see disjoint
    streams, a re-executed step sees identical data (exactly-once
    semantics for the chaos test's loss bookkeeping)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(
        (cfg.seed * 1_000_003 + step) * 65_537 + rank)
    return {"tokens": jnp.asarray(
        rng.integers(0, vocab, (cfg.batch_per_worker, cfg.seq_len + 1)),
        jnp.int32)}


def build_step(cfg: ShardedRunConfig, mesh, example_batch):
    """``(init_fn, grad_fn, apply_fn)`` of the run's model and optimizer
    compiled for ``mesh`` — the gang loop's step, also what a reference
    run on another mesh (chip_smoke.py's one-device comparison) builds
    so both sides share model, optimizer and schedule."""
    from ray_tpu.models import GPT, get_config
    from ray_tpu.train.step import OptimizerConfig

    model = GPT(get_config(cfg.model, **cfg.model_overrides), mesh=mesh)
    opt = OptimizerConfig(learning_rate=cfg.learning_rate,
                          warmup_steps=1, decay_steps=max(10, cfg.steps),
                          optimizer=cfg.optimizer)
    return make_grad_apply_step(model, mesh, opt,
                                example_batch=example_batch)[:3]


def _run_summary(grad_fn, state, batch, mesh, losses, step_s,
                 compile_clock) -> Dict[str, Any]:
    """What this worker ran on, from the worker itself: device facts,
    whether the lowered step holds the Pallas kernel (``tpu_custom_call``
    — absent under the interpreter and under xla attention), how the
    parameters are laid over the mesh's devices, and per-device memory."""
    import jax

    from ray_tpu._private.compile_cache import process_facts
    leaves = jax.tree_util.tree_leaves(state.params)
    mem = [(d.memory_stats() or {}).get("bytes_in_use")
           for d in mesh.devices.flat]
    return {
        **process_facts(compile_clock),
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "pallas_custom_call":
            "tpu_custom_call" in grad_fn.lower(state, batch).as_text(),
        "losses": list(losses),
        "step_s": [round(t, 4) for t in step_s],
        "n_params": len(leaves),
        "min_devices_per_param": min(
            len({s.device.id for s in x.addressable_shards})
            for x in leaves),
        "partitioned_params": sum(
            len({str(s.index) for s in x.addressable_shards}) > 1
            for x in leaves),
        "bytes_in_use": mem,
    }


def sharded_train_loop(config: Dict[str, Any]):
    """The per-worker gang loop (module-level: workers import it)."""
    import time

    import jax
    import numpy as np

    from ray_tpu._private import step_stats
    from ray_tpu.air import session
    from ray_tpu.air.checkpoint import Checkpoint
    from ray_tpu.models import get_config
    from ray_tpu.train.jax_trainer import sync_gradients
    from ray_tpu.train.sharded import layout

    from ray_tpu._private.compile_cache import start_compile_clock
    compile_clock = start_compile_clock()
    cfg: ShardedRunConfig = config["run"]
    rank = session.get_world_rank()
    world = session.get_world_size()
    tag = config.get("tag") or session.get_trial_id() or "sharded"

    plan = layout.plan(cfg.sharding, n_devices=jax.device_count()
                       if jax.process_count() == 1 else None)
    mesh = plan.build_mesh()
    model_cfg = get_config(cfg.model, **cfg.model_overrides)
    step_stats.set_model_info(
        flops_per_token=model_cfg.train_flops_per_token(cfg.seq_len),
        peak_flops=cfg.peak_flops or None,
        tokens_per_step=cfg.batch_per_worker * cfg.seq_len)

    batch = _synth_batch(cfg, model_cfg.vocab_size, rank, 0)
    init_fn, grad_fn, apply_fn = build_step(cfg, mesh, batch)
    # same init seed on every DP rank: replicas must start identical,
    # divergence is what sync_gradients prevents
    state = init_fn(jax.random.PRNGKey(cfg.seed), batch)

    if cfg.register_ici:
        registered = maybe_register_ici(mesh)
    else:
        registered = False

    start_step = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        meta = ckpt.to_dict()
        if meta.get("kind") == "sharded_kv":
            state, start_step = restore_sharded_checkpoint(meta, state)
            start_step += 1

    clock = step_stats.step_clock()
    loss = float("nan")
    summary: Optional[Dict[str, Any]] = None
    losses: List[float] = []
    step_s: List[float] = []
    keep_alive: List[Any] = []
    chain: List[int] = list(
        (ckpt.to_dict().get("chain") if ckpt is not None else None) or [])
    from ray_tpu._private.config import CONFIG
    keep = max(1, int(CONFIG.sharded_ckpt_keep))

    for step in range(start_step, cfg.steps):
        if cfg.kv_breadcrumbs:
            _gcs().kv_put(f"shardsteps/{tag}/{rank}/{step}/{os.getpid()}",
                          b"1")
        t_step = time.perf_counter()
        # Nothing here fences the device: the phases are what the HOST
        # does, leaves that do not overlap.  The one wait for the device
        # is ``loss_fetch`` (in a gang also ``grad_sync``, which copies
        # the gradients to the host); device time per program is in the
        # profiler's trace (programs ``train_grad`` / ``train_apply``).
        clock.begin()
        with clock.phase("batch"):
            step_batch = _synth_batch(cfg, model_cfg.vocab_size, rank, step)
        with clock.phase("grad_dispatch"):
            grads, metrics = grad_fn(state, step_batch)
        with clock.phase("grad_sync"):
            # async: issue the bucketed ring, then prepare the next
            # batch while it runs (the overlap PendingSync.wait, which
            # records its blocked part as ``grad_allreduce``, fences)
            synced = sync_gradients(grads, quantize=cfg.quantize,
                                    async_op=cfg.async_grad_sync)
        if cfg.async_grad_sync:
            with clock.phase("batch"):
                next_batch = _synth_batch(cfg, model_cfg.vocab_size, rank,
                                          step + 1)
                del next_batch  # prefetch: generation cost is the point
            with clock.phase("grad_sync"):
                synced = synced.wait()
        with clock.phase("apply_dispatch"):
            state = apply_fn(state, synced)
        if cfg.step_sleep_s:
            time.sleep(cfg.step_sleep_s)
        with clock.phase("loss_fetch"):
            loss = float(metrics["loss"])
        losses.append(loss)
        step_s.append(time.perf_counter() - t_step)
        out = {"step": step, "loss": loss, "rank": rank,
               "ici_registered": registered}
        report_ckpt = None
        if (step + 1) % cfg.checkpoint_interval == 0 \
                or step == cfg.steps - 1:
            with clock.phase("checkpoint"):
                save_sharded_checkpoint(state, tag=tag, step=step,
                                        rank=rank, world=world,
                                        keep_alive=keep_alive)
            chain.insert(0, step)
            del chain[keep:]
            del keep_alive[:-keep]
            if rank == 0:
                report_ckpt = Checkpoint.from_dict(make_checkpoint_meta(
                    tag=tag, step=step, world=world, chain=chain))
        with clock.phase("report"):
            if step == cfg.steps - 1:
                # the run's facts ride the last report, which is what
                # Result.metrics keeps: which device really ran the steps
                summary = out["summary"] = _run_summary(
                    grad_fn, state, batch, mesh, losses, step_s,
                    compile_clock)
            session.report(out, checkpoint=report_ckpt)
        clock.end()
    return {"final_loss": loss, "steps": cfg.steps,
            "ici_registered": registered, "summary": summary}


def tpu_lease_per_worker(num_workers: int) -> Optional[Dict[str, float]]:
    """What each gang worker must lease on this cluster so its steps run
    on the chips: ``{"TPU": <chips of one host>}``, or None when no
    alive node advertises a TPU (CPU clusters: tests, chipless hosts).

    A worker with no TPU lease is pinned to the CPU backend by the
    raylet, so on a TPU cluster "no lease" would silently train on the
    host.  One worker owns ALL chips of its host (libtpu: one process
    per host), hence more workers than TPU hosts cannot be placed and is
    an error here, not a hang in the placement group."""
    import ray_tpu

    hosts = [n["resources"].get("TPU", 0) for n in ray_tpu.nodes()
             if n["alive"] and n["resources"].get("TPU", 0) > 0]
    if not hosts:
        return None
    if num_workers > len(hosts):
        raise ValueError(
            f"ShardedTrainer: num_workers={num_workers} but the cluster "
            f"has {len(hosts)} TPU host(s).  A TPU worker holds every "
            "chip of its host until it exits, so several TPU worker "
            "processes on one host are unsupported "
            "(docs/train_sharded.md, chip ownership): use one worker "
            "per host and shard over its chips with ShardingConfig, or "
            "pass resources_per_worker= to train on the CPU")
    return {"TPU": float(min(hosts))}


class ShardedTrainer:
    """Driver-side front end: a DataParallelTrainer running
    :func:`sharded_train_loop` under a JaxConfig, with the planner's
    config threaded through.  ``fit()`` returns the underlying trainer's
    Result (gang recovery included).

    ``resources_per_worker=None`` (the default) leases the host's chips
    for each worker when the cluster has any
    (:func:`tpu_lease_per_worker`), decided at ``fit()``."""

    def __init__(self, run: ShardedRunConfig, *,
                 run_config=None, jax_config=None,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 tag: Optional[str] = None,
                 resume_from_checkpoint=None):
        from ray_tpu.air.config import ScalingConfig
        from ray_tpu.train.base_trainer import DataParallelTrainer
        from ray_tpu.train.jax_trainer import JaxConfig

        self.run = run
        self._scaling = ScalingConfig(
            num_workers=run.num_workers,
            resources_per_worker=resources_per_worker)
        self._trainer = DataParallelTrainer(
            sharded_train_loop,
            train_loop_config={"run": run, "tag": tag},
            backend_config=jax_config or JaxConfig(init_distributed=False),
            scaling_config=self._scaling,
            run_config=run_config,
            resume_from_checkpoint=resume_from_checkpoint)

    def fit(self):
        if self._scaling.resources_per_worker is None:
            self._scaling.resources_per_worker = tpu_lease_per_worker(
                self.run.num_workers)
        return self._trainer.fit()
