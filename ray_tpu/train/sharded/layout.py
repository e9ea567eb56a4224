"""GSPMD layout planner: ShardingConfig -> mesh + canonical PartitionSpecs.

The single mesh authority of the repo (docs/train_sharded.md).  A
:class:`ShardingConfig` names the parallelism degrees the way a user
thinks about them — dp / fsdp / cp / tp / pp — and :func:`plan` resolves
them against a device count into a :class:`LayoutPlan`: the mesh shape
(in :data:`ray_tpu.parallel.mesh.AXIS_ORDER`), the actual ``Mesh``, and
the canonical ``PartitionSpec`` table per parameter/activation class.

The spec table is *derived from* the same rule table
(:data:`ray_tpu.parallel.sharding.DEFAULT_RULES`) that
``make_sharded_train`` applies to the model's logical axis metadata, so
the planner's golden table and the shardings actually compiled into the
step cannot drift apart — the table is the contract, the rules are the
implementation.

``pp`` is the MPMD pipeline degree: pp>1 partitions *layers* onto stage
actors connected by compiled-DAG shm channels (pipeline.py), it is not a
mesh axis.  The SPMD GPipe 'stage' mesh axis
(parallel/pipeline.py spmd_pipeline) is requested with
``pp_style="spmd"`` instead, and ``slices>1`` pins the data axis across
a slice boundary (hierarchical DCN+ICI mesh).

This module also owns the per-train-loop mesh cache that used to live in
jax_trainer.py (``get_mesh`` / ``set_loop_mesh_shape`` re-export from
there for compatibility).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec
from ray_tpu.parallel.mesh import AXIS_ORDER
from ray_tpu.parallel.sharding import LOGICAL_RULES, MeshAxes, ShardingRules

_local = threading.local()

# parameter / activation classes -> the model's logical axes, the same
# names gpt.py hangs on params via nn.with_logical_partitioning.  The
# planner's table is these axes pushed through the rule table with
# size-1 mesh axes pruned — exactly what tree_mesh_shardings does to the
# abstract state in make_sharded_train.
PARAM_CLASSES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    ("token_embed", ("vocab", "embed")),
    ("attn_qkv", ("embed", "heads", "head_dim")),
    ("attn_kv", ("embed", "kv", "head_dim")),
    ("attn_out", ("heads_embed", "embed")),
    ("mlp_up", ("embed", "mlp")),
    ("mlp_down", ("mlp", "embed")),
    ("norm_scale", ("norm",)),
    ("lm_head", ("embed", "vocab")),
)
ACTIVATION_CLASSES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    ("batch_tokens", ("batch", None)),
    ("hidden", ("batch", "seq", "act_embed")),
    ("logits", ("batch", "seq", "act_vocab")),
)


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """Parallelism degrees for one training run.

    ``dp``/``fsdp``/``cp``/``tp`` are in-mesh axes (data, fsdp, context,
    tensor in AXIS_ORDER); exactly one may be ``-1`` to absorb remaining
    devices.  ``pp`` is the pipeline degree — MPMD stage actors by
    default (``pp_style="mpmd"``: *layers* split onto actors, the mesh
    below describes one stage's devices), or the SPMD GPipe 'stage'
    mesh axis with ``pp_style="spmd"``.  ``slices>1`` builds the mesh
    from an explicit device grid with the data axis outermost across the
    slice boundary (hierarchical DCN/ICI layout, cf. the 2-slice
    MULTICHIP dryrun).
    """

    dp: int = 1
    fsdp: int = 1
    cp: int = 1
    tp: int = 1
    pp: int = 1
    pp_style: str = "mpmd"          # "mpmd" (stage actors) | "spmd" (mesh axis)
    slices: int = 1

    def __post_init__(self):
        if self.pp_style not in ("mpmd", "spmd"):
            raise ValueError(f"pp_style must be mpmd|spmd, "
                             f"got {self.pp_style!r}")
        sizes = [self.dp, self.fsdp, self.cp, self.tp]
        if self.pp_style == "spmd":
            sizes.append(self.pp)
        elif self.pp < 1:
            raise ValueError("mpmd pp degree must be >= 1")
        if sum(1 for s in sizes if s == -1) > 1:
            raise ValueError("at most one mesh axis may be -1")
        for s in sizes:
            if s != -1 and s < 1:
                raise ValueError(f"axis sizes must be >= 1 or -1, got {s}")
        if self.slices < 1:
            raise ValueError("slices must be >= 1")

    def mesh_axes(self) -> Dict[str, int]:
        """Unresolved mesh axes in AXIS_ORDER (may still contain -1)."""
        shape = {"stage": self.pp if self.pp_style == "spmd" else 1,
                 "data": self.dp, "fsdp": self.fsdp,
                 "context": self.cp, "tensor": self.tp}
        assert tuple(shape) == AXIS_ORDER
        return shape

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Fill the -1 wildcard against ``n_devices`` (one stage's
        devices when pp_style="mpmd": callers pass devices-per-stage)."""
        shape = self.mesh_axes()
        names = list(shape)
        sizes = list(shape.values())
        wild = [i for i, v in enumerate(sizes) if v == -1]
        fixed = math.prod(v for v in sizes if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by {fixed}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {dict(zip(names, sizes))} needs {fixed} devices, "
                f"have {n_devices}")
        return dict(zip(names, sizes))


def _prune_axes(axes: MeshAxes, shape: Dict[str, int]) -> MeshAxes:
    """ShardingRules._prune against a *shape dict* (no Mesh needed, so
    golden tables never touch the backend)."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return axes if shape.get(axes, 1) > 1 else None
    kept = tuple(a for a in axes if shape.get(a, 1) > 1)
    return kept if len(kept) > 1 else (kept[0] if kept else None)


@dataclasses.dataclass(frozen=True)
class LayoutPlan:
    """A resolved layout: mesh shape + canonical spec tables + stage map."""

    config: ShardingConfig
    mesh_shape: Dict[str, int]       # resolved, AXIS_ORDER, per stage
    rules: ShardingRules = LOGICAL_RULES

    # -- spec tables ------------------------------------------------------
    def spec_for(self, logical_axes: Sequence[Optional[str]]
                 ) -> PartitionSpec:
        return PartitionSpec(*[
            _prune_axes(self.rules.to_mesh_axes(a), self.mesh_shape)
            if a is not None else None for a in logical_axes])

    def param_table(self) -> Dict[str, PartitionSpec]:
        return {name: self.spec_for(axes) for name, axes in PARAM_CLASSES}

    def activation_table(self) -> Dict[str, PartitionSpec]:
        return {name: self.spec_for(axes)
                for name, axes in ACTIVATION_CLASSES}

    # -- mesh authority ---------------------------------------------------
    def devices_per_stage(self, n_devices: Optional[int] = None) -> int:
        n = math.prod(self.mesh_shape.values())
        if n_devices is not None and n_devices != n * self.n_stages:
            raise ValueError(
                f"plan needs {n * self.n_stages} devices "
                f"({n}/stage x {self.n_stages} stages), have {n_devices}")
        return n

    def build_mesh(self, devices: Optional[Sequence[Any]] = None):
        """Build the (per-stage) jax Mesh.  ``slices>1`` reshapes an
        explicit grid so the slice boundary is pinned to the outermost
        non-trivial axis (data crosses DCN, fsdp/tensor stay on ICI)."""
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh

        shape = self.mesh_shape
        if devices is None:
            devices = jax.devices()[:math.prod(shape.values())]
        if self.config.slices > 1:
            names = [n for n in AXIS_ORDER if shape[n] > 1] or ["data"]
            if shape.get("data", 1) % self.config.slices:
                raise ValueError(
                    f"data axis {shape.get('data', 1)} not divisible by "
                    f"{self.config.slices} slices")
            grid = np.asarray(list(devices)).reshape(
                [shape[n] for n in names])
            return Mesh(grid, tuple(names))
        return build_mesh(
            MeshConfig(stage=shape["stage"], data=shape["data"],
                       fsdp=shape["fsdp"], context=shape["context"],
                       tensor=shape["tensor"]),
            devices=devices)

    # -- MPMD stage map ---------------------------------------------------
    @property
    def n_stages(self) -> int:
        return self.config.pp if self.config.pp_style == "mpmd" else 1

    def layer_ranges(self, n_layers: int) -> List[Tuple[int, int]]:
        """Contiguous [start, end) layer blocks per MPMD stage (remainder
        layers go to the *early* stages, which also carry the embed)."""
        stages = self.n_stages
        if n_layers < stages:
            raise ValueError(f"{n_layers} layers < {stages} stages")
        base, rem = divmod(n_layers, stages)
        ranges, start = [], 0
        for s in range(stages):
            end = start + base + (1 if s < rem else 0)
            ranges.append((start, end))
            start = end
        return ranges

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary (bench rows, dryrun prints)."""
        return {
            "mesh": {k: v for k, v in self.mesh_shape.items() if v > 1},
            "pp": self.config.pp, "pp_style": self.config.pp_style,
            "slices": self.config.slices,
            "params": {k: str(v) for k, v in self.param_table().items()},
        }


def plan(config: ShardingConfig,
         n_devices: Optional[int] = None,
         rules: ShardingRules = LOGICAL_RULES) -> LayoutPlan:
    """Resolve ``config`` into a LayoutPlan.  ``n_devices`` is the
    per-stage device count (defaults to this process's
    ``jax.device_count()``, only touched when a wildcard or validation
    needs it)."""
    if n_devices is None:
        axes = config.mesh_axes()
        if any(v == -1 for v in axes.values()):
            import jax
            n_devices = jax.device_count()
        else:
            n_devices = math.prod(axes.values())
    return LayoutPlan(config=config, mesh_shape=config.resolve(n_devices),
                      rules=rules)


# ---------------------------------------------------------------------------
# The per-train-loop mesh cache (absorbed from jax_trainer.get_mesh /
# set_loop_mesh_shape: JaxTrainer installs the ScalingConfig's mesh_shape
# here and user loops call get_mesh()).
# ---------------------------------------------------------------------------

def _shape_to_config(mesh_shape: Dict[str, int]) -> ShardingConfig:
    """Arbitrary {axis: size} dict -> ShardingConfig.  Unknown axis names
    are rejected — AXIS_ORDER is the vocabulary of the mesh authority."""
    alias = {"data": "dp", "fsdp": "fsdp", "context": "cp",
             "tensor": "tp", "stage": "pp"}
    kw: Dict[str, Any] = {}
    for name, size in mesh_shape.items():
        if name not in alias:
            raise ValueError(
                f"unknown mesh axis {name!r}; expected one of "
                f"{list(alias)} (AXIS_ORDER)")
        kw[alias[name]] = size
    if "pp" in kw:
        kw["pp_style"] = "spmd"
    return ShardingConfig(**kw)


def get_mesh(mesh_shape: Optional[Dict[str, int]] = None):
    """Build (and cache, per train-loop thread) the device mesh.

    Inside a JaxTrainer loop, reads the mesh shape from the trainer's
    ScalingConfig when not given explicitly.  Axis sizes of -1 absorb
    remaining devices.  This is THE mesh constructor: jax_trainer,
    the sharded executor and the MULTICHIP dryruns all resolve through
    the same :func:`plan`.
    """
    import jax

    from ray_tpu._private.config import CONFIG

    if mesh_shape is None:
        mesh_shape = getattr(_local, "mesh_shape", None) or {}
    cached = getattr(_local, "mesh", None)
    if cached is not None and getattr(_local, "mesh_shape",
                                      None) == mesh_shape:
        return cached

    n = jax.device_count()
    if not mesh_shape:
        mesh_shape = dict(CONFIG.mesh_default_axes) or {"data": n}
    if sum(1 for v in mesh_shape.values() if v == -1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    p = plan(_shape_to_config(dict(mesh_shape)), n_devices=n)
    # preserve the caller's axis subset AND order: a {"data": 2,
    # "fsdp": 4} request yields a 2-axis mesh, not a 5-axis one — the
    # planner resolves/validates, the mesh is built over the requested
    # names only
    resolved = {k: p.mesh_shape[k] for k in mesh_shape}
    mesh = _build_named_mesh(resolved, jax.devices()[:n])
    _local.mesh = mesh
    _local.mesh_shape = resolved
    return mesh


def _build_named_mesh(shape: Dict[str, int], devices):
    from jax.experimental import mesh_utils

    from jax.sharding import Mesh
    names, sizes = list(shape), tuple(shape.values())
    try:
        dev_array = mesh_utils.create_device_mesh(
            sizes, devices=list(devices), allow_split_physical_axes=True)
    except (ValueError, AssertionError, NotImplementedError, TypeError):
        import numpy as np
        dev_array = np.asarray(list(devices)).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def set_loop_mesh_shape(shape: Optional[Dict[str, int]]) -> None:
    _local.mesh_shape = shape
    _local.mesh = None


# ---------------------------------------------------------------------------
# MULTICHIP dryrun configs (folded from __graft_entry__: the dryruns now
# consume planner layouts instead of hand-factoring devices).
# ---------------------------------------------------------------------------

def dryrun_plans(n_devices: int) -> List[Tuple[str, LayoutPlan]]:
    """The named layout sweep the MULTICHIP dryrun exercises:

      - ``train``: dp x fsdp x cp x tp greedy factorization (each model
        axis takes a 2 while divisible, data absorbs the rest),
      - ``pipeline_spmd``: 2-stage SPMD GPipe mesh (even device counts),
      - ``moe_ep``: expert-parallel layout (experts over data axes),
      - ``hier_2slice``: 2-slice hierarchical mesh, data across the
        slice boundary (multiples of 4).
    """
    sizes = {"tp": 1, "cp": 1, "fsdp": 1}
    rem = n_devices
    for axis in ("tp", "cp", "fsdp"):
        if rem % 2 == 0:
            sizes[axis] = 2
            rem //= 2
    out = [("train", plan(ShardingConfig(dp=rem, fsdp=sizes["fsdp"],
                                         cp=sizes["cp"], tp=sizes["tp"]),
                          n_devices=n_devices))]
    if n_devices % 2 == 0:
        out.append(("pipeline_spmd",
                    plan(ShardingConfig(dp=-1, pp=2, pp_style="spmd"),
                         n_devices=n_devices)))
        out.append(("moe_ep", plan(ShardingConfig(dp=-1, fsdp=2),
                                   n_devices=n_devices)))
    if n_devices % 4 == 0:
        per_slice = n_devices // 2
        out.append(("hier_2slice",
                    plan(ShardingConfig(dp=2, fsdp=2, tp=per_slice // 2,
                                        slices=2),
                         n_devices=n_devices)))
    return out
