"""MPMD pipeline runner: pp stage actors compiled into one DAG over shm
channels (docs/train_sharded.md, docs/compiled_dag.md).

The pp > 1 ``pp_style="mpmd"`` execution path of the sharded subsystem:
each pipeline stage is a long-lived actor owning its contiguous block of
transformer layers (plus the embedding on stage 0 and the head on the
last stage).  The whole 1F1B microbatch schedule is ONE compiled DAG —

    inp -> s0.forward -> ... -> sL.forward_loss_backward
        -> s(L-1).backward -> ... -> s0.backward

— an acyclic chain in which every non-final actor appears twice (its
forward op and its backward op).  Compiling with ``threaded_ops=True``
gives each op its own resident channel loop, so stage i runs forward of
microbatch t+1 while its backward op still waits on the cotangent of
microbatch t: the 1F1B interleave, with ``max_inflight`` bounding the
in-flight window to the pipeline depth.

Per microbatch the driver pays one ``execute()`` (a single shm channel
write) and one ``get()`` — ZERO classic task submissions, which
``PipelineRunner.run_step`` asserts through the owner's
``ray_tpu_actor_tasks_submitted_total`` counter.  Only the once-per-step
optimizer application goes through a classic actor call.

Backward is recompute-based (remat semantics): a stage stashes each
microbatch's INPUT, not vjp residuals, and its backward op re-runs the
forward under ``jax.grad`` of <output, cotangent>.  That keeps both
directions jittable (``jax.vjp``'s closure is not) and the stash O(input)
instead of O(activations).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import ray_tpu
from ray_tpu._private import runtime_metrics as rtm
from ray_tpu.dag.dag_node import InputNode
from ray_tpu.models.configs import TransformerConfig, get_config

_SUBMIT_METRIC = "ray_tpu_actor_tasks_submitted_total"


def _actor_submit_count() -> Optional[float]:
    """Owner-process total of classic actor-task submissions, or None
    when runtime metrics are disabled (the zero-submission assert then
    degrades to unchecked)."""
    snap = rtm.snapshot().get(_SUBMIT_METRIC)
    if not snap:
        return None
    return float(sum((snap.get("values") or {}).values()))


# --------------------------------------------------------------- stage split
@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: a contiguous [lo, hi) block of layers, plus
    the embedding (first) / final-norm + head (last) bookends."""

    index: int
    n_stages: int
    lo: int
    hi: int

    @property
    def first(self) -> bool:
        return self.index == 0

    @property
    def last(self) -> bool:
        return self.index == self.n_stages - 1

    @property
    def n_layers(self) -> int:
        return self.hi - self.lo


def gpt_stage_specs(cfg: TransformerConfig, pp: int) -> List[StageSpec]:
    """Split a GPT config into ``pp`` contiguous stages (remainder layers
    go to the EARLY stages, matching ``LayoutPlan.layer_ranges``)."""
    if pp < 1:
        raise ValueError(f"pp must be >= 1, got {pp}")
    if pp > cfg.n_layers:
        raise ValueError(
            f"cannot split {cfg.n_layers} layers into {pp} pipeline stages")
    if pp > 1 and cfg.tie_embeddings:
        raise ValueError(
            "tie_embeddings puts the output head's weights on stage 0; "
            "untie them (tie_embeddings=False) to pipeline with pp > 1")
    base, rem = divmod(cfg.n_layers, pp)
    specs, lo = [], 0
    for i in range(pp):
        hi = lo + base + (1 if i < rem else 0)
        specs.append(StageSpec(index=i, n_stages=pp, lo=lo, hi=hi))
        lo = hi
    return specs


def split_params_by_stage(params: Any, specs: Sequence[StageSpec]) -> list:
    """Slice one full-model GPT param tree (scan-layers layout: block
    params stacked on axis 0 under ``blocks``) into per-stage trees whose
    scopes match ``_StageModule`` — the numerics-test bridge between a
    single-process reference model and the pipeline."""
    import flax.linen as nn
    import jax

    params = nn.meta.unbox(params)
    if "blocks" not in params:
        raise ValueError(
            "split_params_by_stage needs the scan-layers param layout "
            "(cfg.scan_layers=True): expected a stacked 'blocks' scope, "
            f"got {sorted(params)}")
    out = []
    for st in specs:
        p: Dict[str, Any] = {}
        if st.first:
            p["embed"] = params["embed"]
        if st.n_layers:
            p["blocks"] = jax.tree.map(lambda a, st=st: a[st.lo:st.hi],
                                       params["blocks"])
        if st.last:
            p["final_norm"] = params["final_norm"]
            p["lm_head"] = params["lm_head"]
        out.append(p)
    return out


def lm_loss(logits, targets):
    """Mean next-token cross entropy — shared by the last stage and the
    single-process reference the numerics test compares against."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


# --------------------------------------------------------------- stage model
def _stage_module(cfg: TransformerConfig, spec: StageSpec):
    """Flax module for one stage, with param scopes that are a SUBSET of
    the full GPT tree ('embed', 'blocks', 'final_norm', 'lm_head') so a
    full-model checkpoint splits cleanly (split_params_by_stage)."""
    import flax.linen as nn
    import jax.numpy as jnp

    from ray_tpu.models.gpt import RMSNorm, Block, _dense, stack_layers
    from ray_tpu.ops.layers import rope_frequencies

    class _StageModule(nn.Module):
        cfg: TransformerConfig = dataclasses.field(default_factory=lambda: cfg)

        @nn.compact
        def __call__(self, x):
            c = self.cfg
            if spec.first:
                embed = self.param(
                    "embed",
                    nn.with_logical_partitioning(
                        nn.initializers.normal(stddev=0.02),
                        ("vocab", "embed")),
                    (c.vocab_size, c.d_model), c.param_dtype)
                x = jnp.take(embed, x, axis=0).astype(c.dtype)
            else:
                x = x.astype(c.dtype)
            if spec.n_layers:
                cos, sin = rope_frequencies(c.head_dim, c.max_seq_len,
                                            c.rope_theta)
                x = stack_layers(Block, c, {}, x,
                                 (cos, sin, None, None, None, None),
                                 remat=False, n_layers=spec.n_layers)
            if not spec.last:
                return x
            x = RMSNorm(c.norm_eps, name="final_norm")(x)
            logits = _dense(c.vocab_size, ("embed", "vocab"), "lm_head",
                            dtype=c.dtype, param_dtype=c.param_dtype)(x)
            return logits.astype(jnp.float32)

    return _StageModule()


@ray_tpu.remote
class PipelineStageActor:
    """One MPMD stage: owns its param slice + grad accumulator, exposes
    the compiled-DAG ops (forward / forward_loss_backward / backward) and
    the classic once-per-step ``apply_grads``.

    Channel payloads are dicts of numpy arrays; ``targets`` ride the
    forward chain so only the driver's InputNode carries batch data."""

    def __init__(self, cfg: TransformerConfig, spec: StageSpec, *,
                 lr: float = 1e-2, seed: int = 0, params=None):
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.spec = spec
        self.lr = float(lr)
        self.module = _stage_module(cfg, spec)
        if params is None:
            shape = ((1, 8) if spec.first
                     else (1, 8, cfg.d_model))
            dummy = (jnp.zeros(shape, jnp.int32) if spec.first
                     else jnp.zeros(shape, cfg.dtype))
            params = self.module.init(
                jax.random.PRNGKey(seed * 1009 + spec.index), dummy)["params"]
        self.params = jax.tree.map(jnp.asarray, nn.meta.unbox(params))

        apply = self.module.apply
        self._fwd = jax.jit(lambda p, x: apply({"params": p}, x))
        if spec.last:
            def _loss(p, x, tgt):
                return lm_loss(apply({"params": p}, x), tgt)
            # argnums=(0, 1): one fused pass yields the stage's param
            # grads AND the cotangent handed upstream
            self._loss_grad = jax.jit(
                jax.value_and_grad(_loss, argnums=(0, 1)))
        else:
            def _dot(p, x, d):
                out = apply({"params": p}, x)
                return jnp.vdot(out.astype(jnp.float32),
                                d.astype(jnp.float32))
            # grad of <f(p, x), d> == VJP with cotangent d; recompute-
            # based so backward stays a single jittable function
            argnums = (0,) if spec.first else (0, 1)
            self._bwd = jax.jit(jax.grad(_dot, argnums=argnums))
        self._apply = jax.jit(
            lambda p, g, n: jax.tree.map(
                lambda pp, gg: (pp - self.lr * gg / n).astype(pp.dtype),
                p, g),
            donate_argnums=(0,))
        self._stash: collections.deque = collections.deque()
        self._acc = None
        self._n_acc = 0

    # ------------------------------------------------------ compiled-DAG ops
    def forward(self, payload: dict) -> dict:
        import numpy as np
        x = payload["tokens"] if self.spec.first else payload["acts"]
        self._stash.append(x)
        acts = self._fwd(self.params, x)
        return {"acts": np.asarray(acts), "targets": payload["targets"]}

    def forward_loss_backward(self, payload: dict) -> dict:
        import numpy as np
        x = payload["acts"]
        (loss, (d_p, d_x)) = self._loss_grad(self.params, x,
                                             payload["targets"])
        self._accumulate(d_p)
        return {"d_acts": np.asarray(d_x), "loss": float(loss)}

    def backward(self, payload: dict):
        import numpy as np
        x = self._stash.popleft()
        grads = self._bwd(self.params, x, payload["d_acts"])
        self._accumulate(grads[0])
        if self.spec.first:
            return payload["loss"]
        return {"d_acts": np.asarray(grads[1]), "loss": payload["loss"]}

    # ------------------------------------------------------- classic methods
    def _accumulate(self, g) -> None:
        import jax
        self._acc = g if self._acc is None else jax.tree.map(
            lambda a, b: a + b, self._acc, g)
        self._n_acc += 1

    def apply_grads(self) -> int:
        """Once-per-step optimizer: SGD over the microbatch-mean grads.
        (The full optimizer/precision stack lives in the executor path;
        the pipeline runner's contract is the schedule, not the tx.)"""
        if self._n_acc == 0:
            return 0
        if self._stash:
            raise RuntimeError(
                f"stage {self.spec.index}: {len(self._stash)} forward "
                "stashes not consumed by backward — apply_grads called "
                "mid-step?")
        n = self._n_acc
        self.params = self._apply(self.params, self._acc, float(n))
        self._acc, self._n_acc = None, 0
        return n

    def reset_grads(self) -> int:
        """Drop the accumulated grads WITHOUT updating params (numerics
        probes that only want the forward losses)."""
        n = self._n_acc
        self._acc, self._n_acc = None, 0
        self._stash.clear()
        return n

    def ready(self) -> int:
        """Creation fence: the DAG compiler requires live actors."""
        return self.spec.index

    def get_params(self):
        import numpy as np
        import jax
        return jax.tree.map(np.asarray, self.params)


# --------------------------------------------------------------------- spec
@dataclasses.dataclass
class PipelineSpec:
    """A pipelined training run (pp MPMD stages, 1F1B over one compiled
    DAG).  ``microbatches`` per step; each microbatch is
    [microbatch_size, seq_len] tokens."""

    model: str = "tiny"
    model_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    pp: int = 2
    microbatches: int = 4
    microbatch_size: int = 2
    seq_len: int = 32
    steps: int = 4
    lr: float = 1e-2
    seed: int = 0
    max_inflight: Optional[int] = None      # None -> pp (the 1F1B window)
    buffer_bytes: Optional[int] = None      # None -> sized from shapes
    threaded_ops: bool = True               # False: serial per-actor loop

    def config(self) -> TransformerConfig:
        return get_config(self.model, **self.model_overrides)


def synth_microbatches(spec: PipelineSpec, cfg: TransformerConfig,
                       step: int) -> List[dict]:
    """Deterministic synthetic token microbatches (same convention as the
    executor's ``_synth_batch``: seed x step keyed, rank-free here)."""
    out = []
    for m in range(spec.microbatches):
        rng = np.random.default_rng(
            (spec.seed * 1_000_003 + step) * 65_537 + m)
        toks = rng.integers(0, cfg.vocab_size,
                            (spec.microbatch_size, spec.seq_len + 1),
                            dtype=np.int32)
        out.append({"tokens": toks[:, :-1], "targets": toks[:, 1:]})
    return out


# -------------------------------------------------------------------- runner
class PipelineRunner:
    """Driver handle: spawns the stage actors, compiles the DAG once, and
    pumps microbatches through it.

    ``stage_params`` (optional) injects per-stage param trees — the
    numerics test splits one full-model init via
    ``split_params_by_stage`` so the pipeline and the single-process
    reference start bit-identical."""

    def __init__(self, spec: PipelineSpec, *,
                 stage_params: Optional[Sequence[Any]] = None):
        self.spec = spec
        self.cfg = spec.config()
        self.stages = gpt_stage_specs(self.cfg, spec.pp)
        if stage_params is not None and len(stage_params) != spec.pp:
            raise ValueError(
                f"stage_params has {len(stage_params)} entries for "
                f"pp={spec.pp}")
        self.actors = [
            PipelineStageActor.remote(
                self.cfg, st, lr=spec.lr, seed=spec.seed,
                params=None if stage_params is None else stage_params[i])
            for i, st in enumerate(self.stages)]
        # actor creation is async and the DAG compiler rejects non-live
        # actors (it resolves channel endpoints at compile time): fence
        # on a trivial call — also absorbs each stage's jax/flax import
        ray_tpu.get([a.ready.remote() for a in self.actors], timeout=600.0)
        self._dag = self._compile()
        self.telemetry: Dict[str, Any] = {
            "executes": 0,
            "classic_submits_hot_loop": 0.0 if _actor_submit_count()
            is not None else None,
        }

    def _compile(self):
        spec, cfg = self.spec, self.cfg
        with InputNode() as inp:
            node = inp
            for a in self.actors[:-1]:
                node = a.forward.bind(node)
            node = self.actors[-1].forward_loss_backward.bind(node)
            for a in reversed(self.actors[:-1]):
                node = a.backward.bind(node)
        if spec.buffer_bytes is not None:
            buf = spec.buffer_bytes
        else:
            # largest payload on any edge: fp32 activations (or logits'
            # cotangent) + targets + pickle framing slack
            acts = 4 * spec.microbatch_size * spec.seq_len * cfg.d_model
            buf = max(1 << 16, 2 * acts + 8 * spec.microbatch_size
                      * spec.seq_len + 4096)
        return node.experimental_compile(
            max_inflight=spec.max_inflight or spec.pp,
            buffer_size_bytes=buf, threaded_ops=spec.threaded_ops,
            name=f"pp{spec.pp}-{spec.model}")

    def run_step(self, microbatches: Optional[List[dict]] = None, *,
                 step: int = 0, timeout: float = 120.0) -> Dict[str, Any]:
        """One optimizer step: pump every microbatch through the compiled
        chain (zero classic submissions — asserted), then one classic
        ``apply_grads`` per stage."""
        if microbatches is None:
            microbatches = synth_microbatches(self.spec, self.cfg, step)
        c0 = _actor_submit_count()
        refs = [self._dag.execute(mb) for mb in microbatches]
        losses = [r.get(timeout=timeout) for r in refs]
        c1 = _actor_submit_count()
        if c0 is not None and c1 is not None:
            delta = c1 - c0
            self.telemetry["classic_submits_hot_loop"] += delta
            if delta:
                raise RuntimeError(
                    f"compiled pipeline hot loop issued {delta} classic "
                    "task submissions; the zero-submission contract is "
                    "broken (docs/compiled_dag.md)")
        self.telemetry["executes"] += len(microbatches)
        applied = ray_tpu.get(
            [a.apply_grads.remote() for a in self.actors], timeout=timeout)
        assert all(n == len(microbatches) for n in applied), applied
        return {"loss": float(np.mean(losses)),
                "losses": [float(x) for x in losses],
                "microbatches": len(microbatches)}

    def train(self, steps: Optional[int] = None) -> Dict[str, Any]:
        steps = self.spec.steps if steps is None else steps
        history = [self.run_step(step=s)["loss"] for s in range(steps)]
        n_exec = max(1, self.telemetry["executes"])
        subs = self.telemetry["classic_submits_hot_loop"]
        return {
            "steps": steps,
            "loss_history": history,
            "final_loss": history[-1] if history else float("nan"),
            "executes": self.telemetry["executes"],
            "classic_submits_hot_loop": subs,
            "submissions_per_microbatch":
                None if subs is None else subs / n_exec,
        }

    def forward_loss(self, microbatches: List[dict],
                     timeout: float = 120.0) -> List[float]:
        """Losses WITHOUT an optimizer step (numerics comparisons): runs
        the full fwd+bwd chain, then discards the accumulated grads."""
        refs = [self._dag.execute(mb) for mb in microbatches]
        losses = [float(r.get(timeout=timeout)) for r in refs]
        ray_tpu.get([a.reset_grads.remote() for a in self.actors],
                    timeout=timeout)
        return losses

    def stage_params(self) -> list:
        return ray_tpu.get([a.get_params.remote() for a in self.actors])

    def shutdown(self) -> None:
        try:
            self._dag.teardown()
        except Exception:
            pass
        for a in self.actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
