"""Sharded-training subsystem gate (docs/train_sharded.md).

Three contracts, asserted end to end:

* **golden layouts** — :func:`ray_tpu.train.sharded.layout.plan` maps a
  ShardingConfig to an EXACT PartitionSpec table per parameter /
  activation class (including the dp-only and pp-only degenerates).
  The tables are written out literally: any rule-table or pruning
  change must update this file consciously.
* **pipeline numerics** — a pp=2 MPMD pipeline seeded from one
  full-model init via ``split_params_by_stage`` reproduces the
  single-process GPT loss (measured bit-identical on the CPU backend;
  1e-6 is the documented tolerance), and its hot loop keeps the
  zero-classic-submission contract (telemetry-asserted inside
  ``run_step``).
* **gang chaos** — a 2-worker ShardedTrainer run survives a mid-run
  node preemption (graceful drain -> NODE_DRAINED -> SIGKILL, the spot
  termination shape): gang recovery resumes from the newest restorable
  sharded checkpoint and the per-(rank, step, pid) KV breadcrumbs bound
  re-executed work by the checkpoint interval (+1 interval when the
  newest shard set raced the evacuation sweep and restore fell back one
  chain entry).
"""

import collections
import threading
import time

import numpy as np

import pytest

import ray_tpu
from jax.sharding import PartitionSpec as P
from ray_tpu.train.sharded import layout
from ray_tpu.train.sharded.layout import (ShardingConfig, dryrun_plans,
                                          plan)


# ------------------------------------------------------------- golden layouts
def test_golden_fsdp_tp():
    """The headline bench layout: fsdp=2 x tp=2 on 4 devices."""
    p = plan(ShardingConfig(fsdp=2, tp=2), n_devices=4)
    assert p.mesh_shape == {"stage": 1, "data": 1, "fsdp": 2,
                            "context": 1, "tensor": 2}
    assert p.param_table() == {
        "token_embed": P("tensor", "fsdp"),
        "attn_qkv": P("fsdp", "tensor", None),
        "attn_kv": P("fsdp", "tensor", None),
        "attn_out": P("tensor", "fsdp"),
        "mlp_up": P("fsdp", "tensor"),
        "mlp_down": P("tensor", "fsdp"),
        "norm_scale": P(None),
        "lm_head": P("fsdp", "tensor"),
    }
    assert p.activation_table() == {
        "batch_tokens": P("fsdp", None),
        "hidden": P("fsdp", None, None),
        "logits": P("fsdp", None, "tensor"),
    }
    assert p.n_stages == 1 and p.devices_per_stage() == 4


def test_golden_full_stack():
    """All four in-mesh axes live: the tuple-axes ('data','fsdp') batch
    rule survives unpruned and context shards the sequence axis."""
    p = plan(ShardingConfig(dp=2, fsdp=2, cp=2, tp=2), n_devices=16)
    assert p.mesh_shape == {"stage": 1, "data": 2, "fsdp": 2,
                            "context": 2, "tensor": 2}
    t = p.activation_table()
    assert t["batch_tokens"] == P(("data", "fsdp"), None)
    assert t["hidden"] == P(("data", "fsdp"), "context", None)
    assert t["logits"] == P(("data", "fsdp"), "context", "tensor")
    assert p.param_table()["token_embed"] == P("tensor", "fsdp")


def test_golden_dp_only_degenerate():
    """Pure data parallelism: every param replicated, batch on 'data'."""
    p = plan(ShardingConfig(dp=8), n_devices=8)
    assert p.mesh_shape == {"stage": 1, "data": 8, "fsdp": 1,
                            "context": 1, "tensor": 1}
    for name, spec in p.param_table().items():
        assert all(ax is None for ax in spec), (name, spec)
    assert p.activation_table() == {
        "batch_tokens": P("data", None),
        "hidden": P("data", None, None),
        "logits": P("data", None, None),
    }


def test_golden_pp_only_degenerate():
    """pp-only MPMD: a 1-device mesh per stage, everything replicated —
    parallelism lives in the stage split, not the mesh."""
    p = plan(ShardingConfig(pp=2), n_devices=1)
    assert p.mesh_shape == {"stage": 1, "data": 1, "fsdp": 1,
                            "context": 1, "tensor": 1}
    assert p.n_stages == 2 and p.devices_per_stage(n_devices=2) == 1
    for table in (p.param_table(), p.activation_table()):
        for name, spec in table.items():
            assert all(ax is None for ax in spec), (name, spec)
    # remainder layers land on the EARLY stages (they also carry embed)
    assert p.layer_ranges(4) == [(0, 2), (2, 4)]
    assert p.layer_ranges(5) == [(0, 3), (3, 5)]
    with pytest.raises(ValueError):
        p.layer_ranges(1)


def test_spmd_pipeline_and_wildcard():
    """pp_style='spmd' makes pp a mesh axis; -1 absorbs the rest."""
    p = plan(ShardingConfig(dp=-1, pp=2, pp_style="spmd"), n_devices=8)
    assert p.mesh_shape == {"stage": 2, "data": 4, "fsdp": 1,
                            "context": 1, "tensor": 1}
    assert p.n_stages == 1  # spmd: no MPMD stage actors
    assert p.activation_table()["batch_tokens"] == P("data", None)


def test_config_validation():
    with pytest.raises(ValueError, match="at most one"):
        ShardingConfig(dp=-1, fsdp=-1)
    with pytest.raises(ValueError, match="pp_style"):
        ShardingConfig(pp_style="gpipe")
    with pytest.raises(ValueError, match="slices"):
        ShardingConfig(slices=0)
    with pytest.raises(ValueError, match="needs 4 devices"):
        plan(ShardingConfig(fsdp=2, tp=2), n_devices=8)
    with pytest.raises(ValueError, match="not divisible"):
        plan(ShardingConfig(dp=-1, tp=3), n_devices=8)
    with pytest.raises(ValueError, match="unknown mesh axis"):
        layout._shape_to_config({"rows": 2})


def test_mesh_authority_get_mesh():
    """get_mesh is THE mesh constructor (absorbed from jax_trainer):
    resolves through the planner, preserves the caller's axis subset,
    caches per loop thread."""
    from ray_tpu.train import jax_trainer

    assert jax_trainer.get_mesh is layout.get_mesh
    layout.set_loop_mesh_shape(None)
    try:
        m = layout.get_mesh({"data": 2, "fsdp": 4})
        assert m.axis_names == ("data", "fsdp")
        assert dict(m.shape) == {"data": 2, "fsdp": 4}
        assert layout.get_mesh({"data": 2, "fsdp": 4}) is m  # cached
        # the trainer-installed loop shape, wildcard resolved
        layout.set_loop_mesh_shape({"data": -1})
        m2 = layout.get_mesh()
        assert dict(m2.shape) == {"data": 8}
    finally:
        layout.set_loop_mesh_shape(None)


def test_dryrun_plans_accounting():
    """The MULTICHIP dryrun sweep: every named plan factors the device
    count exactly (per stage x stages)."""
    plans = dict(dryrun_plans(8))
    assert set(plans) == {"train", "pipeline_spmd", "moe_ep",
                          "hier_2slice"}
    for name, p in plans.items():
        total = p.devices_per_stage() * p.n_stages
        assert total == 8, (name, p.mesh_shape)
    assert plans["pipeline_spmd"].mesh_shape["stage"] == 2
    assert plans["hier_2slice"].config.slices == 2


# --------------------------------------------------------- pipeline numerics
def test_pipeline_matches_single_process(ray_start_regular):
    """A pp=2 pipeline seeded from ONE full-model init reproduces the
    single-process loss, then trains a step without a single classic
    task submission in the hot loop."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT
    from ray_tpu.train.sharded.pipeline import (PipelineRunner,
                                                PipelineSpec,
                                                gpt_stage_specs, lm_loss,
                                                split_params_by_stage,
                                                synth_microbatches)

    spec = PipelineSpec(model="tiny", pp=2, microbatches=2,
                        microbatch_size=2, seq_len=16, steps=1, seed=3)
    cfg = spec.config()
    mbs = synth_microbatches(spec, cfg, 0)

    model = GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.asarray(mbs[0]["tokens"]))
    params = nn.meta.unbox(variables["params"])
    ref = [float(lm_loss(model.apply({"params": params},
                                     jnp.asarray(mb["tokens"])),
                         jnp.asarray(mb["targets"])))
           for mb in mbs]

    stage_params = split_params_by_stage(params, gpt_stage_specs(cfg, 2))
    runner = PipelineRunner(spec, stage_params=stage_params)
    try:
        got = runner.forward_loss(mbs)
        # measured bit-identical on the CPU backend; 1e-6 is the
        # documented tolerance (docs/train_sharded.md)
        assert np.allclose(got, ref, rtol=0, atol=1e-6), (got, ref)
        out = runner.train(2)
        assert out["classic_submits_hot_loop"] in (None, 0.0)
        assert out["submissions_per_microbatch"] in (None, 0.0)
        assert np.isfinite(out["final_loss"])
        # the optimizer step actually moved the params
        p0 = runner.stage_params()
        assert any(
            not np.array_equal(a, b)
            for a, b in zip(jax.tree_util.tree_leaves(p0),
                            jax.tree_util.tree_leaves(stage_params)))
    finally:
        runner.shutdown()


def test_stage_split_covers_model():
    """split_params_by_stage partitions the full tree: stage scopes are
    disjoint and reassemble to every top-level scope exactly once."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import GPT, get_config
    from ray_tpu.train.sharded.pipeline import (gpt_stage_specs,
                                                split_params_by_stage)

    cfg = get_config("tiny")
    model = GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    parts = split_params_by_stage(variables["params"],
                                  gpt_stage_specs(cfg, 2))
    assert "embed" in parts[0] and "embed" not in parts[1]
    assert "lm_head" in parts[1] and "lm_head" not in parts[0]
    n_layers = [jax.tree_util.tree_leaves(p["blocks"])[0].shape[0]
                for p in parts]
    assert sum(n_layers) == cfg.n_layers


# ----------------------------------------------------------------- gang chaos
def _wait_event(gcs, etype, timeout=60.0, **match):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        evs = gcs.call("list_cluster_events", {"type": etype})
        for ev in reversed(evs or []):
            if all(ev.get(k) == v for k, v in match.items()):
                return ev
        time.sleep(0.3)
    return None


def test_sharded_gang_survives_preemption(ray_start_cluster):
    """Chaos leg: drain+kill a gang node mid-run (the spot-termination
    shape: NODE_PREEMPTING grace, shard evacuation, SIGKILL at the
    NODE_DRAINED edge).  The trainer re-forms the gang on replacement
    capacity, restores the striped sharded checkpoint, and the KV
    breadcrumbs prove re-executed work stayed inside the bound."""
    from ray_tpu.air.config import FailureConfig, RunConfig
    from ray_tpu.runtime.core_worker import get_global_worker
    from ray_tpu.train.sharded import (ShardedRunConfig, ShardedTrainer,
                                       ShardingConfig)

    cluster = ray_start_cluster
    victim = cluster.add_node(resources={"CPU": 2, "slice": 2})
    cluster.add_node(resources={"CPU": 2, "slice": 2})
    cluster.wait_for_nodes(3)
    ray_tpu.init(num_cpus=0, address=cluster.address)
    gcs = get_global_worker().gcs

    tag = "t-sharded-chaos"
    interval = 2
    # fsdp x tp (the headline bench layout): batch shards over fsdp
    # only, so batch_per_worker=4 divides cleanly on the 8-device mesh
    run = ShardedRunConfig(
        sharding=ShardingConfig(fsdp=2, tp=4), model="tiny",
        num_workers=2, steps=10, batch_per_worker=4, seq_len=32,
        checkpoint_interval=interval, quantize="int8",
        async_grad_sync=True, step_sleep_s=0.5, kv_breadcrumbs=True)
    trainer = ShardedTrainer(
        run,
        run_config=RunConfig(name=tag,
                             failure_config=FailureConfig(max_failures=3)),
        resources_per_worker={"CPU": 1, "slice": 1}, tag=tag)

    state = {}

    def _preempt():
        # wait for the first post-checkpoint step (interval=2: step 1's
        # shards are in the KV), then drain the victim and SIGKILL at
        # the NODE_DRAINED edge — killing earlier loses the primaries
        # the survivors are supposed to inherit
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            steps = [int(k.split("/")[3])
                     for k in gcs.kv_keys(f"shardsteps/{tag}/")]
            if steps and max(steps) >= interval:
                break
            time.sleep(0.2)
        else:
            state["error"] = "never saw a post-checkpoint step"
            return
        gcs.call("drain_node", {"node_id": victim.node_id,
                                "grace_s": 30.0,
                                "reason": "chaos spot preemption"})
        if _wait_event(gcs, "NODE_DRAINED", timeout=90,
                       node_id=victim.node_id) is None:
            state["error"] = "drain never completed"
            return
        cluster.remove_node(victim)
        cluster.add_node(resources={"CPU": 2, "slice": 2})
        state["killed"] = True

    th = threading.Thread(target=_preempt, daemon=True)
    th.start()
    result = trainer.fit()
    th.join(timeout=300)
    assert state.get("killed"), state
    assert result.error is None, result.error
    assert result.metrics["step"] == run.steps - 1

    # exactly-once ledger from the per-(rank, step, pid) breadcrumbs
    per_rank = collections.defaultdict(list)
    pids = collections.defaultdict(set)
    for k in gcs.kv_keys(f"shardsteps/{tag}/"):
        _, _, rank, step_s, pid = k.split("/")
        per_rank[rank].append(int(step_s))
        pids[rank].add(pid)
    assert sorted(per_rank) == ["0", "1"]
    # the kill landed mid-run: at least one rank ran in two processes
    assert any(len(p) > 1 for p in pids.values()), dict(pids)
    for rank, steps in per_rank.items():
        counts = collections.Counter(steps)
        # every step executed at least once, none skipped
        assert sorted(counts) == list(range(run.steps)), (rank, counts)
        re_exec = sum(c - 1 for c in counts.values())
        # nominal bound: one checkpoint interval of lost work; +1
        # interval when the newest shard set raced the evacuation sweep
        # and restore fell back one chain entry (docs/train_sharded.md)
        assert re_exec <= 2 * interval, (rank, counts)
