"""Ahead-of-time compiles of the main path for a described TPU v5e 2x2.

The sandbox has no chip, but the TPU compiler is installed and compiles
for a topology that is described, not attached.  That catches what the
interpreter cannot: tiling, VMEM limits, HBM fit, kernels that cannot be
partitioned.  Nothing runs, so these say nothing about results or speed.

Rules this file keeps (only one process may load libtpu, and it keeps it
until exit): the topology is described inside the module-scoped fixture
below and nowhere else — not at import, not in a skipif/parametrize —
every compile runs in this process, and all such tests live in this one
file.  Code under test that asks the backend sees the CPU here, so the
tests steer the kernels' platform question themselves (``on_tpu``).
"""

import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / already held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make kernel dispatch answer "tpu" (compiled Pallas, not the
    interpreter): the compile target is the described chip, while
    ``jax.devices()`` here is the CPU."""
    for name in ("attention", "flash_attention", "paged_attention"):
        mod = importlib.import_module(f"ray_tpu.ops.{name}")
        monkeypatch.setattr(mod, "backend_platform", lambda: "tpu")


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_flash_fwd_bwd_gpt_small_widths(topo, one_chip):
    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct((16, 1024, 12, 64), jnp.bfloat16,
                               sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv)
    # forward + dq + dkv kernels
    assert lowered.as_text().count("tpu_custom_call") >= 3
    lowered.compile()


def test_paged_decode_gpt_small_widths(topo, one_chip):
    from ray_tpu.ops.paged_attention import paged_attention

    rows, heads, hd, ps, pages = 32, 12, 64, 64, 129

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, kv, bt, ln: paged_attention(q, kv, bt, ln, impl="tpu")
    ).lower(sds((rows, heads, hd), jnp.bfloat16),
            sds((pages, heads, ps, 2 * hd), jnp.bfloat16),
            sds((rows, 4), jnp.int32), sds((rows,), jnp.int32))
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def _train_step_programs(devices, sharding, *, batch, overrides):
    """grad_fn/apply_fn of the gang loop's own step (executor.build_step)
    for gpt-small cut to 2 layers, plus its state and batch shapes."""
    from ray_tpu.train.sharded import layout
    from ray_tpu.train.sharded.executor import ShardedRunConfig, build_step

    run = ShardedRunConfig(
        sharding=sharding, model="gpt-small", num_workers=1,
        batch_per_worker=batch, seq_len=1024,
        model_overrides=dict(n_layers=2, remat=True, max_seq_len=1024,
                             **overrides))
    mesh = layout.plan(sharding, n_devices=len(devices)).build_mesh(devices)
    example = {"tokens": np.zeros((batch, 1025), np.int32)}
    init_fn, grad_fn, apply_fn = build_step(run, mesh, example)
    tokens = {"tokens": jax.ShapeDtypeStruct((batch, 1025), jnp.int32)}
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0), tokens)
    return mesh, grad_fn, apply_fn, state, tokens


def test_gpt_small_train_step_one_chip(topo, on_tpu):
    from ray_tpu.train.sharded.layout import ShardingConfig

    _, grad_fn, apply_fn, state, tokens = _train_step_programs(
        topo.devices[:1], ShardingConfig(), batch=16,
        overrides={"attention_impl": "flash"})
    lowered = grad_fn.lower(state, tokens)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    # 16 GiB of HBM: the program's own footprint must fit with room for
    # the optimizer state that stays resident beside it
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 15 * 2**30
    grads = jax.eval_shape(grad_fn, state, tokens)[0]
    apply_fn.lower(state, grads).compile()


def test_train_grad_names_its_program_and_flash_kernels(topo, on_tpu):
    """What a profiler trace finds the step by (ISSUE 24): the program
    is ``train_grad`` and each of the three Pallas calls carries its own
    name — a reader matches ``flash_bwd_dkv``, not a result type."""
    from ray_tpu.train.sharded.layout import ShardingConfig

    _, grad_fn, apply_fn, state, tokens = _train_step_programs(
        topo.devices[:1], ShardingConfig(), batch=16,
        overrides={"attention_impl": "flash"})
    text = grad_fn.lower(state, tokens).as_text()
    assert "@jit_train_grad" in text
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f'kernel_name = "{name}"' in text
    grads = jax.eval_shape(grad_fn, state, tokens)[0]
    assert "@jit_train_apply" in apply_fn.lower(state, grads).as_text()


def test_train_step_2x2_mesh_has_collectives(topo, on_tpu):
    """fsdp=2 x tp=2 over the four described chips, default (auto ->
    flash) attention: the Pallas kernel must ride shard_map (GSPMD cannot
    partition a Mosaic call), fsdp must gather and tp must reduce."""
    from ray_tpu.train.sharded.layout import ShardingConfig

    mesh, grad_fn, _, state, tokens = _train_step_programs(
        topo.devices, ShardingConfig(fsdp=2, tp=2), batch=4, overrides={})
    assert dict(mesh.shape)["fsdp"] == 2 and dict(mesh.shape)["tensor"] == 2
    lowered = grad_fn.lower(state, tokens)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "all-gather" in hlo, "fsdp parameters are never gathered"
    assert "all-reduce" in hlo, "tensor-parallel partials never reduced"
    # parameters really are split four ways: per-device argument bytes
    # are far below the whole model's
    whole = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(state.params))
    assert compiled.memory_analysis().argument_size_in_bytes < whole / 2


def test_engine_prefill_and_paged_decode_programs(topo, one_chip, on_tpu):
    """The serve engine's own jitted programs at chip_smoke's slot and
    page sizes (2 layers): paged prefill and the paged block step, whose
    decode attention must be the Pallas kernel."""
    from ray_tpu.models.configs import get_config
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = get_config("gpt-small", n_layers=2)
    params = GPT(cfg, decode=True).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]
    eng = LLMEngine(cfg, params, num_slots=8, max_seq_len=256, paged=True,
                    page_size=64)
    prefill = eng._get_prefill_paged(64, 8).lower(
        *_shapes((eng.params, eng._cache,
                  jnp.zeros((8, 66), jnp.int32),
                  jnp.zeros((8, eng.max_pages), jnp.int32),
                  jax.random.PRNGKey(0)), one_chip))
    prefill.compile()
    block = eng._block_jit.lower(
        *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                 one_chip))
    assert "tpu_custom_call" in block.as_text()
    # the names a profiler trace shows them by (ISSUE 24)
    assert 'kernel_name = "paged_attention_decode"' in block.as_text()
    assert "@jit_engine_decode_block" in block.as_text()
    assert "@jit_engine_prefill" in prefill.as_text()
    block.compile()
