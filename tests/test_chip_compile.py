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


def _answer_tpu(patch):
    """Every kernel module's platform question answers "tpu"."""
    # import them all before patching any: a module first imported here
    # would bind attention's PATCHED function as its own, and the undo
    # would put that back for every later test of the process
    mods = [importlib.import_module(f"ray_tpu.ops.{name}") for name in
            ("attention", "flash_attention", "paged_attention", "moe",
             "gated_delta", "mamba2")]
    for mod in mods:
        patch.setattr(mod, "backend_platform", lambda: "tpu")


@pytest.fixture
def on_tpu(monkeypatch):
    """Make kernel dispatch answer "tpu" (compiled Pallas, not the
    interpreter): the compile target is the described chip, while
    ``jax.devices()`` here is the CPU."""
    _answer_tpu(monkeypatch)


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
    # the forward and the one backward kernel
    assert lowered.as_text().count("tpu_custom_call") >= 2
    lowered.compile()


# the training cell's call (SmolLM2-360M, 16 x 1024), then what the
# whole-sequence operands of the old backward refused from sequence 2048
# (RESOURCE_EXHAUSTED): 4 x 4096 at heads of 64, 2 x 2048 at heads of
# 128; then the backward's budget's edge (dq^T of 8192 x 128 in scratch
# across eight kv spans) and the float32 backward at the cell's lengths
@pytest.mark.parametrize("shape,dtype", [
    ((16, 1024, 15, 64), jnp.bfloat16), ((4, 4096, 15, 64), jnp.bfloat16),
    ((2, 2048, 28, 128), jnp.bfloat16), ((1, 8192, 4, 128), jnp.bfloat16),
    ((16, 1024, 15, 64), jnp.float32)],
    ids=lambda s: "x".join(map(str, s)) if isinstance(s, tuple)
    else jnp.dtype(s).name)
def test_flash_fwd_bwd_compiles_in_blocks(topo, one_chip, shape, dtype):
    from ray_tpu.ops.flash_attention import flash_attention, plan_blocks

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv)
    hlo = lowered.as_text()
    # one visit a tile pair: the fused backward and no second one
    for name in ("flash_fwd", "flash_bwd_dqkv"):
        assert f'kernel_name = "{name}"' in hlo, name
    assert "flash_bwd_dkv" not in hlo
    plan = plan_blocks(shape[1], shape[1], True, head_dim=shape[3])
    assert plan.fused
    # several blocks a sequence, so causal skipping has pairs to skip
    for t in (plan.fwd, plan.bwd):
        assert t.visited < t.total
    lowered.compile()


# (rows, heads, kv heads, head_dim, table width, window operand, live
# operand): gpt-small as before, then the two serving cells' decode
# shapes (SmolLM2-360M: padded query, no window; SmallThinker: the page
# split into its halves, window and live operands), then the preset
# with the largest page (1 MiB: a chunk of 512 positions twice over
# would not fit VMEM, so the kernel cuts its chunk by bytes)
_PAGED_SHAPES = {
    "gpt-small": (32, 12, 12, 64, 4, False, False),
    "smollm2-360m": (33, 15, 5, 64, 40, False, True),
    "smallthinker-21b-a3b": (33, 28, 4, 128, 96, True, True),
    "gptj-6b": (33, 16, 16, 256, 32, False, True),
}


@pytest.mark.parametrize("shape", list(_PAGED_SHAPES))
def test_paged_decode_gpt_small_widths(topo, one_chip, shape):
    """The kernel takes the whole stacked pool and a layer index (a
    traced scalar, as under the model's layer scan); one call is one
    custom call ``paged_attention_decode`` whatever operands it has."""
    from ray_tpu.ops.paged_attention import paged_attention

    rows, heads, kvh, hd, mp, windowed, masked = _PAGED_SHAPES[shape]
    ps, pages, layers = 64, 129, 3

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, kv, bt, ln, layer, window, live: paged_attention(
            q, kv, bt, ln, layer=layer, impl="tpu",
            window=window if windowed else None,
            live=live if masked else None)
    ).lower(sds((rows, heads, hd), jnp.bfloat16),
            sds((layers, pages, kvh, ps, 2 * hd), jnp.bfloat16),
            sds((rows, mp), jnp.int32), sds((rows,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((rows,), jnp.bool_))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    lowered.compile()


# Kanana-2's decode shape (test_paged_decode_on_latent_pages): 32 query
# heads on one KV head, rows of 640 whose values are the first 512
_LATENT_SHAPE = (33, 32, 1, None, 160, False, True)


@pytest.mark.parametrize("shape", list(_PAGED_SHAPES) + ["latent"])
def test_paged_decode_writes_the_pool_it_is_given(topo, one_chip, shape):
    """Handed the step's rows (ISSUE 45) the call is still ONE custom
    call ``paged_attention_decode``; the pool, a donated argument, goes
    into it as it came and IS its second result: aliased, with no copy
    and no scatter of the pool's shape beside it."""
    import re

    from ray_tpu.ops.paged_attention import paged_attention

    rows, heads, kvh, hd, mp, windowed, masked = (
        _LATENT_SHAPE if shape == "latent" else _PAGED_SHAPES[shape])
    ps, pages, layers = 64, 129, 3
    row, v_width = (2 * hd, None) if hd else (640, 512)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, kv, bt, ln, layer, window, live, new: paged_attention(
            q, kv, bt, ln, layer=layer, impl="tpu", new_rows=new,
            v_width=v_width, window=window if windowed else None,
            live=live if masked else None), donate_argnums=(1,)
    ).lower(sds((rows, heads, hd or row), jnp.bfloat16),
            sds((layers, pages, kvh, ps, row), jnp.bfloat16),
            sds((rows, mp), jnp.int32), sds((rows,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((rows,), jnp.bool_), sds((rows, kvh, row), jnp.bfloat16))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    hlo = lowered.compile().as_text()
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*(ROOT )?%?paged_attention_decode\S* = ", line)]
    pool = f"bf16[{layers},{pages},{kvh},{ps},{row}]"
    operands = re.search(r"custom-call\((.*?)\), custom_call_target",
                         call).group(1)
    operands = re.sub(r"/\*index=\d+\*/", "", operands).split(", ")
    (out, at), = re.findall(
        r"output_to_operand_aliasing=\{\{(\d+)\}: \((\d+), \{\}\)\}", call)
    assert out == "1"
    # the aliased operand is the program's own (donated) argument
    assert any(re.match(
        rf"\s*{re.escape(operands[int(at)])} = {re.escape(pool)}\S* "
        r"parameter\(1\)", line) for line in hlo.splitlines())
    assert "input_output_alias={ {1}: (1, {}, may-alias) }" in hlo
    made = _pool_result_producers(hlo, (layers * pages * kvh * ps * row,))
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast"}, made


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
    is ``train_grad`` and each of the two Pallas calls carries its own
    name — a reader matches ``flash_bwd_dq``, which ``flash_bwd_dqkv``
    holds, not a result type.  One visit a tile pair: no second
    backward kernel is in the program."""
    from ray_tpu.train.sharded.layout import ShardingConfig

    _, grad_fn, apply_fn, state, tokens = _train_step_programs(
        topo.devices[:1], ShardingConfig(), batch=16,
        overrides={"attention_impl": "flash"})
    text = grad_fn.lower(state, tokens).as_text()
    assert "@jit_train_grad" in text
    for name in ("flash_fwd", "flash_bwd_dqkv"):
        assert f'kernel_name = "{name}"' in text
    assert "flash_bwd_dkv" not in text
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
    eng = LLMEngine(cfg, params, num_slots=8, max_seq_len=256,
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


# ---- the stacked KV pool is addressed in place (ISSUE 25) ----

_IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "scatter",
             "dynamic-update-slice", "fusion:scatter",
             "fusion:dynamic-update-slice"}


def _computations(hlo: str):
    """The optimised HLO's computations: ``{name: [its instructions]}``."""
    import re

    comps, lines = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            lines = comps[head.group(2)] = []
        elif lines is not None and " = " in line:
            lines.append(line)
    return comps


def _pool_result_producers(hlo: str, sizes, dtype: str = "bf16"):
    """Opcodes of the optimised HLO's instructions whose (array) result
    holds one of ``sizes`` values of ``dtype``, whatever its rank — the pool,
    one layer of it, or a reshaped view.  A fusion is named by its
    root: ``fusion:dynamic-update-slice`` is an update in place,
    ``fusion:copy`` or ``fusion:dynamic-slice`` moved the pool."""
    import collections
    import re

    inst = re.compile(
        rf"^\s*(ROOT )?%?([\w.\-]+) = {dtype}\[([\d,]+)\]\S* ([\w\-]+)\((.*)$")
    roots, found = {}, []
    for comp, lines in _computations(hlo).items():
        for line in lines:
            m = inst.match(line)
            if not m:
                continue
            is_root, name, dims, op, rest = m.groups()
            if is_root:
                roots[comp] = op
            if np.prod([int(d) for d in dims.split(",")]) in sizes:
                calls = re.search(r"calls=%?([\w.\-]+)", rest)
                found.append((op, calls.group(1) if calls else None))
    return collections.Counter(
        f"fusion:{roots.get(c, '?')}" if op == "fusion" else op
        for op, c in found)


def _described_engine(cfg, monkeypatch, **kw):
    """A paged ``LLMEngine`` of ``cfg`` that is described, not allocated:
    parameters and cache are shapes."""
    from ray_tpu.models.gpt import GPT
    from ray_tpu.serve.llm_engine import LLMEngine

    assert cfg.scan_layers
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, cfg.dtype),
        jax.eval_shape(lambda: GPT(cfg, decode=True).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["params"]))
    # (the package re-exports the function ``generate`` under this name)
    generate = importlib.import_module("ray_tpu.models.generate")
    with monkeypatch.context() as patch:
        init = generate.init_decode_cache
        patch.setattr(
            generate, "init_decode_cache",
            lambda model, batch: jax.eval_shape(lambda: init(model, batch)))
        return LLMEngine(cfg, params, **{"num_slots": 32, "page_size": 64,
                                         **kw})


def _smollm_engine(pages, monkeypatch):
    """SmolLM2-360M's head shapes (15 heads, 5 KV heads of 64), 4 scanned
    layers, and a pool that dwarfs weights and activations."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("gpt-small", n_layers=4, d_model=960, n_heads=15,
                     n_kv_heads=5, d_ff=2560, vocab_size=49152,
                     tie_embeddings=True, dtype=jnp.bfloat16)
    return _described_engine(cfg, monkeypatch, max_seq_len=2560,
                             kv_pool_pages=pages)


def _compiled_engine_programs(pages, one_chip, monkeypatch):
    eng = _smollm_engine(pages, monkeypatch)
    (pool,) = [x for x in jax.tree.leaves(eng._cache)
               if eng._is_pool_leaf(x)]
    assert pool.shape == (4, pages, 5, 64, 128)
    bucket, wave = 64, 2
    block = eng._block_jit.lower(
        *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                 one_chip))
    # once a layer: the scanned layer's body holds the one call
    assert block.as_text().count(
        'kernel_name = "paged_attention_decode"') == 1
    prefill = eng._get_prefill_paged(bucket, wave).lower(
        *_shapes((eng.params, eng._cache,
                  jnp.zeros((wave, bucket + 2), jnp.int32),
                  jnp.zeros((wave, eng.max_pages), jnp.int32),
                  jax.random.PRNGKey(0)), one_chip))
    return {"engine_decode_block": block.compile(),
            "engine_prefill": prefill.compile()}


def _bytes_accessed(compiled):
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — not every compiled object has one
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return (cost or {}).get("bytes accessed")


def test_engine_programs_address_the_pool_in_place(topo, one_chip, on_tpu,
                                                   monkeypatch):
    """ISSUE 25's guard.  In the compiled decode block and prefill the
    stacked KV pool is never sliced, relaid out, copied or written back:
    temporaries stay under ONE layer's pool, nothing but parameters and
    in-place updates produces a pool-sized result, and a pool four times
    as large changes neither temporaries nor bytes accessed.  (The tree
    before ISSUE 25: decode block temporaries 17x one layer's pool,
    ``copy`` and ``dynamic-slice`` fusions of the pool in both.)  The
    prefill's update is XLA's (``write_kv_pages``); the decode block
    holds NO scatter or update of the pool's shape (ISSUE 45): its one
    writer is ``paged_attention_decode``, the pool aliased through it."""
    import re

    pages = 2048
    layer_elems = pages * 5 * 64 * 128
    small = _compiled_engine_programs(pages, one_chip, monkeypatch)
    large = _compiled_engine_programs(4 * pages, one_chip, monkeypatch)
    for name, compiled in small.items():
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 2 * layer_elems, (
            f"{name}: {temp / 1e6:.1f} MB of temporaries, one layer's "
            f"pool is {2 * layer_elems / 1e6:.1f} MB")
        made = _pool_result_producers(compiled.as_text(),
                                      (layer_elems, 4 * layer_elems))
        assert set(made) <= _IN_PLACE, (
            f"{name}: pool-sized results from {dict(made)}")
        updated = any("scatter" in op or "update-slice" in op for op in made)
        assert updated == (name == "engine_prefill"), dict(made)
        if not updated:
            (call,) = [line for line in compiled.as_text().splitlines()
                       if re.match(r"\s*%?paged_attention_decode\S* = ", line)]
            assert "output_to_operand_aliasing={{1}: (" in call
        temp4 = large[name].memory_analysis().temp_size_in_bytes
        assert abs(temp4 - temp) < 0.1 * temp, (
            f"{name}: temporaries {temp / 1e6:.1f} MB at {pages} pages, "
            f"{temp4 / 1e6:.1f} MB at {4 * pages}")
        moved, moved4 = (_bytes_accessed(c) for c in (compiled, large[name]))
        if moved and moved4:
            assert abs(moved4 - moved) < 0.1 * moved, (
                f"{name}: bytes accessed {moved / 1e6:.1f} MB at {pages} "
                f"pages, {moved4 / 1e6:.1f} MB at {4 * pages}")


# ---- decode reads the stacked expert weights in place (ISSUE 27) ----

def test_decode_block_reads_the_stacked_experts_in_place(topo, one_chip,
                                                         on_tpu, monkeypatch):
    """``engine_decode_block`` at SmallThinker's widths (2 of its layers,
    the serve-reason cell's slots, pages and context) holds the
    ``moe_experts_decode`` kernel over the STACKED expert leaves, and
    nothing in the compiled program produces a layer's experts (a custom
    call fed the scan's slice would: 0.755 GB copied a layer), with
    temporaries far under one layer's expert weights."""
    import re

    from ray_tpu.models.configs import get_config

    cfg = get_config("smallthinker-21b-a3b", n_layers=2, max_seq_len=6144,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    eng = _described_engine(cfg, monkeypatch, max_seq_len=6144)
    block = eng._block_jit.lower(
        *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                 one_chip))
    assert 'kernel_name = "moe_experts_decode"' in block.as_text()
    assert block.as_text().count(
        'kernel_name = "paged_attention_decode"') == 1
    compiled = block.compile()
    hlo = compiled.as_text()
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*%?moe_experts_decode\S* = ", line)]
    assert call.count("bf16[2,64,2560,768]") == 2       # w_gate, w_up
    assert call.count("bf16[2,64,768,2560]") == 1       # w_down
    one_matrix = 64 * 2560 * 768
    made = _pool_result_producers(hlo, (one_matrix,))
    assert not made, f"a layer's experts are produced by {dict(made)}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * 2 * one_matrix, (
        f"{temp / 1e6:.1f} MB of temporaries; one matrix of a layer's "
        f"experts is {2 * one_matrix / 1e6:.1f} MB")


# ---- a model whose state is pages AND recurrent entries (ISSUE 33) ----

@pytest.fixture(scope="module")
def olmo_hybrid_programs(topo, one_chip):
    """``engine_decode_block`` and a prefill wave twice the largest
    the serve-assist cell warms (8 x 2048 here; 4 x 2048 and 16 x 1024
    there, all past 1 GiB of scores: ``_prefill_attend`` goes by groups
    of rows), of Olmo-Hybrid-7B at the cell's
    cut and server: 12 layers, 64 slots, 1408 pages, 73 state entries.
    Compiled once for the tests below (some 40 s)."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("olmo-hybrid-7b", n_layers=12, max_seq_len=4096,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, num_slots=64, max_seq_len=4096,
                                max_prompt_len=2048, kv_pool_pages=1408)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        bucket, wave = 2048, 8
        prefill = eng._get_prefill_paged(bucket, wave).lower(
            *_shapes((eng.params, eng._cache,
                      jnp.zeros((wave, eng.packed_width(bucket)), jnp.int32),
                      jnp.zeros((wave, eng.max_pages), jnp.int32),
                      jax.random.PRNGKey(0)), one_chip))
        return {"eng": eng, "block_text": block.as_text(),
                "prefill_text": prefill.as_text(),
                "engine_decode_block": block.compile(),
                "engine_prefill": prefill.compile()}
    finally:
        patch.undo()


def test_olmo_hybrid_engine_programs_compile_with_their_kernels(
        olmo_hybrid_programs):
    """Both programs compile for the described v5e; the decode block
    holds ``gdn_decode`` once a linear layer of the scanned period and
    the paged kernel once, under the names a trace shows."""
    p = olmo_hybrid_programs
    shapes = {k: v.shape for k, v in p["eng"]._cache.items()}
    assert shapes == {"kv_pages": (3, 1408, 30, 64, 256),
                      "gdn_state": (9, 73, 96, 5760),
                      "gdn_conv": (9, 73, 270, 128)}
    text = p["block_text"]
    assert text.count('kernel_name = "gdn_decode"') == 3
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    assert "@jit_engine_decode_block" in text
    assert "@jit_engine_prefill" in p["prefill_text"]
    # the chunked prefill form is plain XLA: no kernel of its own yet
    assert "gdn_" not in p["prefill_text"].replace("gdn_state", "").replace(
        "gdn_conv", "")


@pytest.mark.parametrize("name", ["engine_decode_block", "engine_prefill"])
def test_olmo_hybrid_programs_address_pool_and_state_in_place(
        olmo_hybrid_programs, name):
    """Nothing but parameters and in-place updates produces a result of
    the size of the KV pool, of the recurrent state leaf or of the
    convolution-tail leaf, whole or one layer of it: neither is sliced,
    relaid out, copied or written back (a ``[.., 3, channels]`` tail
    leaf was: its minor pair is tiled to 16 rows, and merging the layer
    and entry axes of ``[.., entries, 3 * channels]`` is a copy)."""
    compiled = olmo_hybrid_programs[name]
    hlo = compiled.as_text()
    pool = 1408 * 30 * 64 * 256
    state, tail = 73 * 96 * 5760, 73 * 270 * 128
    for what, sizes, dtype in (("pool", (pool, 3 * pool), "bf16"),
                               ("state", (state, 9 * state), "f32"),
                               ("tail", (tail, 9 * tail), "bf16")):
        made = _pool_result_producers(hlo, sizes, dtype)
        assert set(made) <= _IN_PLACE, (
            f"{name}: {what}-sized results from {dict(made)}")
    if name == "engine_decode_block":
        # nor is the tail leaf moved through fast memory a layer, as the
        # Mamba-2 cells' was before PR 50 (looked for in ISSUE 52)
        assert "S(1)" not in "".join(
            line for line in hlo.splitlines()
            if any(leaf in line.split(" = ")[-1][:40]
                   for leaf in ("bf16[9,73,270,128]", "bf16[657,270,128]")))


def _reachable(comps, root: str):
    """``root`` and every computation it calls, at any depth."""
    import re

    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += [n for n in re.findall(
                r"%([\w.\-]+)", line.split("metadata=")[0]) if n in comps]
    return seen


def test_decode_block_draws_inside_its_one_conditional(olmo_hybrid_programs):
    """ISSUE 52.  The serve-assist decode block's loop body holds ONE
    ``conditional``, behind the head: one branch passes the greedy
    argmax through, the other holds every operation of the Gumbel draw
    (``jit(_gumbel)`` / ``jit(_uniform)``: a threefry hash a logit), and
    none lies outside it.  The head still writes ``bf16[rows, vocab]``
    and hands the conditional that (as float32 it is twice the bytes,
    the greedy argmax reading them too: ``LLMEngine._sample_fn``
    narrows), and nothing of that size is float32 but inside the
    drawing branch."""
    import re

    hlo = olmo_hybrid_programs["engine_decode_block"].as_text()
    comps = _computations(hlo)
    ((home, cond),) = [(name, line) for name, lines in comps.items()
                       for line in lines if " conditional(" in line]
    assert re.search(rf"body=%?{re.escape(home)}\b", hlo), (
        f"{home} is no loop's body")
    branches = re.search(r"branch_computations=\{([^}]*)\}", cond).group(
        1).replace("%", "").split(", ")
    assert len(branches) == 2
    noisy = {name for name, lines in comps.items()
             if any("_gumbel" in line or "_uniform" in line
                    for line in lines)}
    assert noisy
    inside = {b: _reachable(comps, b) for b in branches}
    (drawing,) = [b for b in branches if inside[b] & noisy]
    (greedy,) = [b for b in branches if b != drawing]
    assert noisy <= inside[drawing]
    (passed,) = comps[greedy]
    assert " parameter(0)" in passed
    logits = "[65,100352]"
    (head,) = [line for line in comps[home]
               if "GPT._head" in line and f" = bf16{logits}" in line]
    head = head.split(" = ")[0].split()[-1]
    (fed,) = [line.split(" = ")[0].split()[-1] for line in comps[home]
              if " tuple(" in line and f"{head}," in line]
    assert f"{fed})" in cond or f"{fed}," in cond
    wide = {name for name, lines in comps.items()
            if any(f" = f32{logits}" in line for line in lines)}
    assert wide <= inside[drawing]


def test_olmo_hybrid_cell_fits_the_chip(olmo_hybrid_programs):
    """12.2 GB resident (weights 6.54, pool 4.15, state entries 1.50),
    and a prefill's temporaries (4.1 GB at 8 x 2048, twice the cell's
    largest wave)
    beside it under the 16.9 GB a v5e gives a process (it ran there:
    PERF.md, PR 33); the decode block's temporaries are far under the
    state entries of one layer."""
    p = olmo_hybrid_programs
    block = p["engine_decode_block"].memory_analysis()
    prefill = p["engine_prefill"].memory_analysis()
    assert 12.1e9 < block.argument_size_in_bytes < 12.3e9
    assert block.temp_size_in_bytes < 0.3e9
    assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
            + block.temp_size_in_bytes) < 16.6e9


# ---- a latent pool, a dense prefix, a share of the experts (ISSUE 37) ----

def test_paged_decode_on_latent_pages(topo, one_chip):
    """The kernel family's latent case at Kanana-2's decode shape: 32
    query heads on ONE KV head, a page of rows padded from 576 to 640,
    scores over the whole row, values its first 512; still one custom
    call ``paged_attention_decode``."""
    from ray_tpu.ops.paged_attention import paged_attention

    rows, heads, mp, ps, pages, layers = 33, 32, 160, 64, 129, 3

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, kv, bt, ln, layer, live: paged_attention(
            q, kv, bt, ln, layer=layer, impl="tpu", live=live,
            sm_scale=192 ** -0.5, v_width=512)
    ).lower(sds((rows, heads, 640), jnp.bfloat16),
            sds((layers, pages, 1, ps, 640), jnp.bfloat16),
            sds((rows, mp), jnp.int32), sds((rows,), jnp.int32),
            sds((), jnp.int32), sds((rows,), jnp.bool_))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    assert lowered.out_info.shape == (rows, heads, 512)
    lowered.compile()


def _grouped_products_and_custom_calls(lowered: str):
    """``(chlo.ragged_dot operations, stablehlo.custom_call
    operations)`` of a lowered program: a layer that holds a share of
    its experts runs its sorted pairs in slabs (ISSUE 56) under the
    grouped products the program had, no more of them than it had (the
    slab loop is a function of its own: layers of one shape share ONE
    lowered body), and no custom call beside those it had."""
    lines = lowered.splitlines()
    return (sum('"chlo.ragged_dot"(' in line for line in lines),
            sum("stablehlo.custom_call" in line for line in lines))


@pytest.fixture(scope="module")
def kanana_programs(topo, one_chip):
    """``engine_decode_block`` and the two widest prefill waves the
    serve-docqa cell warms (2 x 8192, a row's scores in query blocks,
    and 4 x 4096) of Kanana-2-30B-A3B at the cell's cut and server: 16
    layers (one dense, 15 of 16 held experts of 128), 32 slots, pages of
    64, ``max_seq_len`` 10240, the default pool of 5,281 latent pages.
    Compiled once for the tests below."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("kanana-2-30b-a3b", n_layers=16, moe_experts_held=16,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, max_seq_len=10240,
                                max_prompt_len=8192,
                                prefill_wave_tokens=16384)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        out = {"eng": eng, "block_text": block.as_text(),
               "engine_decode_block": block.compile()}
        for bucket, wave in ((8192, 2), (4096, 4)):
            prefill = eng._get_prefill_paged(bucket, wave).lower(
                *_shapes((eng.params, eng._cache,
                          jnp.zeros((wave, eng.packed_width(bucket)),
                                    jnp.int32),
                          jnp.zeros((wave, eng.max_pages), jnp.int32),
                          jax.random.PRNGKey(0)), one_chip))
            out[f"prefill_text_{bucket}"] = prefill.as_text()
            out[f"engine_prefill_{bucket}"] = prefill.compile()
        return out
    finally:
        patch.undo()


def test_kanana_engine_programs_compile_with_their_kernels(kanana_programs):
    """The decode block holds the latent paged kernel once in the dense
    prefix and once in the scanned expert stack, and ``moe_experts_decode``
    over the stacked HELD experts ``[15, 16, ...]``; in the prefill
    programs a row's scores pass 1 GiB at both buckets, so the expanded
    attention is the flash kernel (twice: the dense prefix, the scanned
    stack) and no ``[32, q, k]`` scores exist."""
    p = kanana_programs
    assert {k: v.shape for k, v in p["eng"]._cache.items()} == {
        "kv_pages": (16, 5281, 1, 64, 640)}
    text = p["block_text"]
    assert text.count('kernel_name = "paged_attention_decode"') == 2
    assert text.count('kernel_name = "moe_experts_decode"') == 1
    assert "@jit_engine_decode_block" in text
    hlo = p["engine_decode_block"].as_text()
    import re
    (call,) = [line for line in hlo.splitlines()
               if re.match(r"\s*%?moe_experts_decode\S* = ", line)]
    assert call.count("bf16[15,16,2048,768]") == 2      # w_gate, w_up
    assert call.count("bf16[15,16,768,2048]") == 1      # w_down
    for bucket in (8192, 4096):
        assert "@jit_engine_prefill" in p[f"prefill_text_{bucket}"]
        text = p[f"prefill_text_{bucket}"]
        assert text.count('kernel_name = "flash_fwd"') == 2
        assert not re.search(r"tensor<\d+x32x\d{3,}x\d{3,}xf32>", text)
        # the scanned expert stack's three grouped products, in the slab
        # loop now; the two flash kernels are the only custom calls
        assert _grouped_products_and_custom_calls(text) == (3, 2)


@pytest.mark.parametrize("name", ["engine_decode_block",
                                  "engine_prefill_8192",
                                  "engine_prefill_4096"])
def test_kanana_programs_address_the_latent_pool_in_place(kanana_programs,
                                                          name):
    """Nothing but parameters and in-place updates produces a result of
    the size of the latent pool, whole or one layer of it, nor (in the
    decode block, whose kernel reads the stack in place) of a layer's
    held experts."""
    hlo = kanana_programs[name].as_text()
    pool = 5281 * 64 * 640
    made = _pool_result_producers(hlo, (pool, 16 * pool))
    assert set(made) <= _IN_PLACE, f"{name}: pool-sized from {dict(made)}"
    if name == "engine_decode_block":
        made = _pool_result_producers(hlo, (16 * 2048 * 768,))
        assert not made, f"{name}: a layer's experts from {dict(made)}"


def test_kanana_cell_fits_the_chip(kanana_programs):
    """11.4 GB resident (weights 4.53, latent pool 6.92 at rows of 640),
    and the widest prefill wave's temporaries beside it under the 16.9 GB
    a v5e gives a process."""
    p = kanana_programs
    block = p["engine_decode_block"].memory_analysis()
    assert 11.3e9 < block.argument_size_in_bytes < 11.6e9
    assert block.temp_size_in_bytes < 0.3e9
    for bucket in (8192, 4096):
        prefill = p[f"engine_prefill_{bucket}"].memory_analysis()
        assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
                + block.temp_size_in_bytes) < 16.0e9, (
            bucket, prefill.temp_size_in_bytes / 1e9)


# ---- layers that are one mixer each: Mamba-2, LatentMoE, attention
# ---- (ISSUE 44)

@pytest.fixture(scope="module")
def nemotron_programs(topo, one_chip):
    """``engine_decode_block`` and the two widest prefill waves the
    serve-workers cell may form under ``prefill_wave_tokens`` 4096 (4 x
    1024 and 16 x 256) of Nemotron-3-Super at the cell's cut and server:
    the 11-layer period ``MEMEMEMEM*E`` at the published widths, 128 of
    512 experts a layer held, a quarter of the vocabulary, 64 slots, 73
    state entries.  Compiled once for the tests below."""
    from ray_tpu.models.configs import get_config, pattern_layer_types

    cfg = get_config(
        "nemotron-3-super-120b-a12b", n_layers=11, vocab_size=32768,
        layer_types=pattern_layer_types("MEMEMEMEM*E"), max_seq_len=4096,
        moe_experts_held=128, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, num_slots=64, max_seq_len=4096,
                                max_prompt_len=1024, kv_pool_pages=4673,
                                prefill_wave_tokens=4096)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        out = {"eng": eng, "block_text": block.as_text(),
               "engine_decode_block": block.compile()}
        for bucket, wave in ((1024, 4), (256, 16)):
            prefill = eng._get_prefill_paged(bucket, wave).lower(
                *_shapes((eng.params, eng._cache,
                          jnp.zeros((wave, eng.packed_width(bucket)),
                                    jnp.int32),
                          jnp.zeros((wave, eng.max_pages), jnp.int32),
                          jax.random.PRNGKey(0)), one_chip))
            out[f"prefill_text_{bucket}"] = prefill.as_text()
            out[f"engine_prefill_{bucket}"] = prefill.compile()
        return out
    finally:
        patch.undo()


def test_nemotron_engine_programs_compile_with_their_kernels(
        nemotron_programs):
    """The decode block holds ``ssm_decode`` once a Mamba-2 layer of the
    period, the paged kernel once (32 query heads on 2 KV heads) and
    ``moe_experts_decode`` once an expert layer, on the period's stacked
    HELD experts of two matrices each: its 1,430 pairs a step are past
    ``DENSE_PAIRS_MAX`` but its 65 rows are a small batch, and the
    sorted ``ragged_dot`` of so few rows lowers to an all-experts
    product over all 1,430 pair rows (1 TFLOP a matrix a layer); the
    state leaves have the shapes the model declares."""
    p = nemotron_programs
    assert {k: v.shape for k, v in p["eng"]._cache.items()} == {
        "kv_pages": (1, 4673, 2, 64, 256),
        "ssm_state": (5, 73, 128, 8192),
        "ssm_conv": (5, 73, 240, 128)}
    assert p["eng"]._cache["ssm_state"].dtype == jnp.float32
    text = p["block_text"]
    assert text.count('kernel_name = "ssm_decode"') == 5
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    assert text.count('kernel_name = "moe_experts_decode"') == 5
    assert "@jit_engine_decode_block" in text
    import re
    hlo = p["engine_decode_block"].as_text()
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*%?moe_experts_decode\S* = ", line)]
    assert len(calls) == 5
    for call in calls:
        assert call.count("bf16[1,128,1024,2688]") == 1      # w_up
        assert call.count("bf16[1,128,2688,1024]") == 1      # w_down
    assert "convolution-base-dilated" not in hlo
    for bucket in (1024, 256):
        assert "@jit_engine_prefill" in p[f"prefill_text_{bucket}"]
        # the chunked (SSD) prefill form is plain XLA, and a wave's
        # 90,112 pairs are the grouped ragged products
        assert "ssm_decode" not in p[f"prefill_text_{bucket}"]
        assert "moe_experts_decode" not in p[f"prefill_text_{bucket}"]
        assert "ragged-dot" in p[f"engine_prefill_{bucket}"].as_text()
        # two products an expert (not gated), in the ONE body the five
        # unrolled layers call (ten at the parent); the custom calls it had
        assert _grouped_products_and_custom_calls(
            p[f"prefill_text_{bucket}"]) == (2, 5)


@pytest.mark.parametrize("name", ["engine_decode_block",
                                  "engine_prefill_1024",
                                  "engine_prefill_256"])
def test_nemotron_programs_address_pool_state_and_experts_in_place(
        nemotron_programs, name):
    """Nothing but parameters and in-place updates produces a result of
    the size of the KV pool, of the state leaf or of the tail leaf, whole
    or one layer of it; and no operation of the decode block makes a
    copy of a layer's held experts (one matrix of them is 704 MB) ahead
    of the kernel that reads them.  Since PR 50 the decode block
    addresses the tail leaf one layer's slab at a time too (``models/gpt.py
    Mamba2Mixer``): over the leaf viewed flat the compiler moved the
    whole 22 MB of it through fast memory a step (``S(1)``)."""
    hlo = nemotron_programs[name].as_text()
    pool = 4673 * 2 * 64 * 256
    state, tail = 73 * 128 * 8192, 73 * 240 * 128
    for what, sizes, dtype in (("pool", (pool,), "bf16"),
                               ("state", (state, 5 * state), "f32"),
                               ("tail", (tail, 5 * tail), "bf16")):
        if what == "tail" and name == "engine_decode_block":
            sizes = sizes[1:]       # a step slices a layer's slab out
        made = _pool_result_producers(hlo, sizes, dtype)
        assert set(made) <= _IN_PLACE, (
            f"{name}: {what}-sized results from {dict(made)}")
    if name == "engine_decode_block":
        assert "S(1)" not in "".join(
            line for line in hlo.splitlines()
            if "bf16[5,73,240,128]" in line.split(" = ")[-1][:40])
        made = _pool_result_producers(hlo, (128 * 1024 * 2688,))
        assert set(made) <= _IN_PLACE, (
            f"{name}: a layer's experts from {dict(made)}")


def test_nemotron_cell_fits_the_chip(nemotron_programs):
    """11.2 GB resident (weights 9.30, state entries 1.55, pool 0.31),
    arguments and temporaries of the decode block and of each of the
    widest prefill waves under 16 GB."""
    p = nemotron_programs
    block = p["engine_decode_block"].memory_analysis()
    assert 11.0e9 < block.argument_size_in_bytes < 11.4e9
    assert (block.argument_size_in_bytes + block.temp_size_in_bytes) < 16e9
    for bucket in (1024, 256):
        prefill = p[f"engine_prefill_{bucket}"].memory_analysis()
        assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
                + block.temp_size_in_bytes) < 16.0e9, (
            bucket, prefill.temp_size_in_bytes / 1e9)


# ---- a step that verifies a draft: two query positions a row, and the
# ---- model that drafts for itself (ISSUE 46)

@pytest.mark.parametrize("windowed", [True, False], ids=["window", "global"])
def test_paged_decode_at_two_queries_a_row(topo, one_chip, windowed):
    """K-EXAONE's verify shape (64 query heads on 8 KV heads of 128, two
    positions a row: 16 rows of the MXU tile a KV head) is still ONE
    custom call ``paged_attention_decode`` that writes both rows into
    the pool it is given, aliased."""
    from ray_tpu.ops.paged_attention import paged_attention

    rows, heads, kvh, hd, mp = 33, 64, 8, 128, 64
    ps, pages, layers = 64, 129, 7

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(
        lambda q, kv, bt, ln, layer, window, live, new: paged_attention(
            q, kv, bt, ln, layer=layer, impl="tpu", new_rows=new, live=live,
            window=window if windowed else None), donate_argnums=(1,)
    ).lower(sds((rows, 2, heads, hd), jnp.bfloat16),
            sds((layers, pages, kvh, ps, 2 * hd), jnp.bfloat16),
            sds((rows, mp), jnp.int32), sds((rows,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32), sds((rows,), jnp.bool_),
            sds((rows, 2, kvh, 2 * hd), jnp.bfloat16))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    hlo = lowered.compile().as_text()
    assert "input_output_alias={ {1}: (1, {}, may-alias) }" in hlo
    made = _pool_result_producers(hlo, (layers * pages * kvh * ps * 2 * hd,))
    assert set(made) <= {"parameter", "get-tuple-element", "bitcast"}, made


@pytest.fixture(scope="module")
def k_exaone_programs(topo, one_chip):
    """``engine_decode_block`` (a drafting engine's: verify, accept,
    draft) and the widest prefill wave the serve-think cell may form
    under ``prefill_wave_tokens`` 4096 (4 x 1024) of K-EXAONE-236B-A23B
    at the cell's cut and server: the dense layer and five expert layers
    at the published widths, 16 of 128 experts a layer held, an eighth
    of the vocabulary, the module, 32 slots, 1,400 pages in 7 pool
    layers.  Compiled once for the tests below."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("k-exaone-236b-a23b", n_layers=6, vocab_size=19200,
                     moe_experts_held=16, dtype=jnp.bfloat16,
                     param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, max_seq_len=4096,
                                max_prompt_len=1024, kv_pool_pages=1400,
                                prefill_wave_tokens=4096)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        prefill = eng._get_prefill_paged(1024, 4).lower(
            *_shapes((eng.params, eng._cache,
                      jnp.zeros((4, eng.packed_width(1024)), jnp.int32),
                      jnp.zeros((4, eng.max_pages), jnp.int32),
                      jax.random.PRNGKey(0)), one_chip))
        return {"eng": eng, "block_text": block.as_text(),
                "engine_decode_block": block.compile(),
                "prefill_text": prefill.as_text(),
                "engine_prefill": prefill.compile()}
    finally:
        patch.undo()


def test_k_exaone_engine_programs_compile_with_their_kernels(
        k_exaone_programs):
    """The decode block holds the paged kernel three times (the dense
    layer, the scanned expert layers' ONE traced body, the module) and
    ``moe_experts_decode`` twice (the scanned stack's ``[5, 16, ...]``
    held experts, the module's stack of one): 66 step rows at top-8 are
    528 pairs, past ``DENSE_PAIRS_MAX``, but a small batch by rows.  The
    state carries each row's draft and its logits."""
    import re
    p = k_exaone_programs
    eng = p["eng"]
    assert {k: v.shape for k, v in eng._cache.items()} == {
        "kv_pages": (7, 1400, 8, 64, 256)}
    assert [a.shape for a in eng._state[-2:]] == [(33,), (33, 19200)]
    text = p["block_text"]
    assert text.count('kernel_name = "paged_attention_decode"') == 3
    assert text.count('kernel_name = "moe_experts_decode"') == 2
    assert "@jit_engine_decode_block" in text
    hlo = p["engine_decode_block"].as_text()
    for scope in ("spec_verify", "spec_accept", "mtp_draft"):
        assert f"/{scope}/" in hlo, scope   # in the operations' metadata
    calls = [line for line in hlo.splitlines()
             if re.match(r"\s*%?moe_experts_decode\S* = ", line)]
    assert sorted(call.count("bf16[5,16,6144,2048]") for call in calls) \
        == [0, 2]                                   # w_gate, w_up
    assert sorted(call.count("bf16[1,16,6144,2048]") for call in calls) \
        == [0, 2]
    for name in ("engine_decode_block", "engine_prefill"):
        made = _pool_result_producers(p[name].as_text(),
                                      (1400 * 8 * 64 * 256,
                                       7 * 1400 * 8 * 64 * 256))
        assert set(made) <= _IN_PLACE, f"{name}: pool-sized {dict(made)}"
    # (the module's own experts are that size: parameters, bitcast to
    # their stack of one)
    made = _pool_result_producers(hlo, (16 * 6144 * 2048,))
    assert set(made) <= _IN_PLACE, f"a layer's experts from {dict(made)}"
    # the prefill wave's grouped products: three in the scanned stack's
    # one body and three in the module, and no custom call
    assert "ragged-dot" in p["engine_prefill"].as_text()
    assert _grouped_products_and_custom_calls(p["prefill_text"]) == (6, 0)


def test_k_exaone_cell_fits_the_chip(k_exaone_programs):
    """13.2 GB resident (weights 10.60, pool 2.57), arguments and
    temporaries of the decode block and of the widest prefill wave under
    15.5 GB: the check's float32 reference runs beside them."""
    p = k_exaone_programs
    block = p["engine_decode_block"].memory_analysis()
    assert 13.0e9 < block.argument_size_in_bytes < 13.3e9
    assert (block.argument_size_in_bytes + block.temp_size_in_bytes) < 15e9
    prefill = p["engine_prefill"].memory_analysis()
    assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
            ) < 15.5e9, prefill.temp_size_in_bytes / 1e9


# ---- a model whose Mamba-2 layers carry a SwiGLU each, whole on one
# ---- chip (ISSUE 50)

@pytest.fixture(scope="module")
def granite_programs(topo, one_chip):
    """``engine_decode_block`` and the two widest prefill waves the
    serve-burst cell may form under ``prefill_wave_tokens`` 2048 (4 x 512
    and 32 x 64) of granite-4.0-h-micro WHOLE at the cell's server: 40
    layers (four 10-layer periods), the full 100,352-row tied table, 64
    slots, 73 state entries, 2,081 pages.  Compiled once for the tests
    below."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("granite-4.0-h-micro", max_seq_len=2048,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, num_slots=64, max_seq_len=2048,
                                max_prompt_len=512, kv_pool_pages=2081,
                                prefill_wave_tokens=2048)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        out = {"eng": eng, "block_text": block.as_text(),
               "engine_decode_block": block.compile()}
        for bucket, wave in ((512, 4), (64, 32)):
            prefill = eng._get_prefill_paged(bucket, wave).lower(
                *_shapes((eng.params, eng._cache,
                          jnp.zeros((wave, eng.packed_width(bucket)),
                                    jnp.int32),
                          jnp.zeros((wave, eng.max_pages), jnp.int32),
                          jax.random.PRNGKey(0)), one_chip))
            out[f"prefill_text_{bucket}"] = prefill.as_text()
            out[f"engine_prefill_{bucket}"] = prefill.compile()
        return out
    finally:
        patch.undo()


def test_granite_engine_programs_compile_with_their_kernels(
        granite_programs):
    """The decode block's scanned period holds ``ssm_decode`` once a
    Mamba-2 layer of it (nine: ONE group of 64 heads x 64 = 4096 lanes)
    and the paged kernel once (32 query heads on 8 KV heads of 64; its
    scale is a constant of the kernel's body, held by numbers in
    tests/test_granite_h.py and on the chip); the state leaf is the
    36-layer one the model declares; the prefill waves are plain XLA."""
    p = granite_programs
    assert {k: v.shape for k, v in p["eng"]._cache.items()} == {
        "kv_pages": (4, 2081, 8, 64, 128),
        "ssm_state": (36, 73, 128, 4096),
        "ssm_conv": (36, 73, 102, 128)}
    assert p["eng"]._cache["ssm_state"].dtype == jnp.float32
    text = p["block_text"]
    assert text.count('kernel_name = "ssm_decode"') == 9
    assert text.count('kernel_name = "paged_attention_decode"') == 1
    assert "@jit_engine_decode_block" in text
    for bucket in (512, 64):
        assert "@jit_engine_prefill" in p[f"prefill_text_{bucket}"]
        assert "ssm_decode" not in p[f"prefill_text_{bucket}"]


@pytest.mark.parametrize("name", ["engine_decode_block",
                                  "engine_prefill_512",
                                  "engine_prefill_64"])
def test_granite_programs_address_pool_and_state_in_place(
        granite_programs, name):
    """Nothing but parameters and in-place updates produces a result of
    the size of the KV pool or of the 5.5 GB state leaf, whole or one
    layer of it (a copy of the leaf would not fit the chip beside it),
    nor one of the size of the 69 MB tail leaf, in the decode block
    either: a step addresses it one layer's 1.9 MB slab at a time
    (``models/gpt.py Mamba2Mixer``), where over the leaf viewed flat the
    compiler moved the whole leaf through fast memory a layer (a third
    of a step: PERF.md section 6, PR 50)."""
    hlo = granite_programs[name].as_text()
    pool = 2081 * 8 * 64 * 128
    state, tail = 73 * 128 * 4096, 73 * 102 * 128
    for what, sizes, dtype in (("pool", (pool, 4 * pool), "bf16"),
                               ("state", (state, 36 * state), "f32"),
                               ("tail", (36 * tail,), "bf16")):
        made = _pool_result_producers(hlo, sizes, dtype)
        assert set(made) <= _IN_PLACE, (
            f"{name}: {what}-sized results from {dict(made)}")
    if name == "engine_decode_block":
        assert "S(1)" not in "".join(
            line for line in hlo.splitlines()
            if "bf16[36,73,102,128]" in line.split(" = ")[-1][:40])


def test_granite_cell_fits_the_chip(granite_programs):
    """13.1 GB resident (weights 6.38, state entries 5.58 and their tails
    0.07, pool 1.09), arguments and temporaries of the decode block and
    of each of the widest prefill waves under 16 GB."""
    p = granite_programs
    block = p["engine_decode_block"].memory_analysis()
    assert 13.0e9 < block.argument_size_in_bytes < 13.3e9
    assert (block.argument_size_in_bytes + block.temp_size_in_bytes) < 16e9
    for bucket in (512, 64):
        prefill = p[f"engine_prefill_{bucket}"].memory_analysis()
        assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
                + block.temp_size_in_bytes) < 16.0e9, (
            bucket, prefill.temp_size_in_bytes / 1e9)


# ---- all 27 layers of Kimi Linear beside 12.9 GB resident on ONE
# ---- chip (ISSUE 55)

@pytest.fixture(scope="module")
def kimi_programs(topo, one_chip):
    """``engine_decode_block`` and the widest warmed prefill waves of the
    serve-longgen cell (one prompt of 8,192 tokens, and 8 x 1,024 under
    ``prefill_wave_tokens`` 8192) of kimi-linear-48b-a3b at the cell's
    server: all 27 layers (head run of 4, five scanned periods, tail run
    of 3), 16 of 256 experts held, the full 163,840-row head, 32 slots,
    41 state entries, 2,048 pages.  Compiled once for the tests below."""
    from ray_tpu.models.configs import get_config

    cfg = get_config("kimi-linear-48b-a3b", moe_experts_held=16,
                     max_seq_len=12288, dtype=jnp.bfloat16,
                     param_dtype=jnp.bfloat16)
    patch = pytest.MonkeyPatch()
    _answer_tpu(patch)
    try:
        eng = _described_engine(cfg, patch, num_slots=32, max_seq_len=12288,
                                max_prompt_len=8192, kv_pool_pages=2048,
                                prefill_wave_tokens=8192)
        block = eng._block_jit.lower(
            *_shapes((eng.params, eng._cache, eng._state) + eng._no_admit,
                     one_chip))
        out = {"eng": eng, "block_text": block.as_text(),
               "engine_decode_block": block.compile()}
        for bucket, wave in ((8192, 1), (1024, 8)):
            prefill = eng._get_prefill_paged(bucket, wave).lower(
                *_shapes((eng.params, eng._cache,
                          jnp.zeros((wave, eng.packed_width(bucket)),
                                    jnp.int32),
                          jnp.zeros((wave, eng.max_pages), jnp.int32),
                          jax.random.PRNGKey(0)), one_chip))
            out[f"prefill_text_{bucket}"] = prefill.as_text()
            out[f"engine_prefill_{bucket}"] = prefill.compile()
        return out
    finally:
        patch.undo()


def test_kimi_engine_programs_compile_with_their_kernels(kimi_programs):
    """The decode block holds ``kda_decode`` once a KDA layer of the head
    run, of the scanned period and of the tail run (3 + 3 + 2), the
    absorbed latent kernel once a latent layer of each (1 + 1 + 1) and
    the expert kernel once an expert layer (3 + 4 + 3); no ``gdn_decode``
    (the scalar rule's name); a latent pool of the 7 pool layers alone
    and state leaves of the 20 KDA layers; the prefill waves are plain
    XLA."""
    p = kimi_programs
    assert {k: v.shape for k, v in p["eng"]._cache.items()} == {
        "kv_pages": (7, 2048, 1, 64, 640),
        "gdn_state": (20, 41, 128, 4096),
        "gdn_conv": (20, 41, 288, 128)}
    assert p["eng"]._cache["gdn_state"].dtype == jnp.float32
    text = p["block_text"]
    assert text.count('kernel_name = "kda_decode"') == 8
    assert text.count('kernel_name = "gdn_decode"') == 0
    assert text.count('kernel_name = "paged_attention_decode"') == 3
    assert text.count('kernel_name = "moe_experts_decode"') == 10
    assert "@jit_engine_decode_block" in text
    for bucket in (8192, 1024):
        assert "@jit_engine_prefill" in p[f"prefill_text_{bucket}"]
        assert "kda_decode" not in p[f"prefill_text_{bucket}"]
        # three grouped products in each of the TWO bodies its ten
        # traced expert layers call (the runs' stacks of one, the
        # period's stack; thirty at the parent); no custom call but the
        # latent layers' flash kernels
        assert "ragged-dot" in p[f"engine_prefill_{bucket}"].as_text()
        text = p[f"prefill_text_{bucket}"]
        assert _grouped_products_and_custom_calls(text) == (
            6, text.count('kernel_name = "flash_fwd"'))


@pytest.mark.parametrize("name", ["engine_decode_block",
                                  "engine_prefill_8192",
                                  "engine_prefill_1024"])
def test_kimi_programs_address_pool_and_state_in_place(kimi_programs, name):
    """Nothing but parameters and in-place updates produces a result of
    the size of the latent pool or of the 1.76 GB state leaf, whole or
    one layer of it."""
    hlo = kimi_programs[name].as_text()
    pool = 2048 * 64 * 640
    state = 41 * 128 * 4096
    for what, sizes, dtype in (("pool", (pool, 7 * pool), "bf16"),
                               ("state", (state, 20 * state), "f32")):
        made = _pool_result_producers(hlo, sizes, dtype)
        assert set(made) <= _IN_PLACE, (
            f"{name}: {what}-sized results from {dict(made)}")


def test_kimi_cell_fits_the_chip(kimi_programs):
    """12.9 GB resident (weights 9.91, state entries 1.76 and their tails
    0.06, pool 1.17), arguments and temporaries of the decode block and
    of each of the widest prefill waves under 16 GB."""
    p = kimi_programs
    block = p["engine_decode_block"].memory_analysis()
    assert 12.7e9 < block.argument_size_in_bytes < 13.1e9
    assert (block.argument_size_in_bytes + block.temp_size_in_bytes) < 16e9
    for bucket in (8192, 1024):
        prefill = p[f"engine_prefill_{bucket}"].memory_analysis()
        assert (prefill.argument_size_in_bytes + prefill.temp_size_in_bytes
                + block.temp_size_in_bytes) < 16.0e9, (
            bucket, prefill.temp_size_in_bytes / 1e9)
