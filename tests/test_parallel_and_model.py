"""Mesh/sharding-rule tests and end-to-end sharded training on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import GPT, get_config
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (LOGICAL_RULES, logical_spec,
                                       logical_pspec_to_mesh)
from ray_tpu.train.step import OptimizerConfig, make_sharded_train


def test_mesh_config_resolution():
    assert MeshConfig(data=-1).resolve(8) == (1, 8, 1, 1, 1)
    assert MeshConfig(data=-1, fsdp=2, tensor=2).resolve(8) == (1, 2, 2, 1, 2)
    assert MeshConfig(data=2, fsdp=2, context=2, tensor=1).resolve(8) == \
        (1, 2, 2, 2, 1)
    assert MeshConfig(stage=2, data=-1).resolve(8) == (2, 4, 1, 1, 1)
    with pytest.raises(ValueError):
        MeshConfig(data=3).resolve(8)
    with pytest.raises(ValueError):
        MeshConfig(data=-1, fsdp=-1).resolve(8)


def test_logical_spec_prunes_size1_axes():
    mesh = build_mesh(MeshConfig(data=4, fsdp=2))  # context/tensor size 1
    spec = logical_spec(("batch", "seq", "embed"), mesh)
    assert spec == P(("data", "fsdp"), None, "fsdp")
    # without a mesh, no pruning
    assert logical_spec(("seq",)) == P("context")


def test_logical_pspec_translation():
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    s = logical_pspec_to_mesh(P(None, "embed", "heads"), mesh)
    assert s.spec == P(None, "fsdp", "tensor")
    s2 = logical_pspec_to_mesh(None, mesh)
    assert s2.spec == P()


@pytest.mark.parametrize("mesh_cfg,attn", [
    (MeshConfig(data=-1), "xla"),                          # pure DP
    (MeshConfig(data=2, fsdp=2, tensor=2), "xla"),         # DP+FSDP+TP
    (MeshConfig(data=2, fsdp=2, context=2), "ring"),       # DP+FSDP+CP(ring)
    (MeshConfig(data=2, fsdp=2, context=2), "ulysses"),    # DP+FSDP+CP(a2a)
])
def test_sharded_training_loss_decreases(mesh_cfg, attn):
    mesh = build_mesh(mesh_cfg)
    cfg = get_config("tiny", max_seq_len=64, attention_impl=attn)
    model = GPT(cfg, mesh=mesh)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 65)),
                                   jnp.int32)}
    init_fn, step_fn, state_sh, _ = make_sharded_train(
        model, mesh, OptimizerConfig(learning_rate=1e-3, warmup_steps=1,
                                     decay_steps=100),
        example_batch=batch)
    state = init_fn(jax.random.PRNGKey(0), batch)

    # parameters are born sharded as the rules dictate
    wq = state.params["blocks"]["attn"]["wq"]["kernel"].value
    if mesh.shape["fsdp"] > 1:
        flat_axes = [a for ax in wq.sharding.spec if ax is not None
                     for a in (ax if isinstance(ax, tuple) else (ax,))]
        assert "fsdp" in flat_axes, wq.sharding.spec

    losses = []
    for _ in range(8):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_model_forward_deterministic_across_shardings():
    """Same seed -> same logits whether run replicated or TP-sharded."""
    cfg = get_config("tiny", max_seq_len=32)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (8, 16)), jnp.int32)

    model_plain = GPT(cfg)
    vars_plain = model_plain.init(jax.random.PRNGKey(7), tokens)
    out_plain = model_plain.apply(vars_plain, tokens)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    model_mesh = GPT(cfg, mesh=mesh)
    vars_mesh = model_mesh.init(jax.random.PRNGKey(7), tokens)
    out_mesh = jax.jit(model_mesh.apply)(vars_mesh, tokens)
    np.testing.assert_allclose(out_plain, out_mesh, atol=2e-4)


def test_decode_cache_matches_full_forward():
    cfg = get_config("tiny", max_seq_len=32, scan_layers=True)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (1, 10)), jnp.int32)
    model = GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    full_logits = model.apply(variables, tokens)

    decode_model = GPT(cfg, decode=True)
    dvars = decode_model.init(jax.random.PRNGKey(0), tokens[:, :1])
    cache = dvars["cache"]
    outs = []
    for i in range(tokens.shape[1]):
        logits, mut = decode_model.apply(
            {"params": variables["params"], "cache": cache},
            tokens[:, i:i + 1],
            jnp.full((1, 1), i, jnp.int32),
            mutable=["cache"])
        cache = mut["cache"]
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(dec, full_logits, atol=1e-3)


def test_moe_training_loss_decreases():
    """Tiny MoE model trains end-to-end with expert parallelism + aux loss."""
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    cfg = get_config("tiny-moe", max_seq_len=64)
    model = GPT(cfg, mesh=mesh)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 65)),
                                   jnp.int32)}
    init_fn, step_fn, _, _ = make_sharded_train(
        model, mesh, OptimizerConfig(learning_rate=1e-3, warmup_steps=1,
                                     decay_steps=100),
        example_batch=batch)
    state = init_fn(jax.random.PRNGKey(0), batch)
    # expert weights exist, carry the expert dim, and shard over data axes
    moe_w = state.params["blocks"]["moe"]["w_gate"].value
    assert moe_w.shape[1] == cfg.moe_experts  # [layers, E, D, F] under scan
    losses, aux = [], []
    for _ in range(8):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
        aux.append(float(m["moe_aux_loss"]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all() and np.isfinite(aux).all()


def test_generation_greedy_matches_full_forward():
    """Greedy KV-cache generation equals argmax over repeated full
    forwards (decode-path correctness end-to-end)."""
    from ray_tpu.models import Generator, get_config

    cfg = get_config("tiny", max_seq_len=64)
    model_full = GPT(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(1, cfg.vocab_size, (2, 8)),
        jnp.int32)
    variables = model_full.init(jax.random.PRNGKey(0), tokens)

    gen = Generator(cfg, variables["params"])
    out = gen.generate(tokens, max_new_tokens=6, temperature=0.0)
    assert out.shape == (2, 6)

    # reference: greedy via full re-forward each step
    cur = tokens
    for i in range(6):
        logits = model_full.apply(variables, cur)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        np.testing.assert_array_equal(np.asarray(out[:, i]), np.asarray(nxt))
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)


def test_generation_samplers_and_eos():
    from ray_tpu.models import Generator, get_config, sample_logits

    cfg = get_config("tiny", max_seq_len=64)
    model = GPT(cfg)
    tokens = jnp.ones((1, 4), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    gen = Generator(cfg, variables["params"])

    out = gen.generate(tokens, max_new_tokens=8, temperature=0.8,
                       top_k=16, top_p=0.9, rng=jax.random.PRNGKey(1))
    assert out.shape[1] <= 8 and out.dtype == jnp.int32

    # eos padding: force eos to be whatever the first sampled token is
    first = int(out[0, 0])
    out2 = gen.generate(tokens, max_new_tokens=8, temperature=0.8,
                        top_k=16, top_p=0.9, eos_id=first,
                        rng=jax.random.PRNGKey(1))
    assert int(out2[0, 0]) == first and out2.shape[1] <= 8

    # sampler math: top-k=1 equals greedy
    logits = jax.random.normal(jax.random.PRNGKey(2), (3, 50))
    a = sample_logits(jax.random.PRNGKey(3), logits, temperature=1.0,
                      top_k=1)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(jnp.argmax(logits, -1)))


def _tiny_lm_batch(cfg, rows):
    rng = np.random.default_rng(0)
    return {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (rows, 33)), jnp.int32)}


def test_fused_and_split_steps_agree():
    """``make_sharded_train`` (one jit) and ``make_grad_apply_step``
    (``train_grad`` + ``train_apply``) are one construction: from the
    same seed and batch they give the same loss and the same
    parameters, up to float32 reassociation (two steps each)."""
    from ray_tpu.train.sharded import make_grad_apply_step
    from ray_tpu.train.step import \
        make_grad_apply_step as defined_in_step

    assert make_grad_apply_step is defined_in_step
    mesh = build_mesh(MeshConfig(data=-1))
    cfg = get_config("tiny", max_seq_len=64, attention_impl="xla")
    model = GPT(cfg, mesh=mesh)
    batch = _tiny_lm_batch(cfg, len(jax.devices()))
    opt = OptimizerConfig(learning_rate=1e-2, warmup_steps=1,
                          decay_steps=10)
    init_fn, step_fn, fused_sh, _ = make_sharded_train(
        model, mesh, opt, example_batch=batch)
    init2, grad_fn, apply_fn, split_sh, _ = make_grad_apply_step(
        model, mesh, opt, example_batch=batch)
    assert jax.tree.leaves(fused_sh) == jax.tree.leaves(split_sh)
    key = jax.random.PRNGKey(0)
    before = jax.device_get(init2(key, batch).params)
    fused, split = init_fn(key, batch), init2(key, batch)
    for _ in range(2):      # the schedule's first step has rate 0
        fused, fused_metrics = step_fn(fused, batch)
        grads, metrics = grad_fn(split, batch)
        split = apply_fn(split, grads)
        assert float(metrics["loss"]) == pytest.approx(
            float(fused_metrics["loss"]), rel=1e-6)
    moved = 0.0
    for was, a, b in zip(jax.tree.leaves(before),
                         jax.tree.leaves(fused.params),
                         jax.tree.leaves(split.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
        moved = max(moved, float(np.abs(np.asarray(a) - was).max()))
    assert moved > 1e-4, "the steps moved no parameter"
    assert int(split.step) == int(fused.step) == 2


def test_split_step_takes_init_inputs():
    """The split step initialises a model whose ``init`` does not take
    ``tokens[:, :-1]`` (T5: encoder and decoder tokens), as the fused
    one does: they share ``train_init``."""
    from ray_tpu.models.t5 import T5, seq2seq_loss_fn, t5_init_inputs
    from ray_tpu.train.step import make_grad_apply_step

    cfg = get_config("tiny", max_seq_len=32)
    mesh = build_mesh(MeshConfig(data=-1))
    rng = np.random.default_rng(0)
    batch = {"enc_tokens": jnp.asarray(rng.integers(1, 256, (8, 12)),
                                       jnp.int32),
             "dec_tokens": jnp.asarray(rng.integers(1, 256, (8, 9)),
                                       jnp.int32)}
    init_fn, grad_fn, apply_fn, _, _ = make_grad_apply_step(
        T5(cfg, mesh=mesh), mesh,
        OptimizerConfig(warmup_steps=1, decay_steps=20),
        loss_fn=seq2seq_loss_fn, example_batch=batch,
        init_inputs=t5_init_inputs)
    state = init_fn(jax.random.PRNGKey(0), batch)
    losses = []
    for _ in range(4):
        grads, metrics = grad_fn(state, batch)
        state = apply_fn(state, grads)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("remat,policy", [
    (False, "dots"), (True, "nothing"), (True, "block_outs"),
    (True, "dots"), (True, "dots_all")])
def test_memory_options_do_not_rename_weights(remat, policy):
    """A model's parameter tree does not depend on a memory option:
    without remat and under each of ``stack_layers``' policies ``init``
    yields one tree (a checkpoint saved under one loads under another),
    and logits and gradients agree."""
    import dataclasses

    from ray_tpu.train.step import lm_loss_fn

    base = get_config("tiny", max_seq_len=64, remat=False)
    cfg = get_config("tiny", max_seq_len=64, remat=remat,
                     remat_policy=policy)
    # no field is left that splits the stack in two
    assert {f.name for f in dataclasses.fields(cfg)
            if f.name.startswith("remat")} == {"remat", "remat_policy"}
    batch = _tiny_lm_batch(cfg, 2)
    tokens = batch["tokens"][:, :-1]
    want = GPT(base).init(jax.random.PRNGKey(0), tokens)["params"]
    got = GPT(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [k for k in got if "blocks" in k] == ["blocks"]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        np.asarray(GPT(cfg).apply({"params": want}, tokens)),
        np.asarray(GPT(base).apply({"params": want}, tokens)),
        rtol=1e-5, atol=1e-5)

    def grads(model):
        return jax.grad(lambda p: lm_loss_fn(model.apply, p, batch)[0])(want)

    for a, b in zip(jax.tree.leaves(grads(GPT(cfg))),
                    jax.tree.leaves(grads(GPT(base)))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["auto", "xla", "flash", "ring", "ulysses"])
def test_attention_impl_legal_values_construct(impl):
    from ray_tpu.models.configs import ATTENTION_IMPLS

    assert impl in ATTENTION_IMPLS and len(ATTENTION_IMPLS) == 5
    assert get_config("tiny", attention_impl=impl).attention_impl == impl


@pytest.mark.parametrize("impl", ["splash", "flahs"])
def test_attention_impl_is_checked_where_the_config_is_built(impl):
    """A stale or misspelt ``attention_impl`` fails in ``get_config``,
    by name and with the legal values, not at trace time inside
    ``attention()``."""
    with pytest.raises(ValueError) as err:
        get_config("tiny", attention_impl=impl)
    assert repr(impl) in str(err.value)
    for legal in ("auto", "xla", "flash", "ring", "ulysses"):
        assert legal in str(err.value)


