"""Correctness tests for attention kernels and fused layers (CPU, 8-dev mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.ops.attention import attention, xla_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.ring_attention import ring_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla_fwd_bwd(causal):
    key = jax.random.PRNGKey(0)
    B, S, H, D = 2, 128, 2, 32
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    o_ref = xla_attention(q, k, v, causal=causal)
    o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(o, o_ref, atol=2e-5)

    g_ref = jax.grad(lambda *a: (xla_attention(*a, causal=causal) ** 2).sum(),
                     (0, 1, 2))(q, k, v)
    g = jax.grad(lambda *a: (flash_attention(*a, causal=causal, block_q=32,
                                             block_k=32) ** 2).sum(),
                 (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=2e-4)


def _flash_and_xla_with_grads(q, k, v, do, causal, **blocks):
    """(o, dq, dk, dv) of the flash kernels, and of float32 XLA attention
    on the same values."""
    def run(f, *a):
        o, vjp = jax.vjp(f, *a)
        return (o,) + tuple(vjp(do.astype(o.dtype)))
    got = run(lambda *a: flash_attention(*a, causal=causal, **blocks),
              q, k, v)
    want = run(lambda *a: xla_attention(*a, causal=causal),
               *(x.astype(jnp.float32) for x in (q, k, v)))
    return got, want


# float32 operands keep float32 products: the tolerances of
# test_flash_matches_xla_fwd_bwd.  bf16 operands: a bf16 rounding is
# 2**-9 relative, and a result passes through three of them (the
# operands' own, the probabilities or ds ahead of the second product, the
# output's cast), each of a value no larger than the result's largest:
# 2**-6 of the reference's largest magnitude is four such roundings.
_F32_TOL = dict(o=2e-5, grad=2e-4)
# (dtype, sequence or (q_len, kv_len), head_dim, causal, block keywords,
# span): ``span`` None is the module's own (1024: one span holds these
# sequences, tiles are skipped and masked inside it); 128 makes the GRID
# walk several spans, so whole span pairs are skipped and their index
# maps clamped, and the backward's dq sums over several kv spans while
# its dk and dv sum over several q spans
_FLASH_CASES = {
    # several tiles, block_q != block_k, both orders, causal and not
    "f32-q64-k128-causal": (jnp.float32, 256, 32, True, dict(block_q=64, block_k=128), None),
    "f32-q128-k64-causal": (jnp.float32, 256, 32, True, dict(block_q=128, block_k=64), None),
    "f32-q64-k128-full": (jnp.float32, 256, 32, False, dict(block_q=64, block_k=128), None),
    "f32-q128-k64-full": (jnp.float32, 256, 32, False, dict(block_q=128, block_k=64), None),
    "f32-q64-k128-causal-spans": (jnp.float32, 384, 32, True, dict(block_q=64, block_k=128), 128),
    "f32-q128-k64-causal-spans": (jnp.float32, 384, 32, True, dict(block_q=128, block_k=64), 128),
    "f32-q64-k128-full-spans": (jnp.float32, 384, 32, False, dict(block_q=64, block_k=128), 128),
    # the cell's operand dtype at its head_dim and at 128, blocks as
    # plan_blocks gives them
    "bf16-d64-causal": (jnp.bfloat16, 1024, 64, True, {}, None),
    "bf16-d128-causal": (jnp.bfloat16, 512, 128, True, {}, None),
    "bf16-d64-full": (jnp.bfloat16, 512, 64, False, {}, None),
    "bf16-d64-causal-spans": (jnp.bfloat16, 512, 64, True, {}, 256),
    # four spans a sequence: a q span's dq is the sum of up to four kv
    # spans' visits, written at each and whole after the diagonal's
    "f32-d64-causal-4spans": (jnp.float32, 512, 64, True, {}, 128),
    "bf16-d64-causal-4spans": (jnp.bfloat16, 512, 64, True, {}, 128),
    # an encoder's rectangular call over several spans, both ways
    "f32-384x640-full-spans": (jnp.float32, (384, 640), 32, False, {}, 128),
    "f32-640x384-full-spans": (jnp.float32, (640, 384), 32, False, {}, 128),
    "bf16-384x640-full-spans": (jnp.bfloat16, (384, 640), 64, False, {}, 128),
    # no lane-aligned block divides these: one tile, the whole sequence
    "f32-197-full": (jnp.float32, 197, 32, False, {}, None),
    "f32-200-causal": (jnp.float32, 200, 32, True, {}, None),
}


@pytest.fixture
def flash_span(monkeypatch):
    """Set the kernels' span (the grid's block) for one test."""
    import importlib
    mod = importlib.import_module("ray_tpu.ops.flash_attention")
    return lambda span: monkeypatch.setattr(mod, "SPAN", span)


@pytest.mark.parametrize("case", list(_FLASH_CASES))
def test_flash_blocks_match_xla_fwd_bwd(case, flash_span):
    dtype, S, D, causal, blocks, span = _FLASH_CASES[case]
    if span:
        flash_span(span)
    q_len, kv_len = S if isinstance(S, tuple) else (S, S)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, do = [jax.random.normal(kk, (1, n, 2, D), jnp.float32)
                   .astype(dtype)
                   for kk, n in zip(keys, (q_len, kv_len, kv_len, q_len))]
    got, want = _flash_and_xla_with_grads(q, k, v, do, causal, **blocks)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        if dtype == jnp.float32:
            atol = _F32_TOL["o" if name == "o" else "grad"]
        else:
            atol = 2.0 ** -6 * float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32), b, atol=atol,
                                   rtol=0, err_msg=f"{case}: {name}")


@pytest.mark.parametrize("span", [1024, 128], ids=["tiles", "spans"])
def test_flash_causal_skipped_blocks_are_not_read(span, flash_span):
    """What lies above the diagonal's blocks must not reach the result,
    not even multiplied by a zero probability (0 * NaN is NaN).  Forward
    and backward skip keys past a q block's last row, which is queries
    before a kv block's first column.  ``tiles``: the skipping inside
    one span; ``spans``: the grid's, whole span pairs."""
    flash_span(span)
    S, D, blk, cut = 512, 32, 128, 256
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, do = [jax.random.normal(kk, (1, S, 2, D), jnp.float32)
                   for kk in keys]

    def run(q, k, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention(
            *a, causal=True, block_q=blk, block_k=blk), q, k, v)
        return (o,) + tuple(vjp(do))

    o, dq, dk, dv = run(q, k, v, do)
    # keys and values from `cut` on are NaN: query rows before it never
    # needed them
    nan_tail = lambda x: x.at[:, cut:].set(jnp.nan)
    o_n, dq_n, _, _ = run(q, nan_tail(k), nan_tail(v), do)
    np.testing.assert_array_equal(o_n[:, :cut], o[:, :cut])
    np.testing.assert_array_equal(dq_n[:, :cut], dq[:, :cut])
    # queries and their cotangents before `cut` are NaN: key rows from it
    # on never needed them
    nan_head = lambda x: x.at[:, :cut].set(jnp.nan)
    _, _, dk_n, dv_n = run(nan_head(q), k, v, nan_head(do))
    np.testing.assert_array_equal(dk_n[:, cut:], dk[:, cut:])
    np.testing.assert_array_equal(dv_n[:, cut:], dv[:, cut:])


# (dtype, head width of q and k, of v): the training cell's heads, and
# the latent prefill's (keys 192 wide, values 128, which ride
# zero-padded to the keys' width as ``_prefill_attend`` sends them)
_Q_LENS_HEADS = {"d64": (jnp.float32, 64, 64),
                 "latent-192-128": (jnp.bfloat16, 192, 128)}
_Q_LENS_SPAN, _Q_LENS_SEQ = 128, 512


@pytest.mark.parametrize("heads", list(_Q_LENS_HEADS))
@pytest.mark.parametrize(
    "length", [1, _Q_LENS_SPAN - 1, _Q_LENS_SPAN, _Q_LENS_SPAN + 1,
               _Q_LENS_SEQ],
    ids=["one", "span-1", "span", "span+1", "whole"])
def test_flash_q_lens_skips_the_query_spans_past_a_row(length, heads,
                                                       flash_span):
    """``q_lens``: a row's real prefix comes out as XLA attention on that
    prefix alone gives it; every query span that starts at or past the
    length comes out as exactly zero, whatever the operands hold there
    (NaN here: a skipped span reads nothing and writes zeros, it does
    not multiply by zero); the rest of the span the length ends in is
    computed and finite.  Row 0 has the length under test, row 1 the
    whole sequence: a wave's rows differ."""
    flash_span(_Q_LENS_SPAN)
    dtype, dk, dv = _Q_LENS_HEADS[heads]
    S, span = _Q_LENS_SEQ, _Q_LENS_SPAN
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k = (jax.random.normal(kk, (2, S, 2, dk), jnp.float32).astype(dtype)
            for kk in keys[:2])
    v = jax.random.normal(keys[2], (2, S, 2, dv), jnp.float32).astype(dtype)
    edge = -(-length // span) * span        # where the skipped spans start
    nan_past = lambda x: x.at[0, edge:].set(jnp.nan)       # noqa: E731
    wide = jnp.pad(v, [(0, 0)] * 3 + [(0, dk - dv)])
    got = flash_attention(nan_past(q), nan_past(k), nan_past(wide),
                          causal=True, block_q=64, block_k=128,
                          q_lens=jnp.asarray([length, S], jnp.int32)
                          )[..., :dv]
    assert got.dtype == dtype and bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got[0, edge:], np.float32), 0)
    f32 = lambda x: x.astype(jnp.float32)                  # noqa: E731
    atol = 2e-5 if dtype == jnp.float32 else 2.0 ** -6
    for row, n in ((0, length), (1, S)):
        want = xla_attention(f32(q[row:row + 1, :n]), f32(k[row:row + 1, :n]),
                             f32(v[row:row + 1, :n]), causal=True)
        np.testing.assert_allclose(np.asarray(got[row, :n], np.float32),
                                   want[0], atol=atol, rtol=0)


def test_flash_without_q_lens_is_the_call_it_was():
    """``q_lens=None`` (training, every call before there was one): the
    forward's ``pallas_call`` has q, k, v for operands and no scalar
    prefetch; with ``q_lens`` the lengths ride in front as one.  And a
    gradient through a call with ``q_lens`` is refused, not wrong."""
    q = k = v = jnp.zeros((1, 256, 2, 64), jnp.float32)
    lens = jnp.asarray([100], jnp.int32)

    def fwd_call(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: flash_attention(
            *a, causal=True, **kw))(q, k, v)
        (eqn,) = [e for e in _all_eqns(jaxpr.jaxpr)
                  if e.primitive.name == "pallas_call"]
        return eqn

    plain = fwd_call()
    assert [v.aval.shape for v in plain.invars] == [(2, 256, 64)] * 3
    assert plain.params["grid_mapping"].num_index_operands == 0
    told = fwd_call(q_lens=lens)
    assert [v.aval.shape for v in told.invars] == [(2,)] + [(2, 256, 64)] * 3
    assert told.params["grid_mapping"].num_index_operands == 1
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, k, v, q_lens=lens).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, q_lens=lens)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def _pairs_by_brute_force(q_len, kv_len, block_q, block_k, causal):
    mask = (np.tril(np.ones((q_len, kv_len), bool)) if causal
            else np.ones((q_len, kv_len), bool))
    blocks = mask.reshape(q_len // block_q, block_q,
                          kv_len // block_k, block_k)
    return int(blocks.any(axis=(1, 3)).sum()), blocks.shape[0] * blocks.shape[2]


# (q_len, kv_len, causal): the training cell's call, then a long
# sequence, one that 512 does not divide, a ViT's and an odd length (no
# lane-aligned block at all), and an encoder's rectangular call
_PLAN_SHAPES = [
    (1024, 1024, True),
    (4096, 4096, True),
    (1536, 1536, True),
    (197, 197, False),
    (1500, 1500, True),
    (384, 640, False),
]


@pytest.mark.parametrize("shape", _PLAN_SHAPES,
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_flash_plan_blocks_and_visited_share(shape):
    from ray_tpu.ops.flash_attention import plan_blocks
    q_len, kv_len, causal = shape
    plan = plan_blocks(q_len, kv_len, causal, head_dim=64)
    assert plan.fused
    for t in (plan.fwd, plan.bwd):
        for seq, blocks in ((q_len, (t.block_q, t.span_q)),
                            (kv_len, (t.block_k, t.span_k))):
            for b in blocks:
                assert b == seq or (b % 128 == 0 and seq % b == 0), (seq, b)
        assert t.span_q % t.block_q == 0 and t.span_k % t.block_k == 0
        assert (t.visited, t.total) == _pairs_by_brute_force(
            q_len, kv_len, t.block_q, t.block_k, causal)
    if q_len % 128:
        assert plan.fwd.total == plan.bwd.total == 1    # the whole sequence


@pytest.mark.parametrize("block,pairs", [(1024, (1, 1)), (512, (3, 4)),
                                         (256, (10, 16)), (128, (36, 64))])
def test_flash_visited_share_of_the_training_cell(block, pairs):
    """The counter of how often causal skipping engages is static: the
    pairs of a [1024, 1024] causal call by block size.  So is the one of
    how often the fused backward engages: ``fused``, and each pair is
    visited once for dq, dk and dv together."""
    from ray_tpu.ops.flash_attention import plan_blocks
    plan = plan_blocks(1024, 1024, True, block, block, head_dim=64)
    assert plan.fused and plan.bwd == plan.fwd
    assert (plan.fwd.visited, plan.fwd.total) == pairs
    assert pairs == _pairs_by_brute_force(1024, 1024, block, block, True)


@pytest.mark.parametrize("length,fused", [(256, True), (384, False)],
                         ids=["at-the-budget", "past-it"])
def test_flash_backward_is_fused_up_to_its_budget(length, fused, flash_span,
                                                  monkeypatch):
    """The backward holds dq^T of a head's whole q sequence in VMEM
    (``DQ_ACC_BYTES``, read against ``q_len`` and ``head_dim`` alone).
    Up to the budget it is the one kernel ``flash_bwd_dqkv``, here over
    two spans; past it the forward still runs and the gradient is
    refused by the kernel's name: there is no second backward."""
    import ray_tpu.ops.flash_attention as mod
    flash_span(128)
    D = 32
    monkeypatch.setattr(mod, "DQ_ACC_BYTES", 4 * 256 * D)
    assert mod.plan_blocks(length, length, True, head_dim=D).fused == fused
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    q, k, v, do = [jax.random.normal(kk, (1, length, 2, D), jnp.float32)
                   for kk in keys]
    if not fused:
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(flash_attention(q, k, v, causal=True),
                                   o_ref, atol=_F32_TOL["o"])
        with pytest.raises(NotImplementedError, match="flash_bwd_dqkv"):
            jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
        return
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: flash_attention(*a).sum(), (0, 1, 2)))(q, k, v)
    names = [e.params["name"] for e in _all_eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names == ["flash_fwd", "flash_bwd_dqkv"]
    got, want = _flash_and_xla_with_grads(q, k, v, do, True)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            a, b, atol=_F32_TOL["o" if name == "o" else "grad"], rtol=0,
            err_msg=name)


def test_gqa_repeat_kv():
    key = jax.random.PRNGKey(1)
    B, S, H, KvH, D = 1, 64, 8, 2, 16
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(key, (B, S, KvH, D))
    v = jax.random.normal(key, (B, S, KvH, D))
    out = attention(q, k, v, impl="xla")
    assert out.shape == (B, S, H, D)
    # flash path handles GQA by expansion in ops.attention
    out2 = attention(q, k, v, impl="flash")
    np.testing.assert_allclose(out, out2, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "context"))
    key = jax.random.PRNGKey(2)
    B, S, H, D = 2, 256, 2, 16
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    ref = xla_attention(q, k, v, causal=causal)
    out = jax.jit(lambda *a: ring_attention(
        *a, mesh=mesh, causal=causal, batch_axes=("data",)))(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    # gradients flow through the ring (scan + ppermute autodiff)
    g_ref = jax.grad(lambda *a: (xla_attention(*a, causal=causal) ** 2).sum())(
        q, k, v)
    g = jax.grad(lambda *a: (ring_attention(
        *a, mesh=mesh, causal=causal, batch_axes=("data",)) ** 2).sum())(
        q, k, v)
    np.testing.assert_allclose(g, g_ref, atol=2e-4)


def test_rms_norm_and_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    w = jnp.ones((16,))
    y = rms_norm(x, w)
    norms = jnp.sqrt(jnp.mean(y ** 2, axis=-1))
    np.testing.assert_allclose(norms, jnp.ones_like(norms), atol=1e-3)

    cos, sin = rope_frequencies(16, 32)
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    q_rot = apply_rope(q, cos, sin)
    # norms are preserved by rotation
    np.testing.assert_allclose(
        jnp.linalg.norm(q_rot, axis=-1), jnp.linalg.norm(q, axis=-1),
        atol=1e-4)
    # position 0 is identity
    np.testing.assert_allclose(q_rot[:, 0], q[:, 0], atol=1e-5)
    # explicit positions match implicit arange
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    np.testing.assert_allclose(apply_rope(q, cos, sin, pos), q_rot, atol=1e-6)


def test_cross_entropy_matches_manual():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
    labels = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 16)
    loss, denom = softmax_cross_entropy(logits, labels)
    manual = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), labels[..., None], -1))
    np.testing.assert_allclose(loss, manual, rtol=1e-5)
    assert denom == 32

    mask = jnp.zeros((4, 8)).at[:, :4].set(1.0)
    loss_m, denom_m = softmax_cross_entropy(logits, labels, mask)
    assert denom_m == 16
    manual_m = -jnp.sum(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), labels[..., None], -1)[..., 0] * mask) / 16
    np.testing.assert_allclose(loss_m, manual_m, rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    from ray_tpu.ops.ulysses import ulysses_attention
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "context"))
    key = jax.random.PRNGKey(3)
    B, S, H, D = 2, 256, 4, 16   # H divisible by context axis (4)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    ref = xla_attention(q, k, v, causal=causal)
    out = jax.jit(lambda *a: ulysses_attention(
        *a, mesh=mesh, causal=causal, impl="xla",
        batch_axes=("data",)))(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    g_ref = jax.grad(lambda *a: (xla_attention(*a, causal=causal) ** 2).sum())(
        q, k, v)
    g = jax.grad(lambda *a: (ulysses_attention(
        *a, mesh=mesh, causal=causal, impl="xla",
        batch_axes=("data",)) ** 2).sum())(q, k, v)
    np.testing.assert_allclose(g, g_ref, atol=2e-4)


def test_moe_layer_routes_and_balances():
    from ray_tpu.ops.moe import MoEMLP
    layer = MoEMLP(n_experts=4, d_ff=64, top_k=2, capacity_factor=2.0,
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    y, state = layer.apply(variables, x, mutable=["intermediates"])
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    (aux,) = state["intermediates"]["moe_aux_loss"]
    # Switch aux loss is exactly coef at perfect balance, >= coef otherwise
    assert float(aux) >= layer.aux_loss_coef * 0.99
    # with generous capacity, every token is dispatched: output != 0
    assert float(jnp.mean(jnp.abs(y))) > 0.0
    # gradients flow to expert weights and the router
    g = jax.grad(lambda v: (layer.apply(v, x,
                                        mutable=["intermediates"])[0] ** 2
                            ).sum())(variables)
    gnorm = jax.tree.reduce(lambda a, b: a + float(jnp.sum(jnp.abs(b))),
                            g["params"], 0.0)
    assert gnorm > 0.0


def test_chunked_lm_loss_matches_dense():
    """chunked projection head == materialized logits + CE, values and
    gradients (the memory-lean path must be numerically identical)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.losses import chunked_lm_loss, softmax_cross_entropy

    rng = np.random.default_rng(0)
    B, S, D, V = 2, 48, 16, 64          # S not a multiple of chunk_size
    hidden = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(D, V)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)

    def dense(h, w):
        return softmax_cross_entropy(
            jnp.einsum("bsd,dv->bsv", h, w), labels, mask, z_loss=1e-4)[0]

    def chunked(h, w):
        return chunked_lm_loss(h, w, labels, mask, z_loss=1e-4,
                               chunk_size=32)[0]

    ld, gd = jax.value_and_grad(dense, argnums=(0, 1))(hidden, W)
    lc, gc = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, W)
    np.testing.assert_allclose(float(ld), float(lc), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gd[0]), np.asarray(gc[0]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gd[1]), np.asarray(gc[1]),
                               rtol=1e-4, atol=1e-6)
    # tied-embedding orientation
    lt = chunked_lm_loss(hidden, W.T, labels, mask, z_loss=1e-4,
                         chunk_size=32, transpose_weight=True)[0]
    np.testing.assert_allclose(float(ld), float(lt), rtol=1e-5)


def test_lm_loss_chunked_fn_trains():
    """The chunked head plugs into make_sharded_train and the loss
    tracks the dense head's trajectory."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import GPT, get_config
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train.step import (OptimizerConfig, lm_loss_chunked_fn,
                                    make_sharded_train)

    cfg = get_config("tiny", max_seq_len=64)
    mesh = build_mesh(MeshConfig(data=-1))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (8, 65)), jnp.int32)}
    losses = {}
    for name, loss_fn in (("dense", None), ("chunked", lm_loss_chunked_fn)):
        model = GPT(cfg, mesh=mesh)
        kwargs = {} if loss_fn is None else {"loss_fn": loss_fn}
        init_fn, step_fn, _, _ = make_sharded_train(
            model, mesh, OptimizerConfig(warmup_steps=1, decay_steps=20),
            example_batch=batch, **kwargs)
        state = init_fn(jax.random.PRNGKey(0), batch)
        for _ in range(3):
            state, m = step_fn(state, batch)
        losses[name] = float(m["loss"])
    # same init/data/optimizer: trajectories must agree closely
    np.testing.assert_allclose(losses["dense"], losses["chunked"],
                               rtol=1e-3)
