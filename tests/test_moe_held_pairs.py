"""A layer that holds a SHARE of the router's experts runs its sorted
pairs a slab at a time and only the slabs its held pairs fill (ISSUE 56,
``ops/moe.py dropless_experts(share=)``): against the all-experts sum
over the experts held, at the edges of the slab count, and as a traced
program (one loop, the parent's three grouped products, nothing ``[N *
k, d]`` wide); at share 1 it is the one pass it was."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

N, D, F, K, TOTAL = 500, 32, 48, 4, 32          # 2,000 pairs
RAGGED = "ragged_dot_general"       # the primitive of ``jax.lax.ragged_dot``


def _case(held, *, dtype=jnp.float32, gated=True, n=N, seed=0):
    """``(x, gates, experts, w_gate, w_up, w_down)`` of a layer holding
    experts ``0 .. held - 1`` of ``TOTAL``, routed as ``DroplessMoE``
    routes: a pair of another expert has id ``held`` and gate 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, D)).astype(dtype)
    gates, experts = moe.route_top_k(
        jax.random.normal(ks[1], (n, TOTAL)), K)
    here = experts < held
    w = lambda key, a, b: (jax.random.normal(key, (held, a, b))  # noqa: E731
                           * 0.2).astype(dtype)
    return (x, jnp.where(here, gates, 0.0), jnp.where(here, experts, held),
            w(ks[2], D, F) if gated else None, w(ks[3], D, F),
            w(ks[4], F, D))


def _all_experts(x, gates, experts, w_gate, w_up, w_down, act="silu",
                 live=None):
    """Every held expert on every token in float32, the gates picking."""
    fn, held = moe.ACTS[act], w_up.shape[0]
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    if live is not None:
        gates = jnp.where(live[:, None], gates, 0.0)
    combine = jnp.zeros((x.shape[0], held + 1)).at[
        jnp.arange(x.shape[0])[:, None], experts].add(gates)[:, :held]
    up = jnp.einsum("nd,edf->enf", f32(x), f32(w_up))
    h = (fn(jnp.einsum("nd,edf->enf", f32(x), f32(w_gate))) * up
         if w_gate is not None else fn(up))
    return jnp.einsum("enf,efd,ne->nd", h, f32(w_down), combine)


def _run(case, share, **kw):
    return jax.jit(lambda *a: moe.dropless_experts(
        *a, partial=True, share=share, **kw))(*case)


def _send(case, held, how_many):
    """The case with exactly ``how_many`` pairs held (expert 0, gate
    0.5), the first choices of the first tokens; every other pair is
    another chip's."""
    x, gates, experts, *weights = case
    n = experts.shape[0]
    # first choices first: pair (t, j) is held while j * n + t < how_many
    mine = (jnp.arange(K)[None] * n + jnp.arange(n)[:, None]) < how_many
    return (x, jnp.where(mine, 0.5, 0.0),
            jnp.where(mine, 0, held).astype(experts.dtype), *weights)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("held", [8, 4, 2], ids=["1/4", "1/8", "1/16"])
def test_a_share_of_the_experts_is_the_all_experts_sum(held, dtype, tol):
    """Shares 1/4, 1/8 and 1/16 of 32 experts at top-4: the slabs' sum
    is the all-experts product over the experts held."""
    case = _case(held, dtype=dtype)
    share = held / TOTAL
    got, rows = _run(case, share)
    want = _all_experts(*case)
    assert got.dtype == dtype
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * scale, rtol=0)
    slab = moe.held_slab(N * K, share)
    held_pairs = int((case[2] < held).sum())
    assert rows.tolist() == [N * K, -(-held_pairs // slab) * slab]
    assert slab <= rows[1] <= rows[0]
    # a token none of whose pairs is held gets exactly zero
    none = ~np.asarray((case[2] < held).any(axis=1))
    assert none.any() and not np.asarray(got, np.float32)[none].any()


@pytest.mark.parametrize("how_many,slabs", [
    (0, 0), ("slab", 1), ("slab+1", 2), ("all", 3)],
    ids=["no-pair", "one-slab", "a-pair-more", "every-pair"])
def test_the_slabs_run_are_those_the_held_pairs_fill(how_many, slabs):
    """Routing forced: no pair held runs no slab and the sum is exactly
    zero; a slab's worth runs one; one pair more runs two; every pair
    held runs them all (2,000 pairs in slabs of 768: three, the last
    one padded), and the sum is the all-experts one each time."""
    share, held = 1 / 8, 4
    slab = moe.held_slab(N * K, share)
    assert slab == 768
    count = {"slab": slab, "slab+1": slab + 1, "all": N * K}.get(
        how_many, how_many)
    case = _send(_case(held), held, count)
    got, rows = _run(case, share)
    assert rows.tolist() == [N * K, slabs * slab]
    if count == 0:
        assert not np.asarray(got).any()
    else:
        want = _all_experts(*case)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2e-6 * float(jnp.abs(want).max()))


def test_rows_that_hold_no_request_come_out_zero():
    """``live`` leaves every third row out: its pairs sort behind every
    group with the other chips', and the row is exactly zero."""
    case = _case(4)
    live = jnp.arange(N) % 3 != 0
    got, rows = _run(case, 1 / 8, live=live)
    want = _all_experts(*case, live=live)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))
    assert not np.asarray(got)[~np.asarray(live)].any()
    assert np.asarray(got)[np.asarray(live)].any()
    assert rows.tolist() == [N * K, 768]


def test_an_expert_that_is_not_gated():
    """``down(relu(up x)^2)``, two matrices an expert (Nemotron's)."""
    case = _case(8, gated=False)
    got, _ = _run(case, 1 / 4, act="relu2")
    want = _all_experts(*case, act="relu2")
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))


def test_each_half_of_a_large_wave_loops_its_own_slabs(monkeypatch):
    """Above ``RAGGED_PAIRS_MAX`` pairs the halves of ``lax.map`` run
    250 tokens each: slabs of 768 of their 1,000 pairs, and the counts
    are the halves' sums."""
    monkeypatch.setattr(moe, "RAGGED_PAIRS_MAX", 1000)
    case = _case(4)
    got, rows = _run(case, 1 / 8)
    want = _all_experts(*case)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))
    assert moe.held_slab(1000, 1 / 8) == 768
    assert rows.tolist() == [N * K, 2 * 768]


@pytest.mark.parametrize("pairs,share", [
    (6144, 1 / 8),               # a Kanana chunk of 1,024 tokens at top-6
    (24576, 1 / 8),              # four rows of it
    (90112, 1 / 4),              # Nemotron, 4 x 1,024 at top-22
    (65536, 1 / 16),             # Kimi, 8,192 tokens at top-8
    (2048, 1 / 16),              # the grouped product's floor
    (700, 0.9),                  # never more than the pairs
    (6144, 1.0)],                # every expert held: all of them
    ids=["kanana-chunk", "kanana-4", "nemotron", "kimi", "floor", "cap",
         "whole"])
def test_a_slab_follows_the_pairs_and_the_share_alone(pairs, share):
    """The share's pairs with the headroom, in whole tiles, between the
    grouped product's floor and the pairs (which tile counts the rule
    picks is the compiler's business: not held here); every expert
    held, all the pairs."""
    slab = moe.held_slab(pairs, share)
    assert slab <= pairs
    assert slab == pairs or (
        slab >= max(pairs * share * moe.SLAB_HEADROOM, moe.DENSE_PAIRS_MAX)
        and slab % moe.SLAB_TILE == 0
        and slab <= pairs * share * moe.SLAB_HEADROOM + 3 * moe.SLAB_TILE
        or slab == -(-moe.DENSE_PAIRS_MAX // moe.SLAB_TILE | 1)
        * moe.SLAB_TILE)
    assert moe.held_slab(pairs, 1.0) == pairs


def _equations(jaxpr, into_loops=True):
    """Every equation of ``jaxpr`` and of the jaxprs its equations
    hold (loop bodies, branches, calls; ``into_loops`` False: not those
    a ``while`` holds)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "while" and not into_loops:
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, into_loops)


def _wide(eqns, rows, widths):
    """Operands and results ``[rows, w]`` with ``w`` among ``widths``."""
    return [v.aval.shape for e in eqns for v in (*e.invars, *e.outvars)
            if hasattr(v.aval, "shape") and len(v.aval.shape) == 2
            and v.aval.shape[0] == rows and v.aval.shape[1] in widths]


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_a_held_layer_is_one_loop_of_the_parent_s_products(gated):
    """Share 1/8: ONE ``while``, three ``ragged_dot``s (two where the
    expert is not gated), all inside it on slab rows, and no operand
    ``[N * k, d]`` or ``[N * k, f]`` wide anywhere."""
    case = _case(4, gated=gated)
    eqns = list(_equations(jax.make_jaxpr(
        lambda *a: moe.dropless_experts(*a, partial=True, share=1 / 8,
                                        act="silu" if gated else "relu2")[0]
    )(*case).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 1
    assert names.count(RAGGED) == (3 if gated else 2)
    assert "scan" not in names and "cond" not in names
    for e in eqns:
        if e.primitive.name == RAGGED:
            assert e.invars[0].aval.shape[0] == 768
    assert not _wide(eqns, N * K, (D, F))
    assert _wide(eqns, 768, (D, F))


def test_every_expert_held_is_the_one_pass_it_was():
    """Share 1 (and no share stated): no loop, the grouped products run
    ``[N * k, ...]`` rows, and the program is the one the function
    traced to before it knew of shares."""
    case = _case(TOTAL)
    whole = jax.make_jaxpr(lambda *a: moe.dropless_experts(*a)[0])(*case)
    eqns = list(_equations(whole.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "while" not in names and names.count(RAGGED) == 3
    assert all(e.invars[0].aval.shape[0] == N * K for e in eqns
               if e.primitive.name == RAGGED)
    for stated in ({"share": 1.0}, {"share": 1.0, "partial": True}):
        assert str(jax.make_jaxpr(lambda *a: moe.dropless_experts(
            *a, **stated)[0])(*case)) == str(jax.make_jaxpr(
                lambda *a: _one_pass_as_it_was(*a, **stated))(*case))


def _one_pass_as_it_was(x, gates, experts, w_gate, w_up, w_down,
                        partial=False, share=1.0):
    """The grouped formulation of the tree before ISSUE 56, line for
    line (gated, ``silu``)."""
    n, d = x.shape
    k = experts.shape[1]
    e = w_up.shape[-3]
    fn = moe.ACTS["silu"]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    xs = jnp.take(x, order // k, axis=0)
    up_h = jax.lax.ragged_dot(xs, w_up, sizes)
    h = fn(jax.lax.ragged_dot(xs, w_gate, sizes)) * up_h
    out = jax.lax.ragged_dot(h, w_down, sizes)
    out = out * jnp.take(gates.reshape(-1), order)[:, None].astype(
        out.dtype)
    if partial:
        out = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None], out, 0)
    back = jnp.argsort(order)
    return jnp.take(out, back, axis=0).reshape(n, k, d).sum(axis=1)


def test_a_gradient_through_a_layer_that_holds_everything():
    """``jax.grad`` through ``DroplessMoE`` with no ``held``: defined
    and nonzero for input and experts (the one pass has no loop)."""
    layer = moe.DroplessMoE(D, 8, F, top_k=2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 300, D))
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(0), x)["params"])
    assert moe.held_slab(600 * 2, 1.0) == 1200 > moe.DENSE_PAIRS_MAX
    loss = lambda p, x: jnp.square(                          # noqa: E731
        layer.apply({"params": p}, x)).sum()
    grads, gx = jax.grad(loss, argnums=(0, 1))(params, x)
    assert float(jnp.abs(gx).max()) > 0
    for name in ("w_gate", "w_up", "w_down"):
        assert float(jnp.abs(grads[name]).max()) > 0


def test_a_gradient_through_a_held_layer_says_why_there_is_none():
    """Above the small batch a layer that holds a share loops its slabs
    under a traced count: ``jax.grad`` raises an error that names the
    layer's share, for the input and for the experts alike; in a small
    batch (no loop) the gradient is defined as it was."""
    layer = moe.DroplessMoE(D, 8, F, top_k=2, dtype=jnp.float32, held=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 500, D))
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(0), x)["params"])
    assert moe.runs_in_slabs(2 * 500 * 2, 2 * 500, 2 / 8)
    loss = lambda p, x: jnp.square(                          # noqa: E731
        layer.apply({"params": p}, x)).sum()
    for argnums in (0, 1):
        with pytest.raises(NotImplementedError, match="holds a share"):
            jax.grad(loss, argnums=argnums)(params, x)
    assert np.isfinite(float(loss(params, x)))
    few = x[:, :4]
    assert not moe.runs_in_slabs(2 * 4 * 2, 2 * 4, 2 / 8)
    assert float(jnp.abs(jax.grad(loss, argnums=1)(params, few)).max()) > 0


@pytest.mark.parametrize("held", [None, 2], ids=["all", "held-2-of-8"])
def test_the_layer_sows_its_pair_rows_for_who_asks(held):
    """``DroplessMoE`` states its share itself: asked for ``PAIR_ROWS``
    it sows ``[pairs, pair rows run]``; not asked, it returns what it
    did and sows nothing."""
    layer = moe.DroplessMoE(D, 8, F, top_k=2, dtype=jnp.float32,
                            held=held, held_first=3 if held else 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 500, D))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    plain = layer.apply({"params": params}, x)
    got, sown = layer.apply({"params": params}, x,
                            mutable=[moe.PAIR_ROWS])
    np.testing.assert_array_equal(got, plain)
    (rows,) = sown[moe.PAIR_ROWS]["rows"]
    pairs = 2 * 500 * 2
    slab = moe.held_slab(pairs, 1.0 if held is None else held / 8)
    assert rows[0] == pairs and 0 < rows[1] <= pairs
    assert rows[1] % slab == 0
    assert (rows[1] == pairs) == (held is None)


def test_a_stack_s_layer_is_picked_inside_the_slab_loop():
    """Handed a layer stack's whole leaves and a traced layer index, the
    slab loop reads layer ``layer`` inside its body: outside it no
    array of one layer's experts exists (a scanned stack's layer would
    be copied out to be carried into the loop), and the sum is the one
    the layer's own leaves give."""
    case = _case(4)
    x, gates, experts, *weights = case
    stacks = [jnp.stack([jnp.zeros_like(w), w, 2 * w]) for w in weights]
    run = lambda layer, *ws: moe.dropless_experts(         # noqa: E731
        x, gates, experts, *ws, layer=layer, partial=True, share=1 / 8)[0]
    np.testing.assert_array_equal(
        jax.jit(run)(jnp.int32(1), *stacks), _run(case, 1 / 8)[0])
    outside = list(_equations(
        jax.make_jaxpr(run)(jnp.int32(1), *stacks).jaxpr, into_loops=False))
    assert [e.primitive.name for e in outside].count("while") == 1
    assert not [v.aval.shape for e in outside for v in e.outvars
                if v.aval.shape in ((4, D, F), (4, F, D))]
    # every expert held, or a small batch: the layer is picked up front
    whole = jax.make_jaxpr(lambda layer, *ws: moe.dropless_experts(
        x, gates, experts, *ws, layer=layer)[0])(jnp.int32(1), *stacks)
    assert "while" not in [e.primitive.name
                           for e in _equations(whole.jaxpr)]


def test_layers_of_one_shape_trace_the_slab_loop_once():
    """Five expert layers of one shape in one program (an unrolled
    period's): the slab loop's body is traced once and called five
    times, not traced a layer."""
    case = _case(4)
    traced = []
    real = moe._pair_rows
    try:
        moe._pair_rows = lambda *a: traced.append(1) or real(*a)
        moe._in_slabs.clear_cache()
        jaxpr = jax.make_jaxpr(lambda *a: sum(
            moe.dropless_experts(a[0] * (i + 1), *a[1:], partial=True,
                                 share=1 / 8)[0] for i in range(5)))(*case)
    finally:
        moe._pair_rows = real
        moe._in_slabs.clear_cache()
    assert len(traced) == 1
    names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert names.count("jit") >= 5 and "while" not in names
