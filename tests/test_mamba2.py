"""``ops/mamba2.py``: the three forms of the Mamba-2 recurrence agree
(ISSUE 44).  Test sizes on the CPU: 8 heads of 32 in 2 groups, a state
of 16, chunks of 8; the decode kernel runs in the Pallas interpreter."""

import pytest


def _inputs(key, b, s, h=8, p=32, g=2, n=16):
    """``(u, delta, A, B, C)`` over the ranges the model's own
    initialisation gives: steps in [1e-3, 0.5], ``A`` in (-16, -1]."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jnp.exp(jax.random.uniform(ks[1], (b, s, h), minval=jnp.log(1e-3),
                                       maxval=jnp.log(0.5))),
            -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)))


@pytest.mark.parametrize("length", [1, 7, 8, 24, 29])
def test_chunked_form_matches_the_scan(length):
    """Whole chunks, a ragged tail and a prompt shorter than a chunk;
    then a second stretch from the state the first left."""
    import jax
    import numpy as np
    from ray_tpu.ops import mamba2
    xs = _inputs(jax.random.PRNGKey(length), 2, length)
    y1, s1 = mamba2.ssm_chunked(*xs, chunk=8)
    y2, s2 = mamba2.ssm_scan(*xs)
    np.testing.assert_allclose(y1, y2, atol=2e-5)
    np.testing.assert_allclose(s1, s2, atol=2e-5)
    more = _inputs(jax.random.PRNGKey(100 + length), 2, 11)
    more = more[:2] + (xs[2],) + more[3:]
    y3, s3 = mamba2.ssm_chunked(*more, state0=s1, chunk=8)
    y4, s4 = mamba2.ssm_scan(*more, state0=s2)
    np.testing.assert_allclose(y3, y4, atol=2e-5)
    np.testing.assert_allclose(s3, s4, atol=2e-5)


def test_a_prompt_s_state_is_that_of_its_last_real_token():
    """Rows of different real lengths in one right-padded batch: each
    row's outputs up to its length and its final state are those of the
    row run alone at its length; the pad is not absorbed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import mamba2
    u, delta, a, b, c = _inputs(jax.random.PRNGKey(5), 3, 24)
    lengths = jnp.asarray([24, 9, 17])
    y, state = mamba2.ssm_chunked(u, delta, a, b, c, lengths, chunk=8)
    for r, n in enumerate(np.asarray(lengths)):
        cut = lambda x: x[r:r + 1, :n]                        # noqa: E731
        want_y, want_s = mamba2.ssm_scan(cut(u), cut(delta), a, cut(b),
                                         cut(c))
        np.testing.assert_allclose(y[r, :n], want_y[0], atol=2e-5)
        np.testing.assert_allclose(state[r], want_s[0], atol=2e-5)
    # and without the lengths the pad IS absorbed: the reading means
    # something
    _, absorbed = mamba2.ssm_chunked(u, delta, a, b, c, chunk=8)
    assert float(jnp.abs(absorbed[1] - state[1]).max()) > 1e-2


@pytest.mark.parametrize("length", [64, 256, 300])
def test_three_forms_agree_at_one_group_and_the_published_chunk(length):
    """``groups = 1`` with ``heads x P`` a multiple of 128 (4 heads of
    64: every head reads the one ``B`` and ``C``) at a chunk of 256
    (ISSUE 50's shape, fewer heads): a prompt shorter than the chunk, one
    chunk whole, a ragged second chunk.  The chunked form against the
    scan, then one decode step from the prompt's state, as the XLA form
    and as the Pallas kernel in the interpreter, against one more step of
    the scan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import mamba2
    from ray_tpu.ops.gated_delta import pack_state
    h, p, g, n = 4, 64, 1, 16
    u, delta, a_neg, b, c = _inputs(jax.random.PRNGKey(length), 2,
                                    length + 1, h, p, g, n)
    head = lambda x: x[:, :length]                            # noqa: E731
    y1, s1 = mamba2.ssm_chunked(head(u), head(delta), a_neg, head(b),
                                head(c), chunk=256)
    y2, s2 = mamba2.ssm_scan(head(u), head(delta), a_neg, head(b), head(c))
    # 256 terms a sum in another order: float32 noise, not a form's
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)
    want, _ = mamba2.ssm_scan(u[:, length:], delta[:, length:], a_neg,
                              b[:, length:], c[:, length:], s2)
    # the prompt's states in entries 2 and 1 of layer 1 of a stacked leaf
    state = jnp.zeros((2, 4, n, h * p)).at[1, jnp.asarray([2, 1])].set(
        pack_state(s1))
    x = (delta[..., None] * u)[:, length]
    a = jnp.exp(delta * a_neg)[:, length]
    for fn, kw in ((mamba2.ssm_decode_xla, {}),
                   (mamba2.ssm_decode_tpu, {"interpret": True})):
        y, _ = fn(x, a, b[:, length], c[:, length], state,
                  jnp.asarray([2, 1]), jnp.ones((2,), bool), layer=1, **kw)
        np.testing.assert_allclose(y, want[:, 0], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("heads,p,groups,n", [(8, 32, 2, 16), (4, 64, 2, 8),
                                              (2, 64, 1, 16)])
def test_decode_kernel_in_the_interpreter_leaves_dead_rows_alone(
        heads, p, groups, n):
    """``ssm_decode`` (the Pallas kernel, interpreted) against the jnp
    form on a stacked state: live rows agree and are one step of the
    scan, a dead row's entry and every entry no row names are bit for
    bit what they were, whichever layer is addressed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops import mamba2
    from ray_tpu.ops.gated_delta import unpack_state
    rows, layers, entries = 6, 2, 9
    u, delta, a_neg, b, c = _inputs(jax.random.PRNGKey(3), rows, 1, heads, p,
                                    groups, n)
    x = (delta[..., None] * u)[:, 0]
    a = jnp.exp(delta * a_neg)[:, 0]
    b, c = b[:, 0], c[:, 0]
    state = jax.random.normal(jax.random.PRNGKey(9),
                              (layers, entries, n, heads * p))
    ent = jnp.asarray([3, 1, 8, 5, 7, 0])
    live = jnp.asarray([1, 1, 0, 1, 0, 0], bool)
    for layer in (0, 1):
        y1, s1 = mamba2.ssm_decode_xla(x, a, b, c, state, ent, live,
                                       layer=layer)
        y2, s2 = mamba2.ssm_decode_tpu(x, a, b, c, state, ent, live,
                                       layer=layer, interpret=True)
        np.testing.assert_allclose(y2, y1, atol=1e-5)
        np.testing.assert_allclose(s2, s1, atol=1e-6)
        assert not np.asarray(y2)[~np.asarray(live)].any()
        for got in (s1, s2):
            touched = np.zeros((layers, entries), bool)
            touched[layer, [3, 1, 5]] = True
            same = np.asarray((got == state).all(axis=(2, 3)))
            assert (same == ~touched).all()
        want, _ = mamba2.ssm_scan(
            u, delta, a_neg, b[:, None], c[:, None],
            unpack_state(state[layer][ent], heads))
        np.testing.assert_allclose(
            np.asarray(y2)[np.asarray(live)],
            np.asarray(want[:, 0])[np.asarray(live)], atol=1e-5)
    # no live row at all: nothing moves
    _, s3 = mamba2.ssm_decode_tpu(x, a, b, c, state, ent,
                                  jnp.zeros((rows,), bool), layer=1,
                                  interpret=True)
    assert bool((s3[:, 1:] == state[:, 1:]).all())


def test_the_kernel_is_chosen_by_platform_and_lane_alignment(monkeypatch):
    from ray_tpu.ops import mamba2
    assert mamba2.resolve_ssm_impl(128, 64, 8) == "xla"       # the CPU
    monkeypatch.setattr(mamba2, "backend_platform", lambda: "tpu")
    assert mamba2.resolve_ssm_impl(128, 64, 8) == "tpu"
    assert mamba2.resolve_ssm_impl(4, 16, 2) == "xla"   # 32 lanes a group
    assert mamba2.resolve_ssm_impl(64, 64, 1) == "tpu"  # one group of 4096
    with pytest.raises(ValueError, match="unknown ssm_decode impl"):
        mamba2.resolve_ssm_impl(128, 64, 8, "cuda")
