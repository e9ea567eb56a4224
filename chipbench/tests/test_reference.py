"""The plain reference against ``ray_tpu/models/gpt.py`` at a tiny size on
the CPU (float32 on both sides), through the one adapter that knows the
program's parameter tree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import reference


@pytest.fixture(scope="module", params=[True, False], ids=["tied", "untied"])
def model(request):
    from ray_tpu.models import GPT, get_config
    cfg = get_config("tiny", n_kv_heads=2, tie_embeddings=request.param,
                     rope_theta=5000.0, norm_eps=1e-5, max_seq_len=64)
    net = GPT(cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (3, 33))
    params = net.init(jax.random.PRNGKey(1),
                      jnp.asarray(tokens[:, :-1]))["params"]
    return cfg, net, params, tokens


def test_logits_agree(model):
    cfg, net, params, tokens = model
    want = net.apply({"params": params}, jnp.asarray(tokens[:1, :-1]))[0]
    got = reference.logits(reference.from_program_params(params),
                           tokens[0, :-1], rope_theta=cfg.rope_theta,
                           rms_norm_eps=cfg.norm_eps)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_loss_agrees_with_the_loop_s_loss_and_needs_its_z_term(model):
    from ray_tpu.train.step import lm_loss_fn
    cfg, net, params, tokens = model
    want = float(lm_loss_fn(net.apply, params,
                            {"tokens": jnp.asarray(tokens)}, 1e-2)[0])
    weights = reference.from_program_params(params)
    kw = dict(rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps)
    assert reference.lm_loss(weights, tokens, z_loss=1e-2, **kw) == \
        pytest.approx(want, abs=2e-5)
    assert abs(reference.lm_loss(weights, tokens, **kw) - want) > 1e-2


def test_greedy_margin_accepts_the_model_s_own_argmax_only(model):
    cfg, net, params, tokens = model
    weights = reference.from_program_params(params)
    kw = dict(rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps)
    seq = list(tokens[0, :8])
    for _ in range(6):          # greedy continuation by the program itself
        lg = net.apply({"params": params}, jnp.asarray([seq]))[0, -1]
        seq.append(int(jnp.argmax(lg)))
    good = reference.greedy_margin(weights, seq[:8], seq[8:], **kw)
    assert good["worst_deficit_sigma"] < 1e-3
    assert good["argmax_agree_share"] == 1.0
    bad = reference.greedy_margin(weights, seq[:8],
                                  [(t + 1) % 256 for t in seq[8:]], **kw)
    assert bad["worst_deficit_sigma"] > 0.5
