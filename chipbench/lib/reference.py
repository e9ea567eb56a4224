"""Plain reference of the published block: RMSNorm, rotary positions
(half-split, as the Hugging Face Llama/Mistral code rotates), grouped-query
causal attention, SwiGLU, no biases, tied or untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks, and nothing imported from the program.  Weights come in
the reference's OWN layout (``from_program_params`` is the one adapter
that knows the program's parameter tree):

    embed [V, d]; final_norm [d]; lm_head [d, V] or None (tied)
    layers: dict of arrays with a leading layer axis L
        attn_norm [L, d], wq [L, d, H, hd], wk/wv [L, d, KV, hd],
        wo [L, H*hd, d], mlp_norm [L, d],
        w_gate/w_up [L, d, f], w_down [L, f, d]

One layer at a time is cast to float32, so a model whose bf16 weights
fill half the chip can still be checked on it.

Departure from the published models, stated: the training loss adds the
program's z-loss term ``z * logsumexp(logits)**2`` per token, because the
loop's reported loss includes it (``TransformerConfig.z_loss``).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``, scanned layers) ->
    the reference's layout.  Shapes only move; no value changes."""
    import flax.linen as nn
    p = nn.unbox(params)
    b = p["blocks"]
    return {
        "embed": p["embed"],
        "final_norm": p["final_norm"]["scale"],
        "lm_head": p["lm_head"]["kernel"] if "lm_head" in p else None,
        "layers": {
            "attn_norm": b["attn_norm"]["scale"],
            "wq": b["attn"]["wq"]["kernel"],
            "wk": b["attn"]["wk"]["kernel"],
            "wv": b["attn"]["wv"]["kernel"],
            "wo": b["attn"]["wo"]["kernel"],
            "mlp_norm": b["mlp_norm"]["scale"],
            "w_gate": b["mlp"]["w_gate"]["kernel"],
            "w_up": b["mlp"]["w_up"]["kernel"],
            "w_down": b["mlp"]["w_down"]["kernel"],
        },
    }


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, heads, hd]; position i rotates pair (j, j + hd/2) by
    i / theta**(2j/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(x, w, *, theta, eps):
    """One block on one sequence x [S, d]; ``w`` is one layer's slice."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    s = x.shape[0]
    heads, hd = w["wq"].shape[1:]
    kv = w["wk"].shape[1]
    y = _rms_norm(x, w["attn_norm"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", y, w["wq"]), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", y, w["wk"]), theta)
    v = jnp.einsum("sd,dhk->shk", y, w["wv"])
    k = jnp.repeat(k, heads // kv, axis=1)      # head h reads kv h // g
    v = jnp.repeat(v, heads // kv, axis=1)
    logits = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), -1)
    att = jnp.einsum("hqt,thk->qhk", probs, v).reshape(s, heads * hd)
    x = x + att @ w["wo"]
    y = _rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    if lm_head is None:
        return x @ embed.astype(jnp.float32).T
    return x @ lm_head.astype(jnp.float32)


def logits(weights: dict, tokens, *, rope_theta: float, rms_norm_eps: float):
    """float32 logits [S, V] of one sequence of token ids [S]."""
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0
                     ).astype(jnp.float32)
        n_layers = weights["layers"]["wq"].shape[0]
        for i in range(n_layers):
            w = jax.tree.map(lambda a: a[i], weights["layers"])
            x = _layer(x, w, theta=float(rope_theta),
                       eps=float(rms_norm_eps))
        return _head(x, weights["final_norm"], weights["embed"],
                     weights["lm_head"], eps=float(rms_norm_eps))


def lm_loss(weights: dict, tokens, *, rope_theta: float,
            rms_norm_eps: float, z_loss: float = 0.0) -> float:
    """Mean next-token loss over a batch ``tokens`` [B, S+1], one
    sequence at a time; adds ``z_loss * logsumexp**2`` per token (see the
    module docstring)."""
    total, count = 0.0, 0
    for row in tokens:
        lg = logits(weights, row[:-1], rope_theta=rope_theta,
                    rms_norm_eps=rms_norm_eps)
        lse = jax.nn.logsumexp(lg, -1)
        picked = jnp.take_along_axis(lg, jnp.asarray(row[1:])[:, None], -1)
        per_token = lse - picked[:, 0] + z_loss * lse * lse
        total += float(jnp.sum(per_token))
        count += int(per_token.shape[0])
    return total / count


def greedy_margin(weights: dict, prompt, generated, *, rope_theta: float,
                  rms_norm_eps: float) -> dict:
    """Teacher-forced check of a greedy continuation: for each generated
    token, how far its reference logit lies under the reference's largest
    at that position, in units of that position's logit standard
    deviation.  Needs no logits from the system under test."""
    seq = list(prompt) + list(generated)
    lg = logits(weights, seq[:-1], rope_theta=rope_theta,
                rms_norm_eps=rms_norm_eps)
    rows = lg[len(prompt) - 1:]                       # one per generated
    picked = jnp.take_along_axis(
        rows, jnp.asarray(generated)[:, None], -1)[:, 0]
    deficit = (jnp.max(rows, -1) - picked) / jnp.std(rows, -1)
    agree = jnp.argmax(rows, -1) == jnp.asarray(generated)
    return {"tokens": len(generated),
            "worst_deficit_sigma": float(jnp.max(deficit)),
            "mean_deficit_sigma": float(jnp.mean(deficit)),
            "argmax_agree_share": float(jnp.mean(agree))}
