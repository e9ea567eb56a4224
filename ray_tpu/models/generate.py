"""Autoregressive decoding: KV-cache generation with standard samplers.

Inference counterpart of the GPT decode path (ray_tpu/models/gpt.py
``decode=True``: cache collection + rotary offsets).  The per-token step is
one jitted function (prefill is a single wide step at offset 0), and the
sampler supports temperature / top-k / nucleus (top-p) — the decoding
surface an LLM Serve deployment needs (the reference's Serve LLM benchmark
surface, BASELINE.md llama3-8b row).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.configs import TransformerConfig
from ray_tpu.models.gpt import GPT


def _trim_logits(logits: jax.Array, top_k: int, top_p: float) -> jax.Array:
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _tempered(logits: jax.Array, temps: jax.Array, top_k: int,
              top_p: float) -> jax.Array:
    """Each row's ``logits / temperature``, trimmed: what a sampled
    row's token is drawn from (a greedy row's counts as 1 here)."""
    safe_t = jnp.where(temps > 0, temps, 1.0)
    return _trim_logits(logits / safe_t[:, None], top_k, top_p)


def any_sampled(temps: jax.Array) -> jax.Array:
    """Whether some row of a per-row ``temperature`` [B] is sampled:
    the predicate ``sample_logits`` draws under."""
    return jnp.any(temps > 0)


def sample_logits(rng: jax.Array, logits: jax.Array, *,
                  temperature=1.0, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """Sample token ids from [B, V] logits (greedy when temperature == 0).

    ``temperature`` may be a scalar or a per-row [B] array — the
    continuous-batching engine mixes greedy and sampled requests in one
    batch, so greedy rows (temperature 0) select argmax under the same
    trace.  The draw (tempering, trimming, ``rows x vocabulary`` Gumbel
    noise and its argmax) sits in a ``lax.cond`` on ``any_sampled``: a
    batch whose rows are all greedy computes the argmax alone, and one
    with a sampled row draws for every row on ``rng`` as it always did,
    so the tokens are the same either way.  The logits enter the
    drawing branch in their own dtype and are cast there."""
    if isinstance(temperature, (int, float)):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(
            rng, _trim_logits(logits / temperature, top_k, top_p), axis=-1)
    temps = jnp.asarray(temperature)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(logits):
        sampled = jax.random.categorical(
            rng, _tempered(logits, temps, top_k, top_p), axis=-1)
        return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    return jax.lax.cond(any_sampled(temps), draw, lambda _: greedy, logits)


def sampling_probs(logits: jax.Array, temperature, *, top_k: int = 0,
                   top_p: float = 1.0) -> jax.Array:
    """float32 ``[B, V]``: the distribution ``sample_logits`` draws a
    row's token from under a per-row ``temperature`` [B]: the softmax of
    the trimmed ``logits / temperature``; one-hot at the argmax for a
    greedy row (temperature 0)."""
    temps = jnp.asarray(temperature)
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(_tempered(logits, temps, top_k, top_p), axis=-1)
    greedy = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                            dtype=jnp.float32)
    return jnp.where(temps[:, None] > 0, probs, greedy)


def accept_draft(p1: jax.Array, q: jax.Array, draft: jax.Array,
                 u: jax.Array):
    """The acceptance rule of speculative sampling (Leviathan et al.,
    arXiv:2211.17192) for ONE draft a row: ``draft`` [B] was drawn from
    ``q`` [B, V]; the model's own distribution of that token is ``p1``;
    ``u`` [B] is uniform on [0, 1).  Returns ``(accepted [B] bool,
    residual [B, V])``: the draft stands with probability ``min(1,
    p1(d) / q(d))`` (``u q(d) < p1(d)``: no division), and a rejected
    row draws its token from ``residual`` normalised, ``max(p1 - q,
    0)``.  Whatever ``q`` is, the token that comes out is distributed as
    ``p1``.  All float32; one-hot ``p1`` and ``q`` (greedy rows) make it
    "equal or not", the residual one-hot at ``p1``'s token."""
    at = draft[:, None]
    accepted = (u * jnp.take_along_axis(q, at, axis=1)[:, 0]
                < jnp.take_along_axis(p1, at, axis=1)[:, 0])
    return accepted, jnp.maximum(p1 - q, 0.0)


def verify_draft(rng: jax.Array, logits1: jax.Array, logits2: jax.Array,
                 q_logits: jax.Array, draft: jax.Array, *, temperature,
                 top_k: int = 0, top_p: float = 1.0):
    """One row-wise step of self-speculative decoding.  ``draft`` [B] is
    the token proposed for the position after the row's last confirmed
    one, drawn from ``q_logits`` [B, V] by ``sample_logits`` at the
    row's ``temperature`` [B]; ``logits1`` / ``logits2`` are the model's
    logits of that position and (given the draft) of the next.  Returns
    ``(n [B] int32, first [B], second [B])``: the row emits ``n`` tokens,
    ``first`` (the draft where ``accept_draft`` lets it stand, else a
    draw from the residual) and, where the draft stood (``n`` 2),
    ``second``, a draw from the model's distribution behind it."""
    k_u, k_r, k_2 = jax.random.split(rng, 3)
    p1, p2, q = (sampling_probs(lg, temperature, top_k=top_k, top_p=top_p)
                 for lg in (logits1, logits2, q_logits))
    accepted, residual = accept_draft(
        p1, q, draft, jax.random.uniform(k_u, draft.shape))
    # p1 == q leaves no residual, and no rejection either but by rounding
    residual = jnp.where(residual.sum(-1, keepdims=True) > 0, residual, p1)
    first = jnp.where(accepted, draft,
                      jax.random.categorical(k_r, jnp.log(residual)))
    second = jax.random.categorical(k_2, jnp.log(p2))
    return (1 + accepted.astype(jnp.int32), first.astype(jnp.int32),
            second.astype(jnp.int32))


def init_decode_cache(model, batch_size: int):
    """Zeroed KV cache for a decode-mode model, built from shapes alone
    (eval_shape — no second copy of the parameters is materialized).
    Shared by Generator and the serving engine (serve/llm_engine.py)."""
    tokens = jnp.zeros((batch_size, 1), jnp.int32)
    abstract = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t), tokens)
    return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                        abstract["cache"])


class Generator:
    """Holds a decode-mode model + jitted prefill/step for repeated calls."""

    def __init__(self, cfg: TransformerConfig, params,
                 mesh=None):
        self.cfg = cfg
        self.params = params
        self.model = GPT(cfg, mesh=mesh, decode=True)

        def prefill(params, cache, tokens):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            logits, mut = self.model.apply(
                {"params": params, "cache": cache}, tokens, positions,
                mutable=["cache"])
            return logits[:, -1], mut["cache"]

        def step(params, cache, token, pos):
            positions = pos[:, None]
            logits, mut = self.model.apply(
                {"params": params, "cache": cache}, token[:, None],
                positions, mutable=["cache"])
            return logits[:, -1], mut["cache"]

        self._prefill = jax.jit(prefill)
        self._step = jax.jit(step, donate_argnums=(1,))

    def init_cache(self, batch_size: int):
        return init_decode_cache(self.model, batch_size)

    def generate(self, prompt_tokens, *, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None) -> jnp.ndarray:
        """prompt_tokens [B, S] -> generated ids [B, <=max_new_tokens].

        Stops early only when *every* row has emitted ``eos_id``; rows that
        finished earlier keep their first eos and are padded with it.
        """
        prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
        b, s = prompt_tokens.shape
        if s + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt {s} + {max_new_tokens} new > max_seq_len "
                f"{self.cfg.max_seq_len}")
        if max_new_tokens <= 0:
            return jnp.zeros((b, 0), jnp.int32)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        sampler = functools.partial(sample_logits, temperature=temperature,
                                    top_k=top_k, top_p=top_p)
        cache = self.init_cache(b)
        logits, cache = self._prefill(self.params, cache, prompt_tokens)
        out = []
        done = jnp.zeros((b,), bool)
        for i in range(max_new_tokens):
            rng, key = jax.random.split(rng)
            token = sampler(key, logits)
            if eos_id is not None:
                token = jnp.where(done, eos_id, token)
                done = done | (token == eos_id)
            out.append(token)
            last = i == max_new_tokens - 1
            if last or (eos_id is not None and bool(done.all())):
                break   # the logits for a further token are never needed
            pos = jnp.full((b,), s + i, jnp.int32)
            logits, cache = self._step(self.params, cache, token, pos)
        return jnp.stack(out, axis=1)


def generate(cfg: TransformerConfig, params, prompt_tokens,
             **kwargs) -> jnp.ndarray:
    """One-shot convenience wrapper around Generator."""
    return Generator(cfg, params).generate(prompt_tokens, **kwargs)
