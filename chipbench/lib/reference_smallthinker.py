"""Plain reference of the SmallThinker block (PowerInfer,
SmallThinker-21BA3B-Instruct), as ISSUE 26 writes it down.  For layer
``l`` on one sequence ``x [S, d]``::

    h   = RMSNorm_in(x)
    r   = h W_r                    64 router logits a token: the router
                                   reads the ATTENTION's input
    T_t = top6(r_t);  g_t = softmax(r_t[T_t])
    q, k, v = h W_q, h W_k, h W_v  no biases; rotated (half-split,
                                   theta 1.5e6) iff rope_layout[l] == 1
    key j visible to query i  iff  j <= i and
                                   (sliding_window_layout[l] == 0 or
                                    j > i - sliding_window_size)
    x1  = x + softmax(q k^T / sqrt(hd) + mask) v W_o
    m   = RMSNorm_post(x1)
    x2  = x1 + sum_{e in T_t} g_te W_down_e (relu(W_gate_e m_t) * (W_up_e m_t))

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
sort, no capacity, nothing imported from the program
(``from_program_params`` is the one adapter that knows its parameter
tree).  Its own weight layout::

    embed [V, d]; final_norm [d]; lm_head [d, V] or None (tied)
    layers (leading layer axis L): attn_norm, mlp_norm [L, d],
        wq [L, d, H, hd], wk/wv [L, d, KV, hd], wo [L, H*hd, d],
        router [L, d, E], w_gate/w_up [L, E, d, f], w_down [L, E, f, d]

So that 4,500 positions of the published widths fit beside a serving
engine: one layer, and inside it one expert, is cast to float32 at a
time; attention runs in blocks of ``Q_BLOCK`` queries; the comparisons
(``hidden_check`` and the others at the end) are of hidden states, so
the 152k-row head runs only where a test asks for ``logits``.

How the expert sum is computed, stated: every expert is applied to every
token and weighted by ``c[t, e]`` = the token's gate for that expert, 0
where it was not chosen.  That is the sum above term for term (the
dropped terms are exact zeros) and holds under any imbalance.  Indexing
the six experts' matrices per token, as one would write it for one
token, gathers 23.6 MB of float32 weights for each of a sequence's
27,000 pairs.

Departures from the published model (the configuration file's
``assumed``): no attention or expert biases, no QK-norm, the router reads
the normalised attention input ``h``, half-split rotation.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 512


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    n = config["num_hidden_layers"]
    return {"theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "top_k": int(config["moe_num_active_primary_experts"]),
            "window": int(config["sliding_window_size"]),
            "rope_layout": tuple(config["rope_layout"][:n]),
            "window_layout": tuple(config["sliding_window_layout"][:n])}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``, scanned layers, the
    dropless expert layer of ``ops/moe.py``) -> the reference's layout.
    Shapes only move; no value changes."""
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
            "router": b["moe"]["router"]["kernel"],
            "w_gate": b["moe"]["w_gate"],
            "w_up": b["moe"]["w_up"],
            "w_down": b["moe"]["w_down"],
        },
    }


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rnd(a, bits):
    """``a`` as a float of ``bits`` mantissa bits would hold it (7:
    bfloat16, 3: float8_e4m3), whatever its exponent; None: as it is.
    Only the WRONG references round (``hidden``)."""
    if bits is None:
        return a
    m, e = jnp.frexp(a)
    return jnp.ldexp(jnp.round(m * 2.0 ** (bits + 1)) / 2.0 ** (bits + 1), e)


def _rope(x, theta):
    """x [S, heads, hd]; position i rotates pair (j, j + hd/2) by
    i / theta**(2j/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window, bits=None):
    """q [S, H, hd], k/v [S, KV, hd] -> [S, H*hd]; ``window`` None for a
    global layer.  Blocks of queries against all keys."""
    s, heads, hd = q.shape
    g = heads // k.shape[1]
    k = jnp.repeat(_rnd(k, bits), g, axis=1)   # head h reads kv head h // g
    v = jnp.repeat(_rnd(v, bits), g, axis=1)
    q = _rnd(q, bits)
    j = jnp.arange(s)[None, :]
    out = []
    for lo in range(0, s, Q_BLOCK):
        i = jnp.arange(lo, min(lo + Q_BLOCK, s))[:, None]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        logits = jnp.einsum("qhk,thk->hqt", q[lo:lo + Q_BLOCK], k
                            ) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        out.append(jnp.einsum("hqt,thk->qhk", _rnd(probs, bits), v))
    return jnp.concatenate(out, 0).reshape(s, heads * hd)


def route(r, top_k: int):
    """Router logits ``r [S, E]`` -> ``c [S, E]``: the softmax over each
    token's ``top_k`` largest logits at their places, 0 elsewhere."""
    top, idx = jax.lax.top_k(r, top_k)
    g = jax.nn.softmax(top, -1)
    rows = jnp.arange(r.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, idx].set(g)


def _experts(m, c, w_gate, w_up, w_down, bits=None):
    """``sum_e c[:, e] * W_down_e (relu(W_gate_e m) * (W_up_e m))``, one
    expert cast to float32 at a time."""
    m = _rnd(m, bits)

    def one(y, xs):
        wg, wu, wd, ce = xs
        wg, wu, wd = (_rnd(a.astype(jnp.float32), bits)
                      for a in (wg, wu, wd))
        mid = _rnd(jax.nn.relu(m @ wg) * (m @ wu), bits)
        return y + ce[:, None] * (mid @ wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(m), (w_gate, w_up, w_down, c.T))
    return y


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "rotate", "window", "top_k", "router_dtype", "bits"))
def _layer(x, w, *, theta, eps, rotate, window, top_k,
           router_dtype="float32", bits=None):
    """One block on one sequence x [S, d]; ``w`` is one layer's slice.
    ``router_dtype`` is float32 and ``bits`` None; a WRONG reference may
    ask for the router in bfloat16, or for every product's operands in
    ``bits`` mantissa bits."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    h = _rms_norm(x, w["attn_norm"].astype(jnp.float32), eps)
    rd = jnp.dtype(router_dtype)
    hr = _rnd(h, bits)
    r = (hr.astype(rd) @ f32(w["router"]).astype(rd)).astype(jnp.float32)
    q = jnp.einsum("sd,dhk->shk", hr, f32(w["wq"]))
    k = jnp.einsum("sd,dhk->shk", hr, f32(w["wk"]))
    v = jnp.einsum("sd,dhk->shk", hr, f32(w["wv"]))
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    x = x + _rnd(_attend(q, k, v, window, bits), bits) @ f32(w["wo"])
    m = _rms_norm(x, w["mlp_norm"].astype(jnp.float32), eps)
    return x + _experts(m, route(r, top_k), w["w_gate"], w["w_up"],
                        w["w_down"], bits)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head(x, embed, lm_head):
    if lm_head is None:
        return x @ embed.astype(jnp.float32).T
    return x @ lm_head.astype(jnp.float32)


def hidden(weights: dict, tokens, config: dict, *, no_window: bool = False,
           top_k: int = None, router_dtype: str = "float32",
           bits: int = None):
    """Post-final-norm hidden states [S, d] of one sequence.  The
    keyword arguments build WRONG references on purpose (no window, fewer
    experts a token, the router in bfloat16, every product's operands
    rounded to ``bits`` mantissa bits: 7 is one bfloat16 pass, 3 a
    float8_e4m3 pass), for showing what the check tells apart
    (``chipbench/tests``, PERF.md)."""
    kd = kinds(config)
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0
                     ).astype(jnp.float32)
        for i in range(config["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[i], weights["layers"])
            window = (kd["window"] if kd["window_layout"][i]
                      and not no_window else None)
            x = _layer(x, w, theta=kd["theta"], eps=kd["eps"],
                       rotate=bool(kd["rope_layout"][i]), window=window,
                       top_k=top_k or kd["top_k"],
                       router_dtype=router_dtype, bits=bits)
        return _norm(x, weights["final_norm"], eps=kd["eps"])


def logits(weights: dict, tokens, config: dict, **wrong):
    """float32 logits [S, V] of one sequence of token ids [S]."""
    with jax.default_matmul_precision(HIGHEST):
        return _head(hidden(weights, tokens, config, **wrong),
                     weights["embed"], weights["lm_head"])


# What the program's numbers are held to ---------------------------------

def _row_err(got, want):
    """``|got_i - want_i| / |want_i|`` for each row."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.linalg.norm(want, axis=-1))


def _projection(got, want, wrong) -> float:
    """How much of the step from the reference to a WRONG reference the
    program's numbers take: ``<got - want, wrong - want> / |wrong -
    want|^2`` over all rows.  Rounding noise is not aligned with that
    step and averages out over rows x width, so a program that computes
    the block as published reads about 0 and one that makes the same
    mistake as ``wrong`` about 1, whatever the scale of either."""
    got, want, wrong = (a.astype(jnp.float32) for a in (got, want, wrong))
    step = wrong - want
    size = float(jnp.sum(step * step))
    return float(jnp.sum((got - want) * step)) / size if size else 0.0


def hidden_check(weights: dict, tokens, got, config: dict) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` against the reference's, row by row, and against two
    references made wrong on purpose, which say what a program with that
    fault would read (the ``*_control`` numbers):

    ``hidden_rel_err``            mean over rows of |got - want| / |want|
    ``dropped_expert_projection`` ``_projection`` on the reference with
                                  one expert less a token
    ``no_window_projection``      the same on the reference without the
                                  window, over the rows past it (only
                                  where the sequence passes the window)
    """
    k, window = kinds(config)["top_k"], kinds(config)["window"]
    want = hidden(weights, tokens, config)
    less = hidden(weights, tokens, config, top_k=k - 1)
    err = _row_err(got, want)
    out = {"positions": len(tokens),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err)),
           "dropped_expert_projection": _projection(got, want, less),
           "dropped_expert_control": float(jnp.mean(_row_err(less, want)))}
    past = len(tokens) - window     # query i misses a key from i = window
    if past > 0 and any(kinds(config)["window_layout"]):
        bare = hidden(weights, tokens, config, no_window=True)
        out["past"] = past
        out["hidden_rel_err_past"] = float(jnp.mean(err[window:]))
        out["no_window_projection"] = _projection(
            got[window:], want[window:], bare[window:])
        out["no_window_control"] = float(
            jnp.mean(_row_err(bare[window:], want[window:])))
    return out


def router_check(weights: dict, h, r) -> dict:
    """The program's router logits ``r [L, N, E]`` against float32
    products of the inputs it read, ``h [L, N, d]`` (the program's own
    activations, in the dtype it holds them): ``router_rel_err`` is
    |r - h W_r| / |h W_r| over everything.  Products of bfloat16 values
    are exact in float32, so a float32 router reads some 1e-7 here;
    ``router_bf16_control`` is what one reads that does no more than
    round its float32 logits to bfloat16 (some 2e-3)."""
    with jax.default_matmul_precision(HIGHEST):
        want = jnp.einsum("lnd,lde->lne", h.astype(jnp.float32),
                          weights["layers"]["router"].astype(jnp.float32))
    size = jnp.linalg.norm(want)
    low = want.astype(jnp.bfloat16).astype(jnp.float32)
    return {"router_rows": int(r.shape[0] * r.shape[1]),
            "router_rel_err": float(
                jnp.linalg.norm(r.astype(jnp.float32) - want) / size),
            "router_bf16_control": float(
                jnp.linalg.norm(low - want) / size)}


def decode_attention(q, k, v, lengths, window=None):
    """Plain attention of one query a row over the first ``lengths[r]``
    of the same keys: q [R, H, hd], k/v [T, KV, hd], lengths [R] ->
    [R, H, hd] float32.  Under a ``window`` only the last ``window`` of
    those keys are visible.  What a paged decode kernel has to equal."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    pos = jnp.arange(k.shape[0])[None, :]
    seen = pos < lengths[:, None]
    if window is not None:
        seen = seen & (pos >= lengths[:, None] - window)
    with jax.default_matmul_precision(HIGHEST):
        logits = jnp.einsum("rhk,thk->rht", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], logits, -jnp.inf), -1)
        return jnp.einsum("rht,thk->rhk", probs, v)


def token_agreement(rows, want) -> float:
    """Share of positions whose largest logit (``rows [N, V]``) is the
    token ``want [N]``."""
    return float(jnp.mean(jnp.argmax(rows, -1) == jnp.asarray(want)))
