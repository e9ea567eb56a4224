"""Plain reference of the Granite 4.0-H layer stack (ibm-granite,
granite-4.0-h-micro, ``model_type: granitemoehybrid``), as ISSUE 50
writes it down.  ``layer_types`` says which mixer a layer has; EVERY
layer also has a dense SwiGLU (``num_local_experts`` 0: the family's
``shared_mlp`` of ``shared_intermediate_size`` alone), and four published
scalars stand in the equations: ``e = embedding_multiplier``, ``r =
residual_multiplier``, ``s = attention_multiplier``, ``L =
logits_scaling``.  On one sequence of token ids::

    x_0    = e * E[token]
    h      = x + r * Mixer_l(RMSNorm(x))
    x'     = h + r * W_down(SiLU(W_gate n) * (W_up n)),  n = RMSNorm(h)
    logits = (E @ RMSNorm(x_last)) / L        E the same table, NOT times e

``attention``: causal softmax attention, ``heads`` queries on ``kv_heads``
keys and values of ``head_dim``, no bias, NO rotation, ``softmax(s q k^T
+ mask) v`` with ``s`` as published (1/64 for heads of 64: not
``head_dim^-1/2``).

``mamba`` (Mamba-2; ``d_inner = heads * P``, ``G`` groups, state ``N``)::

    [z | xBC | dt] = n W_in                d_inner + (d_inner + 2GN) + heads
    xBC_t = SiLU(sum_{j=0..3} w_j * xBC_{t-3+j} + b)   causal, zeros before 0
    xBC = [u | B | C];  head i uses group i // (heads / G)
    delta = softplus(dt + dt_bias);  a = exp(delta * A),  A = -exp(A_log)
    H_t = a_t H_{t-1} + delta_t u_t (x) B_t     H_0 = 0, H in R^(P x N)
    y_t = H_t C_t + D u_t
    out = [RMSNorm over each group's d_inner / G channels of (y * SiLU(z))
           * weight] W_out                      gate THEN norm

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence runs TOKEN BY
TOKEN (``lax.scan``; the loop itself is ``reference_nemotron_h.py
recurrence``, the same equations, imported as it stands), the
convolution is the explicit sum of its shifted products, attention runs
in blocks of ``Q_BLOCK`` queries, one layer is cast to float32 at a time
and the head a block of the vocabulary at a time, so that the check fits
beside 13 GB of weights, pool and state entries.  No kernels, no cache,
no chunks, nothing imported from the program (``from_program_params``
and ``from_program_state`` are the adapters that know its trees).

Departures from the published model are the configuration file's
``assumed`` list.
"""

import functools

import jax
import jax.numpy as jnp

from chipbench.lib.reference_nemotron_h import (  # noqa: F401 -- the
    NEVER, _projection, _rel, _rms_norm, _rnd, _row_err,  # check calls the
    from_program_state, prefill_state_check, recurrence,  # last three by
    recurrence_check)                                     # this module

HIGHEST = "highest"
Q_BLOCK = 512
V_BLOCK = 16384           # rows of the table cast to float32 at a time
PAD = 256                 # the check runs a request at a multiple of this
# the five references made wrong in ONE published scalar: a program that
# drops or misplaces it computes what that reference computes
WRONG = {"embedding_one": {"e": 1.0}, "residual_one": {"r": 1.0},
         "softmax_one": {"s": 1.0}, "softmax_sqrt": {"s": "sqrt"},
         "logits_unscaled": {"L": 1.0}}


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    n = config["num_hidden_layers"]
    heads = int(config["num_attention_heads"])
    return {"eps": float(config["rms_norm_eps"]),
            "layer_types": tuple(config["layer_types"][:n]),
            "heads": int(config["mamba_n_heads"]),
            "p": int(config["mamba_d_head"]),
            "n": int(config["mamba_d_state"]),
            "groups": int(config["mamba_n_groups"]),
            "taps": int(config["mamba_d_conv"]),
            "attn_heads": heads,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim")
                            or config["hidden_size"] // heads),
            "e": float(config["embedding_multiplier"]),
            "r": float(config["residual_multiplier"]),
            "s": float(config["attention_multiplier"]),
            "L": float(config["logits_scaling"])}


def multipliers(config: dict, wrong: dict = None) -> dict:
    """``{"e", "r", "s", "L"}`` as published, or with the entries of
    ``wrong`` in their place (``"s": "sqrt"`` is ``head_dim^-1/2``)."""
    kd = kinds(config)
    m = {key: kd[key] for key in "ersL"}
    m.update(wrong or {})
    if m["s"] == "sqrt":
        m["s"] = kd["head_dim"] ** -0.5
    return m


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``: ``Period`` scanned,
    one subtree ``layer_<j>`` a position in the period, stacked over
    periods) -> the reference's weights.  Nothing is copied here: the
    stacked leaves stay as they are and ``layer_weights`` takes one
    layer out of them when it is that layer's turn.  The head is the
    table (``tie_word_embeddings``)."""
    import flax.linen as nn
    p = nn.unbox(params)
    blocks = p["blocks"]
    places = sorted(blocks, key=lambda name: int(name.split("_")[1]))
    periods = jax.tree.leaves(blocks)[0].shape[0]
    return {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
            "blocks": blocks,
            "layers": [(name, i) for i in range(periods)
                       for name in places]}


def layer_weights(weights: dict, index: int) -> dict:
    """Layer ``index``'s matrices, by the reference's own names."""
    name, i = weights["layers"][index]
    b = jax.tree.map(lambda a: a[i], weights["blocks"][name])
    w = {"mlp_norm": b["mlp_norm"]["scale"],
         "w_gate": b["mlp"]["w_gate"]["kernel"],
         "w_up": b["mlp"]["w_up"]["kernel"],
         "w_down": b["mlp"]["w_down"]["kernel"]}
    if "mixer" in b:
        m = b["mixer"]
        w.update(norm=b["mixer_norm"]["scale"], w_in=m["in_proj"]["kernel"],
                 w_out=m["out_proj"]["kernel"], conv=m["conv"],
                 conv_bias=m["conv_bias"], A_log=m["A_log"],
                 dt_bias=m["dt_bias"], D=m["D"], gate_norm=m["norm"])
    else:
        m = b["attn"]
        w.update(norm=b["attn_norm"]["scale"], wq=m["wq"]["kernel"],
                 wk=m["wk"]["kernel"], wv=m["wv"]["kernel"],
                 wo=m["wo"]["kernel"])
    return w


def _swiglu(h, w, r, bits, eps):
    """``h + r W_down(SiLU(W_gate n) * (W_up n))``, ``n = RMSNorm(h)``."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    n = _rnd(_rms_norm(h, w["mlp_norm"].astype(jnp.float32), eps), bits)
    mid = _rnd(jax.nn.silu(n @ f32(w["w_gate"])) * (n @ f32(w["w_up"])),
               bits)
    return h + r * (mid @ f32(w["w_down"]))


@functools.partial(jax.jit, static_argnames=(
    "eps", "heads", "p", "n", "groups", "taps"))
def _mamba_layer(x, w, real, reset_at, no_skip, bits, r, *, eps, heads, p,
                 n, groups, taps):
    """-> ``(the layer's output, final state [H, P, N], the
    convolution's last taps - 1 inputs [taps - 1, channels])``.  ``real
    [S]``: positions the recurrence runs over (the others leave state
    and tail as they were: a right-pad the reference is told about).
    ``reset_at``: state and convolution window zeroed at that position;
    ``no_skip``: the ``D u`` term left out."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    s = x.shape[0]
    inner, bc = heads * p, groups * n
    h = _rnd(_rms_norm(x, w["norm"].astype(jnp.float32), eps), bits)
    zxd = h @ f32(w["w_in"])
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * bc],
                  zxd[:, 2 * inner + 2 * bc:])
    pos = jnp.arange(s)
    # the convolution sees nothing from before a reset
    conv = w["conv"].astype(jnp.float32)
    c = w["conv_bias"].astype(jnp.float32)[None, :]
    for j in range(taps):
        shift = taps - 1 - j
        past = jnp.pad(xbc, ((shift, 0), (0, 0)))[:s]
        seen = (pos - shift >= 0) & ((pos < reset_at) | (pos - shift
                                                         >= reset_at))
        c = c + jnp.where(seen[:, None], past, 0.0) * conv[j]
    c = jax.nn.silu(c)
    u = c[:, :inner].reshape(s, heads, p)
    over = lambda a: jnp.repeat(                              # noqa: E731
        a.reshape(s, groups, n), heads // groups, axis=1)
    b, cc = over(c[:, inner:inner + bc]), over(c[:, inner + bc:])
    delta = jnp.where(real[:, None], jax.nn.softplus(
        dt + w["dt_bias"].astype(jnp.float32)), 0.0)
    a_neg = -jnp.exp(w["A_log"].astype(jnp.float32))
    y, state = recurrence(u, delta, a_neg, b, cc,
                          jnp.zeros((heads, p, n), jnp.float32),
                          None, reset_at)
    y = y + jnp.where(no_skip, 0.0, 1.0) * w["D"].astype(
        jnp.float32)[:, None] * u
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(s, inner) * w["gate_norm"].astype(jnp.float32)
    # the last taps - 1 REAL inputs of the convolution, zeros before 0
    last = jnp.sum(real) - 1
    at = last - (taps - 2) + jnp.arange(taps - 1)
    tail = jnp.where((at >= 0)[:, None], xbc[jnp.maximum(at, 0)], 0.0)
    hid = x + r * (_rnd(y, bits) @ f32(w["w_out"]))
    return _swiglu(hid, w, r, bits, eps), state, tail


def _attend(q, k, v, real, scale, bits=None):
    """Causal softmax attention of [S, H, hd] queries over [S, KV, hd]
    keys and values at the GIVEN ``scale``, a block of queries at a
    time; keys at positions ``real`` leaves out are seen by nobody."""
    s, heads, _ = q.shape
    rep = heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        seen = (pos[None, :] <= pos[lo:lo + Q_BLOCK, None]) & real[None, :]
        pr = _rnd(jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1),
                  bits)
        out.append(jnp.einsum("hqk,khd->qhd", pr, v))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("eps",))
def _attention_layer(x, w, real, bits, r, scale, *, eps):
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    s = x.shape[0]
    h = _rnd(_rms_norm(x, w["norm"].astype(jnp.float32), eps), bits)
    q = jnp.einsum("sd,dhk->shk", h, f32(w["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, f32(w["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, f32(w["wv"]))
    o = _attend(_rnd(q, bits), _rnd(k, bits), _rnd(v, bits), real, scale,
                bits)
    hid = x + r * (_rnd(o.reshape(s, -1), bits) @ f32(w["wo"]))
    return _swiglu(hid, w, r, bits, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, rows):
    return x @ rows.astype(jnp.float32).T


def head(weights: dict, normed, scaling: float):
    """``(E @ normed) / L``: float32 logits [S, V] of post-final-norm
    hidden states, the table a block of its rows at a time."""
    table = weights["embed"]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(
            [_head_block(normed, table[lo:lo + V_BLOCK])
             for lo in range(0, table.shape[0], V_BLOCK)], -1) / scaling


def hidden(weights: dict, tokens, config: dict, *, bits: int = None,
           no_skip: bool = False, reset_at: int = None,
           absorb: tuple = None, pad_to: int = None, states: bool = False,
           wrong: dict = None):
    """Post-final-norm hidden states [S, d] of one sequence (with
    ``states``: and each Mamba layer's ``(final state, convolution
    tail)``).  The keyword arguments build WRONG references on purpose
    (``bits``: every product's operands in that many mantissa bits;
    ``no_skip``: no ``D u``; ``reset_at``: state and convolution window
    zeroed there; ``absorb`` = (n, pad): ``pad`` tokens of id 0 after
    the first ``n`` run through every recurrence, unseen by attention,
    as a prefill that does not know the prompt's real length runs them;
    ``wrong``: published scalars replaced, ``multipliers``).  ``pad_to``:
    the sequence is run at that length, with token 0 AFTER its end that
    no recurrence absorbs (every layer is causal: no row before them
    changes), so that the check's references of one request share one
    compiled program a layer kind."""
    kd = kinds(config)
    m = multipliers(config, wrong)
    ssm = {key: kd[key] for key in ("eps", "heads", "p", "n", "groups",
                                    "taps")}
    tokens = jnp.asarray(tokens)
    seen = jnp.ones(tokens.shape, bool)       # by attention
    run = jnp.ones(tokens.shape, bool)        # through the recurrences
    if absorb:
        n, pad = absorb
        tokens = jnp.concatenate([tokens[:n], jnp.zeros((pad,), tokens.dtype),
                                  tokens[n:]])
        at = jnp.arange(tokens.shape[0])
        seen = (at < n) | (at >= n + pad)
        run = jnp.ones(tokens.shape, bool)
    wanted = seen
    if pad_to and pad_to > tokens.shape[0]:
        tail = pad_to - tokens.shape[0]
        tokens = jnp.concatenate([tokens, jnp.zeros((tail,), tokens.dtype)])
        seen = jnp.concatenate([seen, jnp.ones((tail,), bool)])
        run = jnp.concatenate([run, jnp.zeros((tail,), bool)])
        wanted = jnp.concatenate([wanted, jnp.zeros((tail,), bool)])
    left = []
    bits = jnp.int32(bits or 0)
    r, scale = jnp.float32(m["r"]), jnp.float32(m["s"])
    with jax.default_matmul_precision(HIGHEST):
        x = m["e"] * jnp.take(weights["embed"], tokens,
                              axis=0).astype(jnp.float32)
        for index, kind in enumerate(kd["layer_types"]):
            w = layer_weights(weights, index)
            if kind == "mamba":
                x, state, tail = _mamba_layer(
                    x, w, run,
                    jnp.int32(NEVER if reset_at is None else reset_at),
                    jnp.bool_(no_skip), bits, r, **ssm)
                left.append((state, tail))
            else:
                x = _attention_layer(x, w, seen, bits, r, scale,
                                     eps=kd["eps"])
        out = _norm(x, weights["final_norm"], eps=kd["eps"])[wanted]
    return (out, left) if states else out


def logits(weights: dict, tokens, config: dict, **how):
    """float32 logits [S, V] of one sequence of token ids [S]."""
    m = multipliers(config, how.get("wrong"))
    return head(weights, hidden(weights, tokens, config, **how), m["L"])


# What the program's numbers are held to ---------------------------------

def _padded(n: int) -> int:
    return -(-n // PAD) * PAD


def hidden_check(weights: dict, tokens, got, config: dict, *,
                 n_prompt: int, bucket: int, left=None, pad_to: int = 0,
                 got_logits=None, logit_rows=None) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` (a prompt of ``n_prompt`` tokens the engine prefilled
    at ``bucket``, then one decode step a position) against the
    reference's, row by row (``hidden_rel_err``); its float32 logits
    ``got_logits [R, V]`` at the positions ``logit_rows [R]`` against the
    reference's there (``logits_rel_err``: what holds the logits'
    divisor and the tied, unmultiplied table); ``left``: what the
    program left in the request's entry after those tokens, one ``(state
    [H, P, N], tail [taps - 1, channels])`` a Mamba layer, against the
    reference's after the same tokens (``state_rel_err``,
    ``tail_rel_err``: the worst layer; their ``*_other_layer_control``:
    the reference's own state of the next Mamba layer).  And against
    references made wrong on purpose, each of which says what a program
    with that fault would read: its mean row error against the right
    reference (``*_control``; of the logits for ``logits_unscaled``) and
    how much of the step towards it the program takes
    (``*_projection``):

    ``state_dropped``      state and convolution window zeroed at the
                           prompt's end (rows from there)
    ``padding_absorbed``   the prompt run on through its right-pad to
                           the bucket's end before the answer
    ``no_skip``            the ``D u`` term left out
    ``embedding_one``, ``residual_one``, ``softmax_one``,
    ``softmax_sqrt``, ``logits_unscaled``
                           one published scalar replaced (``WRONG``)
    ``fp8``                every product's operands rounded to
                           float8_e4m3: control only (of the hidden
                           states, and of the logits through the right
                           head: ``logits_fp8_control``)
    """
    tokens = jnp.asarray(tokens)
    run = functools.partial(hidden, weights, tokens, config, pad_to=_padded(
        max(tokens.shape[0] + max(bucket - n_prompt, 0), pad_to)))
    want, want_left = run(states=True)
    err = _row_err(got, want)
    out = {"positions": int(tokens.shape[0]),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err)),
           "hidden_rel_err_decode": float(jnp.mean(err[n_prompt:]))}

    def state_err(theirs, ours=want_left):
        return (max(_rel(a[0], b[0]) for a, b in zip(theirs, ours)),
                max(_rel(a[1], b[1]) for a, b in zip(theirs, ours)))
    if left is not None:
        out["state_rel_err"], out["tail_rel_err"] = state_err(left)
        (out["state_other_layer_control"],
         out["tail_other_layer_control"]) = state_err(
            want_left[1:] + want_left[:1])

    def against(name, wrong, rows=slice(None)):
        out[name + "_projection"] = _projection(got[rows], want[rows],
                                                wrong[rows])
        out[name + "_control"] = float(jnp.mean(_row_err(wrong[rows],
                                                         want[rows])))
    after = slice(n_prompt, None)
    if tokens.shape[0] > n_prompt:
        against("state_dropped", run(reset_at=n_prompt), after)
        if bucket > n_prompt:
            against("padding_absorbed",
                    run(absorb=(n_prompt, bucket - n_prompt)), after)
    against("no_skip", run(no_skip=True))
    for name, wrong in WRONG.items():
        if "L" not in wrong:
            against(name, run(wrong=wrong))
    low = run(bits=3)
    out["fp8_control"] = float(jnp.mean(_row_err(low, want)))
    if got_logits is not None:
        kd = kinds(config)
        scaling = kd["L"]
        want_logits = head(weights, want[logit_rows], scaling)
        out["logits_rel_err"] = float(jnp.mean(_row_err(got_logits,
                                                        want_logits)))
        # the same head on the float8 reference's hidden states
        out["logits_fp8_control"] = float(jnp.mean(_row_err(
            head(weights, low[logit_rows], scaling), want_logits)))
        # what a head that forgets the divisor reads, and how much of
        # the way there the program's logits are
        unscaled = want_logits * scaling
        out["logits_unscaled_control"] = float(jnp.mean(_row_err(
            unscaled, want_logits)))
        out["logits_unscaled_projection"] = _projection(
            got_logits, want_logits, unscaled)
        # a head that reads the table times the embedding's multiplier
        out["logits_tied_scaled_projection"] = _projection(
            got_logits, want_logits, want_logits * kd["e"])
    return out


@jax.jit
def decode_attention(q, k, v, lengths, scale):
    """Plain attention of one query a row over the first ``lengths[i]``
    of the same cached keys and values at the GIVEN softmax ``scale``: q
    [R, H, hd], k, v [T, KV, hd] -> [R, H, hd] float32.  What the paged
    decode kernel has to equal on the pages a request left."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    with jax.default_matmul_precision(HIGHEST):
        sc = jnp.einsum("ihd,thd->iht", q, k) * scale
        seen = jnp.arange(k.shape[0])[None, :] < lengths[:, None]
        pr = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -jnp.inf), -1)
        return jnp.einsum("iht,thd->ihd", pr, v)
