"""Plain reference of the Olmo-Hybrid block (allenai, Olmo-Hybrid-7B), as
ISSUE 33 writes it down.  ``layer_types`` says which of two blocks a
layer is.  On one sequence ``x [S, d]``:

*linear_attention* (Gated DeltaNet), pre-norm::

    h = RMSNorm_in(x)
    u = [h W_q; h W_k; h W_v]                   2*H*dk + H*dv channels
    c_t = SiLU(sum_{j=0..3} w_j * u_{t-3+j})    causal, zeros before 0
    q = c_q / |c_q| / sqrt(dk),  k = c_k / |c_k|,  v = c_v      a head
    beta_t = 2 sigmoid(h_t W_b)                 linear_allow_neg_eigval
    alpha_t = exp(-exp(A_log) softplus(h_t W_a + dt_bias))
    S' = alpha_t S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                             S_0 = 0, S in R^(dk x dv)
    x1 = x + [RMSNorm_dv(o) * SiLU(h W_g)] W_o
    x2 = x1 + MLP(RMSNorm_post(x1))

*full_attention*, the OLMo-3 block (norms on the sub-layers' outputs)::

    q, k, v = x W_q, x W_k, x W_v;  q, k = RMSNorm(q), RMSNorm(k) over
    the whole projection; no rotation; causal softmax, scale hd^-1/2
    x1 = x + RMSNorm_in(attn W_o);   x2 = x1 + RMSNorm_post(MLP(x1))

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; the recurrence runs TOKEN
BY TOKEN (``lax.scan``), the convolution is the explicit sum of its
four shifted products, attention runs in blocks of ``Q_BLOCK`` queries,
one layer is cast to float32 at a time.  No kernels, no cache, no
chunks, nothing imported from the program (``from_program_params`` is
the one adapter that knows its parameter tree).  Its own weight layout:
``embed [V, d]``, ``final_norm [d]``, ``lm_head [d, V]``, and one dict a
layer of that layer's matrices (``layer_weights``).

Departures from the published model are the configuration file's
``assumed`` list.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 512
NEVER = 2 ** 30           # ``reset_at`` of a reference that never resets
PAD = 256                 # the check runs a request at a multiple of this
L2_EPS = 1e-6


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    n = config["num_hidden_layers"]
    return {"eps": float(config["rms_norm_eps"]),
            "layer_types": tuple(config["layer_types"][:n]),
            "heads": int(config["linear_num_value_heads"]),
            "key_heads": int(config["linear_num_key_heads"]),
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "taps": int(config["linear_conv_kernel_dim"]),
            "beta_scale": 2.0 if config["linear_allow_neg_eigval"] else 1.0,
            "attn_heads": int(config["num_attention_heads"])}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``: ``Period`` scanned,
    one subtree ``layer_<j>`` a position in the period, stacked over
    periods) -> the reference's weights.  Nothing is copied here: the
    stacked leaves stay as they are and ``layer_weights`` takes one
    layer out of them when it is that layer's turn (a copy of every
    layer at once is 5 GB beside a serving engine)."""
    import flax.linen as nn
    p = nn.unbox(params)
    blocks = p["blocks"]
    places = sorted(blocks, key=lambda name: int(name.split("_")[1]))
    periods = jax.tree.leaves(blocks)[0].shape[0]
    return {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"], "blocks": blocks,
            "layers": [(name, i) for i in range(periods)
                       for name in places]}


def layer_weights(weights: dict, index: int) -> dict:
    """Layer ``index``'s matrices, by the reference's own names."""
    name, i = weights["layers"][index]
    b = jax.tree.map(lambda a: a[i], weights["blocks"][name])
    a = b["attn"]
    w = {"attn_norm": b["attn_norm"]["scale"],
         "mlp_norm": b["mlp_norm"]["scale"],
         "w_gate": b["mlp"]["w_gate"]["kernel"],
         "w_up": b["mlp"]["w_up"]["kernel"],
         "w_down": b["mlp"]["w_down"]["kernel"],
         "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
         "wv": a["wv"]["kernel"], "wo": a["wo"]["kernel"]}
    if "conv" in a:
        w.update(wg=a["wg"]["kernel"], wa=a["wa"]["kernel"],
                 wb=a["wb"]["kernel"], conv=a["conv"], A_log=a["A_log"],
                 dt_bias=a["dt_bias"], o_norm=a["o_norm"])
    else:
        w.update(q_norm=a["q_norm"]["scale"], k_norm=a["k_norm"]["scale"])
    return w


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rnd(a, bits):
    """``a`` as a float of ``bits`` mantissa bits would hold it (7:
    bfloat16, 3: float8_e4m3), whatever its exponent; None: as it is.
    Only the WRONG references round."""
    if bits is None:
        return a
    # one operation the compiler keeps (a frexp / ldexp pair on every
    # operand made the float8 reference a 28 s compile a layer)
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=bits)


def recurrence(q, k, v, alpha, beta, state0, state_bits=None,
               reset_at=None):
    """The gated delta rule token by token: q, k [S, H, dk], v [S, H,
    dv], alpha, beta [S, H], state0 [H, dk, dv] -> ``(o [S, H, dv],
    state)``.  ``state_bits``: a WRONG recurrence that keeps its state
    in that many mantissa bits; ``reset_at``: one that starts again
    from zeros at that position."""
    def step(s, xs):
        qt, kt, vt, at, bt, t = xs
        if reset_at is not None:
            s = jnp.where(t == reset_at, 0.0, s)
        s = s * at[:, None, None]
        ks = jnp.einsum("hk,hkv->hv", kt, s)
        s = _rnd(s + kt[:, :, None] * (bt[:, None] * (vt - ks))[:, None, :],
                 state_bits)
        return s, jnp.einsum("hk,hkv->hv", qt, s)
    with jax.default_matmul_precision(HIGHEST):
        state, o = jax.lax.scan(step, state0, (
            q, k, v, alpha, beta, jnp.arange(q.shape[0])))
    return o, state


def _mlp(x, w, bits):
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    x = _rnd(x, bits)
    mid = _rnd(jax.nn.silu(x @ f32(w["w_gate"])) * (x @ f32(w["w_up"])),
               bits)
    return mid @ f32(w["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "heads", "key_heads", "dk", "dv", "taps", "bits"))
def _linear_layer(x, w, beta_scale, no_decay, no_conv, reset_at, *, eps,
                  heads, key_heads, dk, dv, taps, bits=None):
    """One linear_attention block on one sequence x [S, d].  WRONG on
    purpose: ``bits`` (every product's operands rounded), ``beta_scale``
    1.0 (the write strength in (0, 1)), ``no_decay`` (alpha = 1),
    ``no_conv`` (the convolution left out), ``reset_at`` (the state and
    the convolution's window zeroed before that position; ``NEVER``:
    not at all).  All but ``bits`` are operands, not constants: one
    compiled program a length serves the reference and every reference
    made wrong (they were 7 programs a length, most of the check's
    time), and each selects between values the right reference computes
    anyway, so the right one's arithmetic is what it was."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    s = x.shape[0]
    h = _rnd(_rms_norm(x, w["attn_norm"].astype(jnp.float32), eps), bits)
    u = jnp.concatenate([
        jnp.einsum("sd,dhk->shk", h, f32(w[n])).reshape(s, -1)
        for n in ("wq", "wk", "wv")], -1)
    z = jnp.einsum("sd,dhk->shk", h, f32(w["wg"]))
    a = h @ f32(w["wa"])
    b = h @ f32(w["wb"])
    taps_w = w["conv"].astype(jnp.float32)
    t = jnp.arange(s)[:, None]
    c = jnp.zeros_like(u)
    for j in range(taps):               # tap j reads u_{t - (taps-1) + j}
        back = taps - 1 - j
        shifted = jnp.pad(u, ((back, 0), (0, 0)))[:s]
        # nothing from before the reset
        shifted = jnp.where((t >= reset_at) & (t - back < reset_at),
                            0.0, shifted)
        c = c + taps_w[j] * shifted
    c = jax.nn.silu(jnp.where(no_conv, u, c))
    qk = key_heads * dk
    q = c[:, :qk].reshape(s, key_heads, dk)
    k = c[:, qk:2 * qk].reshape(s, key_heads, dk)
    v = c[:, 2 * qk:].reshape(s, heads, dv)
    unit = lambda y: y * jax.lax.rsqrt(                      # noqa: E731
        jnp.sum(y * y, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    if heads != key_heads:
        q, k = (jnp.repeat(y, heads // key_heads, axis=1) for y in (q, k))
    beta = beta_scale * jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(w["A_log"].astype(jnp.float32))
                    * jax.nn.softplus(a + w["dt_bias"].astype(jnp.float32)))
    alpha = jnp.where(no_decay, 1.0, alpha)
    q, k, v = _rnd(q, bits), _rnd(k, bits), _rnd(v, bits)
    o, _ = recurrence(q, k, v, alpha, beta,
                      jnp.zeros((heads, dk, dv), jnp.float32),
                      reset_at=reset_at)
    o = _rms_norm(o, w["o_norm"].astype(jnp.float32), eps) * jax.nn.silu(z)
    x = x + _rnd(o.reshape(s, heads * dv), bits) @ f32(w["wo"])
    return x + _mlp(_rms_norm(x, w["mlp_norm"].astype(jnp.float32), eps),
                    w, bits)


def _attend(q, k, v, real, bits=None):
    """q, k, v [S, H, hd] -> [S, H*hd], causal over the keys ``real``
    [S] names, blocks of queries against all keys."""
    s, heads, hd = q.shape
    q, k, v = _rnd(q, bits), _rnd(k, bits), _rnd(v, bits)
    j = jnp.arange(s)[None, :]
    out = []
    for lo in range(0, s, Q_BLOCK):
        i = jnp.arange(lo, min(lo + Q_BLOCK, s))[:, None]
        logits = jnp.einsum("qhk,thk->hqt", q[lo:lo + Q_BLOCK], k
                            ) / jnp.sqrt(jnp.float32(hd))
        seen = (j <= i) & (real[None, :] | (j == i))
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        out.append(jnp.einsum("hqt,thk->qhk", _rnd(probs, bits), v))
    return jnp.concatenate(out, 0).reshape(s, heads * hd)


@functools.partial(jax.jit, static_argnames=("eps", "attn_heads", "bits"))
def _full_layer(x, w, real, *, eps, attn_heads, bits=None):
    """One full_attention block on one sequence x [S, d]; ``real`` [S]
    bool: the positions whose keys are visible (all, but for the WRONG
    reference that runs a prompt's padding through the recurrence)."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    s = x.shape[0]
    xr = _rnd(x, bits)
    q, k, v = (jnp.einsum("sd,dhk->shk", xr, f32(w[n])).reshape(s, -1)
               for n in ("wq", "wk", "wv"))
    q = _rms_norm(q, w["q_norm"].astype(jnp.float32), eps)
    k = _rms_norm(k, w["k_norm"].astype(jnp.float32), eps)
    q, k, v = (y.reshape(s, attn_heads, -1) for y in (q, k, v))
    y = _rnd(_attend(q, k, v, real, bits), bits) @ f32(w["wo"])
    x = x + _rms_norm(y, w["attn_norm"].astype(jnp.float32), eps)
    return x + _rms_norm(_mlp(x, w, bits),
                         w["mlp_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(jnp.float32)


def hidden(weights: dict, tokens, config: dict, *, bits: int = None,
           no_decay: bool = False, no_conv: bool = False,
           beta_scale: float = None, reset_at: int = None,
           absorb: tuple = None, pad_to: int = None):
    """Post-final-norm hidden states [S, d] of one sequence.  The
    keyword arguments build WRONG references on purpose (see
    ``_linear_layer``; ``absorb`` = (n, pad): ``pad`` tokens of id 0
    after the first ``n`` run through every recurrence, unseen by
    attention, as a prefill that does not know the prompt's real length
    runs them) for showing what the check tells apart.  ``pad_to``: the
    sequence is run at that length, with token 0 AFTER its end (every
    layer is causal: no row before them changes), so that the check's
    references of one request share one compiled program a layer
    kind."""
    kd = kinds(config)
    lin = {key: kd[key] for key in ("eps", "heads", "key_heads", "dk",
                                    "dv", "taps")}
    tokens = jnp.asarray(tokens)
    real = jnp.ones(tokens.shape, bool)
    if absorb:
        n, pad = absorb
        tokens = jnp.concatenate([tokens[:n], jnp.zeros((pad,), tokens.dtype),
                                  tokens[n:]])
        real = jnp.arange(tokens.shape[0])
        real = (real < n) | (real >= n + pad)
    wanted = real
    if pad_to and pad_to > tokens.shape[0]:
        tail = pad_to - tokens.shape[0]
        tokens = jnp.concatenate([tokens, jnp.zeros((tail,), tokens.dtype)])
        real = jnp.concatenate([real, jnp.ones((tail,), bool)])
        wanted = jnp.concatenate([wanted, jnp.zeros((tail,), bool)])
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        for index, kind in enumerate(kd["layer_types"]):
            w = layer_weights(weights, index)
            if kind == "linear_attention":
                x = _linear_layer(
                    x, w, jnp.float32(beta_scale or kd["beta_scale"]),
                    jnp.bool_(no_decay), jnp.bool_(no_conv),
                    jnp.int32(NEVER if reset_at is None else reset_at),
                    bits=bits, **lin)
            else:
                x = _full_layer(x, w, real, eps=kd["eps"],
                                attn_heads=kd["attn_heads"], bits=bits)
        return _norm(x, weights["final_norm"], eps=kd["eps"])[wanted]


def logits(weights: dict, tokens, config: dict, **wrong):
    """float32 logits [S, V] of one sequence of token ids [S]."""
    with jax.default_matmul_precision(HIGHEST):
        return _head(hidden(weights, tokens, config, **wrong),
                     weights["lm_head"])


# What the program's numbers are held to ---------------------------------

def _padded(n: int) -> int:
    return -(-n // PAD) * PAD


def _row_err(got, want):
    """``|got_i - want_i| / |want_i|`` for each row."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.linalg.norm(want, axis=-1))


def _projection(got, want, wrong) -> float:
    """How much of the step from the reference to a WRONG reference the
    program's numbers take: ``<got - want, wrong - want> / |wrong -
    want|^2`` over all rows.  Rounding noise is not aligned with that
    step, so a program that computes the block as published reads about
    0 and one that makes the same mistake as ``wrong`` about 1."""
    got, want, wrong = (a.astype(jnp.float32) for a in (got, want, wrong))
    step = wrong - want
    size = float(jnp.sum(step * step))
    return float(jnp.sum((got - want) * step)) / size if size else 0.0


def hidden_check(weights: dict, tokens, got, config: dict, *,
                 n_prompt: int, bucket: int) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` (a prompt of ``n_prompt`` tokens the engine prefilled
    at ``bucket``, then one decode step a position) against the
    reference's, row by row, and against references made wrong on
    purpose, each of which says what a program with that fault would
    read: its mean row error against the right reference (``*_control``)
    and how much of the step towards it the program takes
    (``*_projection``):

    ``state_dropped``      state and convolution window zeroed at the
                           prompt's end: a program that loses them
                           between prefill and install (rows from there)
    ``padding_absorbed``   the prompt run on through its right-pad (token
                           0) to the bucket's end before the answer: a
                           prefill that does not know the real length
                           (rows from the prompt's end)
    ``beta_range``         write strength in (0, 1)
    ``no_decay``           alpha = 1
    ``no_conv``            the convolution left out
    ``fp8``                every product's operands rounded to
                           float8_e4m3, the precision under the stated
                           one: control only (its error is noise, not a
                           direction to project on)
    """
    tokens = jnp.asarray(tokens)
    # every reference of this request at one length: its own, or the
    # one with the prompt's right-pad run through, whichever is longer
    run = functools.partial(hidden, weights, tokens, config, pad_to=_padded(
        tokens.shape[0] + max(bucket - n_prompt, 0)))
    want = run()
    err = _row_err(got, want)
    out = {"positions": int(tokens.shape[0]),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err)),
           "hidden_rel_err_decode": float(jnp.mean(err[n_prompt:]))}

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
    against("beta_range", run(beta_scale=1.0))
    against("no_decay", run(no_decay=True))
    against("no_conv", run(no_conv=True))
    out["fp8_control"] = float(jnp.mean(_row_err(run(bits=3), want)))
    return out


def handover_check(weights: dict, tokens, got, config: dict, *,
                   n_prompt: int) -> dict:
    """The LAST ``got.shape[0]`` positions of ``tokens [S]`` (a prompt of
    ``n_prompt`` tokens, then what the program answered): ``got [M, d]``
    are the program's hidden states there, decoded from the pages and
    the state entry that the engine's own compiled prefill and decode
    block left (``lib/replica_hybrid.py engine_handover``).
    ``handover_rel_err``: mean row error against the reference;
    ``handover_state_dropped_*``: against the reference whose state and
    convolution window are zeroed at the prompt's end, what an engine
    reads that writes the prompt's state to one entry and decodes from
    another (control: how far that reference lies from the right one on
    these rows; projection: how much of the step the program takes)."""
    tokens = jnp.asarray(tokens)
    m = got.shape[0]
    run = functools.partial(hidden, weights, tokens, config,
                            pad_to=_padded(tokens.shape[0]))
    want = run()[-m:]
    wrong = run(reset_at=n_prompt)[-m:]
    return {"handover_rel_err": float(jnp.mean(_row_err(got, want))),
            "handover_state_dropped_projection": _projection(got, want,
                                                             wrong),
            "handover_state_dropped_control": float(jnp.mean(
                _row_err(wrong, want)))}


def recurrence_f64(q, k, v, g, beta, state0):
    """The gated delta rule of ONE row in numpy float64, step by step:
    q, k [T, H, dk], v [T, H, dv], g, beta [T, H], state0 [H, dk, dv]
    -> o [T, H, dv].  A second witness beside ``recurrence``: other
    arithmetic, another library, nothing lowered by the compiler that
    lowers the kernel."""
    import numpy as np
    f64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32)  # noqa: E731
                               ).astype(np.float64)
    q, k, v, g, beta, s = (f64(a) for a in (q, k, v, g, beta, state0))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        s = s * np.exp(g[t])[:, None, None]
        ks = np.einsum("hk,hkv->hv", k[t], s)
        s = s + k[t][:, :, None] * (beta[t][:, None] * (v[t] - ks)
                                    )[:, None, :]
        out[t] = np.einsum("hk,hkv->hv", q[t], s)
    return out


def recurrence_check(o, q, k, v, g, beta, state0) -> dict:
    """A decode kernel's outputs ``o [T, R, H, dv]`` over ``T`` steps of
    ``R`` rows against the float32 recurrence from the same ``state0
    [R, H, dk, dv]`` on the same inputs (q, k [T, R, H, dk], v [T, R, H,
    dv], g, beta [T, R, H]): ``gdn_kernel_rel_err`` is the largest
    row's |o - want| / |want| over all its steps;
    ``gdn_kernel_bf16_state_control`` what a recurrence reads that
    rounds its state to bfloat16 every step."""
    want, low = _recurrences(q, k, v, g, beta, state0)
    size = jnp.linalg.norm(want.reshape(want.shape[0], want.shape[1], -1),
                           axis=(0, 2))
    dist = lambda a: jnp.linalg.norm(                        # noqa: E731
        (a.astype(jnp.float32) - want).reshape(
            want.shape[0], want.shape[1], -1), axis=(0, 2)) / size
    return {"gdn_kernel_rel_err": float(jnp.max(dist(o))),
            "gdn_kernel_bf16_state_control": float(jnp.max(dist(low)))}


@jax.jit
def _recurrences(q, k, v, g, beta, state0):
    """``recurrence`` a row (operands as ``recurrence_check`` takes
    them) in float32, and again with the state rounded to bfloat16
    every step: ``[T, R, H, dv]`` each.  Jitted, so that the layers of
    a check share one compiled program (run op by op, every call
    compiled its two scans anew)."""
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731

    def rows(state_bits):
        run = lambda q, k, v, a, b, s: recurrence(           # noqa: E731
            q, k, v, a, b, s, state_bits)[0]
        return jax.vmap(run, in_axes=(1, 1, 1, 1, 1, 0), out_axes=1)(
            f32(q), f32(k), f32(v), jnp.exp(f32(g)), f32(beta), f32(state0))
    return rows(None), rows(7)


def decode_attention(q, k, v, lengths, window=None):
    """Plain attention of one query a row over the first ``lengths[r]``
    of the same keys: q [R, H, hd], k/v [T, KV, hd], lengths [R] ->
    [R, H, hd] float32.  What a paged decode kernel has to equal."""
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
