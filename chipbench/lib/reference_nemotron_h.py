"""Plain reference of the Nemotron-H layer stack (nvidia,
NVIDIA-Nemotron-3-Super-120B-A12B, ``model_type: nemotron_h``), as
ISSUE 44 writes it down.  ``hybrid_override_pattern`` says which mixer a
layer is, a letter a layer; every layer is ``x <- x + Mixer(RMSNorm(x))``.
On one sequence ``x [S, d]``, with ``h = RMSNorm(x)``:

``M`` (Mamba-2; ``d_inner = heads * P``, ``G`` groups, state ``N``)::

    [z | xBC | dt] = h W_in                     d_inner + (d_inner + 2GN) + heads
    xBC_t = SiLU(sum_{j=0..3} w_j * xBC_{t-3+j} + b)   causal, zeros before 0
    xBC = [u | B | C];  head i uses group i // (heads / G)
    delta = softplus(dt + dt_bias);  a = exp(delta * A),  A = -exp(A_log)
    H_t = a_t H_{t-1} + delta_t u_t (x) B_t     H_0 = 0, H in R^(P x N)
    y_t = H_t C_t + D u_t
    out = [RMSNorm over each group's d_inner / G channels of (y * SiLU(z))
           * weight] W_out

``E`` (LatentMoE)::

    s = sigmoid(h W_r)                          float32, over ALL experts
    chosen = the top_k largest of s + e_score_correction_bias
    gate_e = scale * s_e / sum of the chosen s
    l = h W_in                                  the latent
    out = [sum_{e chosen AND held} gate_e relu(l W1_e)^2 W2_e] W_out
          + relu(h V1)^2 V2                     the shared expert

``*``: causal softmax attention, ``heads`` queries on ``kv_heads`` keys
and values of ``head_dim``, scale ``head_dim^-1/2``, no bias, no
rotation.  Final RMSNorm, untied head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; the recurrence runs TOKEN BY
TOKEN (``lax.scan``), the convolution is the explicit sum of its shifted
products, attention runs in blocks of ``Q_BLOCK`` queries, one layer and
one expert is cast to float32 at a time.  No kernels, no cache, no
chunks, nothing imported from the program (``from_program_params`` and
``from_program_state`` are the adapters that know its trees).  ``held``
experts: the reference is given the same share of the routed experts as
the program holds and leaves the others' terms out, as the program does.

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


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    n = config["num_hidden_layers"]
    return {"eps": float(config["layer_norm_epsilon"]),
            "pattern": config["hybrid_override_pattern"][:n],
            "heads": int(config["mamba_num_heads"]),
            "p": int(config["mamba_head_dim"]),
            "n": int(config["ssm_state_size"]),
            "groups": int(config["n_groups"]),
            "taps": int(config["conv_kernel"]),
            "attn_heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "top_k": int(config["num_experts_per_tok"]),
            "scale": float(config["routed_scaling_factor"]),
            "held": int(config["n_routed_experts"]),
            "held_first": int(config.get("experts_held_first", 0))}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``: ``Period`` scanned,
    one subtree ``layer_<j>`` a position in the period, stacked over
    periods) -> the reference's weights.  Nothing is copied here: the
    stacked leaves stay as they are and ``layer_weights`` takes one
    layer out of them when it is that layer's turn."""
    import flax.linen as nn
    p = nn.unbox(params)
    blocks = p["blocks"]
    places = sorted(blocks, key=lambda name: int(name.split("_")[1]))
    periods = jax.tree.leaves(blocks)[0].shape[0]
    return {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"], "blocks": blocks,
            "layers": [(name, i) for i in range(periods)
                       for name in places]}


def from_program_state(state, tail, heads: int, taps: int):
    """One request's entry of the program's recurrent leaves in one
    layer (``state [N, heads * P]``, ``tail`` the convolution's last
    inputs flat) -> the reference's ``(H [heads, P, N], tail [taps - 1,
    channels])``."""
    n = state.shape[0]
    return (jnp.transpose(state.reshape(n, heads, -1), (1, 2, 0)),
            tail.reshape(taps - 1, -1))


def layer_weights(weights: dict, index: int) -> dict:
    """Layer ``index``'s matrices, by the reference's own names."""
    name, i = weights["layers"][index]
    b = jax.tree.map(lambda a: a[i], weights["blocks"][name])
    m = b["mixer"]
    w = {"norm": b["norm"]["scale"]}
    if "in_proj" in m:
        w.update(w_in=m["in_proj"]["kernel"], w_out=m["out_proj"]["kernel"],
                 conv=m["conv"], conv_bias=m["conv_bias"], A_log=m["A_log"],
                 dt_bias=m["dt_bias"], D=m["D"], gate_norm=m["norm"])
    elif "moe" in m:
        w.update(router=m["moe"]["router"]["kernel"],
                 bias=m["moe"]["e_score_correction_bias"],
                 w1=m["moe"]["w_up"], w2=m["moe"]["w_down"],
                 w_in=m["w_in"]["kernel"], w_out=m["w_out"]["kernel"],
                 v1=m["shared_up"]["kernel"], v2=m["shared_down"]["kernel"])
    else:
        w.update(wq=m["wq"]["kernel"], wk=m["wk"]["kernel"],
                 wv=m["wv"]["kernel"], wo=m["wo"]["kernel"])
    return w


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rnd(a, bits):
    """``a`` as a float of ``bits`` mantissa bits would hold it (7:
    bfloat16, 3: float8_e4m3), whatever its exponent; 0 or None: as it
    is.  ``bits`` may be traced (an operand of ONE compiled program a
    layer kind, as the other faults are: a program a precision took the
    check a minute of compiling); only the WRONG references round."""
    if bits is None:
        return a
    low = lambda m: jax.lax.reduce_precision(              # noqa: E731
        a, exponent_bits=8, mantissa_bits=m)
    return jnp.where(bits == 7, low(7), jnp.where(bits == 3, low(3), a))


def recurrence(u, delta, a_neg, b, c, state0, state_bits=None,
               reset_at=None):
    """The selective state-space recurrence token by token: u [S, H, P],
    delta [S, H], a_neg [H], b, c [S, H, N] (each head its group's),
    state0 [H, P, N] -> ``(y [S, H, P], state)``, ``y`` without ``D
    u``.  ``state_bits``: a WRONG recurrence that keeps its state in
    that many mantissa bits; ``reset_at``: one that starts again from
    zeros at that position."""
    if reset_at is None:
        reset_at = NEVER

    def step(s, xs):
        ut, dt, bt, ct, t = xs
        s = jnp.where(t == reset_at, 0.0, s)
        s = _rnd(s * jnp.exp(dt * a_neg)[:, None, None]
                 + (dt[:, None] * ut)[:, :, None] * bt[:, None, :],
                 state_bits)
        return s, jnp.einsum("hpn,hn->hp", s, ct)
    with jax.default_matmul_precision(HIGHEST):
        state, y = jax.lax.scan(step, state0, (
            u, delta, b, c, jnp.arange(u.shape[0])))
    return y, state


@functools.partial(jax.jit, static_argnames=(
    "eps", "heads", "p", "n", "groups", "taps"))
def _mamba_layer(x, w, real, reset_at, no_skip, bits, state_bits, *, eps,
                 heads, p, n, groups, taps):
    """-> ``(x + Mamba2(RMSNorm(x)), final state [H, P, N], the
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
                          state_bits, reset_at)
    y = y + jnp.where(no_skip, 0.0, 1.0) * w["D"].astype(
        jnp.float32)[:, None] * u
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(s, inner) * w["gate_norm"].astype(jnp.float32)
    # the last taps - 1 REAL inputs of the convolution, zeros before 0
    last = jnp.sum(real) - 1
    at = last - (taps - 2) + jnp.arange(taps - 1)
    tail = jnp.where((at >= 0)[:, None], xbc[jnp.maximum(at, 0)], 0.0)
    return x + _rnd(y, bits) @ f32(w["w_out"]), state, tail


def route(logits, bias, top_k: int, scale):
    """``combine [S, E]`` float32: a token's gate for each expert, 0
    where it was not chosen."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, top_k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    gates = scale * top / jnp.sum(top, -1, keepdims=True)
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], idx].add(gates)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "held", "held_first"))
def _moe_layer(x, w, scale, bits, router_bits, *, eps, top_k, held,
               held_first):
    """-> ``x + LatentMoE(RMSNorm(x))``, the routed sum over the experts
    ``held_first .. held_first + held - 1`` only.  ``router_bits``: a
    WRONG router whose logits are rounded to that many mantissa bits."""
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    relu2 = lambda a: jnp.square(jax.nn.relu(a))             # noqa: E731
    h = _rms_norm(x, w["norm"].astype(jnp.float32), eps)
    logits = _rnd(h @ w["router"].astype(jnp.float32), router_bits)
    combine = route(logits, w["bias"].astype(jnp.float32), top_k, scale)
    combine = combine[:, held_first:held_first + held]
    h = _rnd(h, bits)
    lat = _rnd(h @ f32(w["w_in"]), bits)

    def one(acc, xs):
        w1, w2, gate = xs
        mid = _rnd(relu2(lat @ f32(w1)), bits)
        return acc + gate[:, None] * (mid @ f32(w2)), None
    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                             (w["w1"], w["w2"], combine.T))
    shared = _rnd(relu2(h @ f32(w["v1"])), bits) @ f32(w["v2"])
    return x + _rnd(routed, bits) @ f32(w["w_out"]) + shared


def _attend(q, k, v, real, bits=None):
    """Causal softmax attention of [S, H, hd] queries over [S, KV, hd]
    keys and values, a block of queries at a time; keys at positions
    ``real`` leaves out are seen by nobody."""
    s, heads, hd = q.shape
    rep = heads // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        seen = (pos[None, :] <= pos[lo:lo + Q_BLOCK, None]) & real[None, :]
        pr = _rnd(jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1),
                  bits)
        out.append(jnp.einsum("hqk,khd->qhd", pr, v))
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=(
    "eps", "attn_heads", "kv_heads"))
def _attention_layer(x, w, real, bits, *, eps, attn_heads, kv_heads):
    f32 = lambda a: _rnd(a.astype(jnp.float32), bits)        # noqa: E731
    s = x.shape[0]
    h = _rnd(_rms_norm(x, w["norm"].astype(jnp.float32), eps), bits)
    q = jnp.einsum("sd,dhk->shk", h, f32(w["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, f32(w["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, f32(w["wv"]))
    o = _attend(_rnd(q, bits), _rnd(k, bits), _rnd(v, bits), real, bits)
    return x + _rnd(o.reshape(s, -1), bits) @ f32(w["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(jnp.float32)


def hidden(weights: dict, tokens, config: dict, *, bits: int = None,
           state_bits: int = None, router_bits: int = None,
           no_skip: bool = False, reset_at: int = None,
           absorb: tuple = None, pad_to: int = None, states: bool = False):
    """Post-final-norm hidden states [S, d] of one sequence (with
    ``states``: and each Mamba layer's ``(final state, convolution
    tail)``).  The keyword arguments build WRONG references on purpose
    (``bits``: every product's operands in that many mantissa bits;
    ``state_bits``: the recurrent state kept so; ``router_bits``: the
    router's logits rounded so; ``no_skip``: no ``D u``; ``reset_at``:
    state and convolution window zeroed there; ``absorb`` = (n, pad):
    ``pad`` tokens of id 0 after the first ``n`` run through every
    recurrence, unseen by attention, as a prefill that does not know the
    prompt's real length runs them).  ``pad_to``: the sequence is run at
    that length, with token 0 AFTER its end that no recurrence absorbs
    (every layer is causal: no row before them changes), so that the
    check's references of one request share one compiled program a layer
    kind."""
    kd = kinds(config)
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
    bits, state_bits, router_bits = (jnp.int32(v or 0) for v in (
        bits, state_bits, router_bits))
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        for index, letter in enumerate(kd["pattern"]):
            w = layer_weights(weights, index)
            if letter == "M":
                x, state, tail = _mamba_layer(
                    x, w, run,
                    jnp.int32(NEVER if reset_at is None else reset_at),
                    jnp.bool_(no_skip), bits, state_bits, **ssm)
                left.append((state, tail))
            elif letter == "E":
                x = _moe_layer(x, w, jnp.float32(kd["scale"]), bits,
                               router_bits, eps=kd["eps"], top_k=kd["top_k"],
                               held=kd["held"], held_first=kd["held_first"])
            else:
                x = _attention_layer(x, w, seen, bits, eps=kd["eps"],
                                     attn_heads=kd["attn_heads"],
                                     kv_heads=kd["kv_heads"])
        out = _norm(x, weights["final_norm"], eps=kd["eps"])[wanted]
    return (out, left) if states else out


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


def _rel(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _projection(got, want, wrong) -> float:
    """How much of the step from the reference to a WRONG reference the
    program's numbers take: ``<got - want, wrong - want> / |wrong -
    want|^2`` over all rows.  Rounding noise is not aligned with that
    step, so a program that computes the layer as published reads about
    0 and one that makes the same mistake as ``wrong`` about 1."""
    got, want, wrong = (a.astype(jnp.float32) for a in (got, want, wrong))
    step = wrong - want
    size = float(jnp.sum(step * step))
    return float(jnp.sum((got - want) * step)) / size if size else 0.0


def hidden_check(weights: dict, tokens, got, config: dict, *,
                 n_prompt: int, bucket: int, left=None,
                 pad_to: int = 0) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` (a prompt of ``n_prompt`` tokens the engine prefilled
    at ``bucket``, then one decode step a position) against the
    reference's, row by row; ``left``: what the program left in the
    request's entry after those tokens, one ``(state [H, P, N], tail
    [taps - 1, channels])`` a Mamba layer, against the reference's after
    the same tokens (``state_rel_err``, ``tail_rel_err``: the worst
    layer; some percent at the published width, because the program's
    INPUTS are bfloat16, as far as a reference that keeps its state in
    bfloat16 lies, so whether the kernel's own arithmetic keeps float32
    is ``recurrence_check``'s to say; their ``*_other_layer_control``:
    the reference's own state of the next Mamba layer).  And against references made wrong on purpose, each of which
    says what a program with that fault would read: its mean row error
    against the right reference (``*_control``), how far its state lies
    from the right one (``*_state_control``) and how much of the step
    towards it the program takes (``*_projection``):

    ``state_dropped``      state and convolution window zeroed at the
                           prompt's end: a program that loses them
                           between prefill and install (rows from there)
    ``padding_absorbed``   the prompt run on through its right-pad (token
                           0) to the bucket's end before the answer: a
                           prefill that does not know the real length
    ``no_skip``            the ``D u`` term left out
    ``fp8``                every product's operands rounded to
                           float8_e4m3, the precision under the stated
                           one: control only (its error is noise, not a
                           direction to project on)

    ``pad_to``: run every reference at least that long (``hidden``), so
    that two requests of one check share their compiled programs.
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
        # what a program reads that keeps a layer's state and tail under
        # another layer's index (the next Mamba-2 layer's, cyclically)
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
    out["fp8_control"] = float(jnp.mean(_row_err(run(bits=3), want)))
    return out


@jax.jit
def _recurrences(u, delta, a_neg, b, c, state0):
    """``recurrence`` a row, in float32 and with the state held in
    bfloat16: u [S, R, H, P], delta [S, R, H], b, c [S, R, G, N], state0
    [R, H, P, N] -> two ``y [S, R, H, P]``."""
    over = lambda a: jnp.repeat(a, u.shape[2] // a.shape[2], axis=2)  # noqa: E731
    b, c = over(b), over(c)
    rows = lambda bits: jax.vmap(                             # noqa: E731
        lambda ur, dr, br, cr, s0: recurrence(ur, dr, a_neg, br, cr, s0,
                                              bits)[0],
        in_axes=(1, 1, 1, 1, 0), out_axes=1)(u, delta, b, c, state0)
    return rows(None), rows(7)


def recurrence_check(y, u, delta, a_neg, b, c, state0) -> dict:
    """The decode kernel's outputs ``y [S, R, H, P]`` over ``S`` steps of
    ``R`` rows from the states ``state0 [R, H, P, N]`` against the
    float32 recurrence on the same inputs (``ssm_kernel_rel_err``), and
    what a recurrence that keeps its state in bfloat16 reads
    (``ssm_kernel_bf16_state_control``)."""
    f32 = lambda a: a.astype(jnp.float32)                     # noqa: E731
    want, low = _recurrences(f32(u), f32(delta), f32(a_neg), f32(b), f32(c),
                             f32(state0))
    return {"ssm_kernel_rel_err": _rel(y, want),
            "ssm_kernel_bf16_state_control": _rel(low, want)}


@jax.jit
def _final_states(u, delta, a_neg, b, c):
    """``recurrence`` a row from zeros, in float32 and with the state
    held in bfloat16: u [S, R, H, P], delta [S, R, H] (0 where a row has
    no token: its state stays), b, c [S, R, G, N] -> two ``[R, H, P,
    N]``."""
    over = lambda a: jnp.repeat(a, u.shape[2] // a.shape[2], axis=2)  # noqa: E731
    b, c = over(b), over(c)
    zeros = jnp.zeros(u.shape[2:] + b.shape[-1:], jnp.float32)
    rows = lambda bits: jax.vmap(                             # noqa: E731
        lambda ur, dr, br, cr: recurrence(ur, dr, a_neg, br, cr, zeros,
                                          bits)[1],
        in_axes=1)(u, delta, b, c)
    return rows(None), rows(7)


def prefill_state_check(left, u, delta, a_neg, b, c, lengths) -> dict:
    """The states ``left [R, H, P, N]`` a prefill wrote for ``R`` prompts
    of real ``lengths`` (inputs ``[R, S, ..]``, a row's positions past
    its length padding) against the float32 recurrence over the real
    positions from zeros (``ssm_prefill_rel_err``), and what a state
    kept in bfloat16 reads (``ssm_prefill_bf16_state_control``)."""
    rows = lambda a: jnp.moveaxis(a.astype(jnp.float32), 0, 1)  # noqa: E731
    real = jnp.arange(u.shape[1])[None, :] < lengths[:, None]
    want, low = _final_states(
        rows(u), rows(jnp.where(real[..., None], delta, 0.0)),
        a_neg.astype(jnp.float32), rows(b), rows(c))
    return {"ssm_prefill_rel_err": _rel(left, want),
            "ssm_prefill_bf16_state_control": _rel(low, want)}


def router_check(weights: dict, z, r) -> dict:
    """The program's router logits ``r [L, N, E]`` in every expert layer
    against float32 products of the inputs it read, ``z [L, N, d]`` (the
    layer's normalised input, the program's own activations in the dtype
    it holds them): ``router_rel_err`` is |r - z W_r| / |z W_r| over
    everything.  Products of bfloat16 values are exact in float32, so a
    float32 router reads some 1e-7 here; ``router_bf16_control`` is what
    one reads that does no more than round its float32 logits to
    bfloat16 (some 2e-3)."""
    routers = [layer_weights(weights, i)["router"]
               for i, (name, _) in enumerate(weights["layers"])
               if "moe" in weights["blocks"][name]["mixer"]]
    with jax.default_matmul_precision(HIGHEST):
        want = jnp.stack([zi.astype(jnp.float32) @ w.astype(jnp.float32)
                          for zi, w in zip(z, routers)])
    return {"router_rows": int(r.shape[0] * r.shape[1]),
            "router_rel_err": _rel(r, want),
            "router_bf16_control": _rel(_rnd(want, 7), want)}


@jax.jit
def decode_attention(q, k, v, lengths):
    """Plain attention of one query a row over the first ``lengths[i]``
    of the same cached keys and values: q [R, H, hd], k, v [T, KV, hd]
    -> [R, H, hd] float32.  What the paged decode kernel has to equal on
    the pages a request left."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    with jax.default_matmul_precision(HIGHEST):
        sc = jnp.einsum("ihd,thd->iht", q, k) * q.shape[-1] ** -0.5
        seen = jnp.arange(k.shape[0])[None, :] < lengths[:, None]
        pr = jax.nn.softmax(jnp.where(seen[:, None, :], sc, -jnp.inf), -1)
        return jnp.einsum("iht,thd->ihd", pr, v)
