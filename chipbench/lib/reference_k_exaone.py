"""Plain reference of K-EXAONE-236B-A23B (LGAI-EXAONE, ``model_type:
exaone_moe``), as ISSUE 46 writes its layers down, for ONE chip of the
eight that share each layer.  On one sequence ``x [S, d]``, with ``n`` an
RMSNorm with a weight (eps 1e-5)::

    h   = x + Attn(n1(x));   out = h + FFN(n2(h))           (pre-norm)
    Attn(y):  q = y Wq  (64 heads of 128), k = y Wk, v = y Wv  (8 of 128)
              q, k = n_q(q), n_k(k)     over each head's 128 dims, one
                                        weight of 128 for all heads
              sliding_attention layer: q, k rotated (theta 1e6, the
                pairs (j, j + 64)); key j visible to query i iff
                i - 128 < j <= i
              full_attention layer: NO rotation; j <= i
              s = q . k / sqrt(128), softmax in float32, o = p v, o Wo
    FFN, layer 0:   SwiGLU(18432)
    FFN, layers 1..: s = sigmoid(z Wr), 128 wide, float32
              idx = the 8 largest of s + b        (b: the choice only)
              g = s[idx] / (sum s[idx] + 1e-20) * 2.5
              sum_{e in idx, e HELD HERE} g_e down_e(silu(gate_e z) * up_e z)
                + Shared(z),   Shared one SwiGLU of 2048
    MTP module (DeepSeek-V3, arXiv:2412.19437 section 2.2):
              x'_i = [n_e(Emb(t_{i+1})) ; n_h(h_i)] M      (12288 -> 6144)
              g_i  = n_m(Block(x')_i)   Block: full_attention, experts
              logits_i = g_i Head       the model's own head: of t_{i+2}
              h_i the stack's output at i BEFORE the final norm

Departures from the published checkpoint's code, each because the
config has no key for it (``assumed`` in the configuration file): the
per-head QK norm and the no-rotation rule of global layers are EXAONE
4.0's (arXiv:2507.11407); pre-norm placement; the module's feed-forward
is an expert layer and its embedding and head are the model's own.

The experts held here are ``num_experts`` of the file (16) starting at
``experts_held_first``; the router is ``published.num_experts`` (128)
wide; what the other experts would add is left out, as in the program.
``accept`` is the acceptance rule of speculative sampling as a function
of ``(P_1, P_2, Q, d, u)``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no cache, no kernels, no
batching, nothing imported from the program (``from_program_params`` is
the one adapter that knows its parameter tree).  Its own weight layout::

    embed [V, d]; final_norm [d]; lm_head [d, V]
    dense / layers (a leading layer axis) / mtp.block (none): attn_norm,
        mlp_norm [L, d], wq [L, d, H, 128], wk, wv [L, d, KV, 128],
        q_norm, k_norm [L, 128], wo [L, H*128, d]
    dense:  w_gate, w_up [L, d, F], w_down [L, F, d]
    layers, mtp.block: router [L, d, E], bias [L, E], w_gate, w_up
        [L, held, d, f], w_down [L, held, f, d], s_gate, s_up [L, d, f],
        s_down [L, f, d]
    mtp: enorm, hnorm, norm [d], eh_proj [2 d, d], block

So that 4,096 positions of the published widths fit beside a serving
engine that holds 13.2 GB: the weights stay the program's own arrays
(no layer is sliced out of its stack outside a compiled layer, and
inside one the experts are read one at a time: a layer's held experts
are 1.2 GB); one layer, inside it one expert, is cast to float32 at a
time; attention runs in blocks of ``Q_BLOCK`` queries and
the dense feed-forward in blocks of ``F_BLOCK`` hidden units.  The references made WRONG on purpose (``FAULTS``)
are operands of the one compiled layer; a sequence is padded to a
multiple of ``PAD`` with token 0 (every layer is causal).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 128            # queries a block: its scores are [64, 128, S]
F_BLOCK = 2048           # a feed-forward's hidden units a block
PAD = 512
ROWS_BLOCK = 256         # positions whose float32 logits [.., V] are held

# each names what a program with that fault computes
FAULTS = (
    "no_qk_norm",            # q and k rotated and scored without their norm
    "global_rotates",        # a full_attention layer rotates like the others
    "no_window",             # a sliding_attention layer sees every key
    "bias_in_gates",         # g from s + b, not from s
    "no_route_scale",        # g not multiplied by routed_scaling_factor
    "no_shared_expert",      # the routed sum alone
    "absent_experts_added",  # a pair whose expert is another chip's runs
                             # the nearest expert held here
    "mtp_halves_swapped",    # the module's projection reads [h ; Emb]
    "mtp_same_token",        # the module embeds t_i, not t_{i+1}
)


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    c = config
    n = int(c["num_hidden_layers"])
    return {
        "theta": float(c["rope_parameters"]["rope_theta"]),
        "eps": float(c["rms_norm_eps"]),
        "window": int(c["sliding_window"]),
        "sliding": tuple(t == "sliding_attention"
                         for t in c["layer_types"][:n]),
        "top_k": int(c["num_experts_per_tok"]),
        "scale": float(c["routed_scaling_factor"]),
        "held": int(c["num_experts"]),
        "first": int(c.get("experts_held_first", 0)),
        "n_dense": int(c["first_k_dense_replace"]), "n_layers": n}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``: the dense prefix stack
    ``dense_blocks``, the scanned expert stack ``blocks``, the module
    ``mtp``) -> the reference's layout.  Shapes only move."""
    import flax.linen as nn
    p = nn.unbox(params)

    def attention(b):
        a = b["attn"]
        return {"attn_norm": b["attn_norm"]["scale"],
                "mlp_norm": b["mlp_norm"]["scale"],
                "wq": a["wq"]["kernel"], "wk": a["wk"]["kernel"],
                "wv": a["wv"]["kernel"], "q_norm": a["q_norm"]["scale"],
                "k_norm": a["k_norm"]["scale"], "wo": a["wo"]["kernel"]}

    def experts(b):
        return {**attention(b),
                "router": b["moe"]["router"]["kernel"],
                "bias": b["moe"]["e_score_correction_bias"],
                "w_gate": b["moe"]["w_gate"], "w_up": b["moe"]["w_up"],
                "w_down": b["moe"]["w_down"],
                "s_gate": b["shared_mlp"]["w_gate"]["kernel"],
                "s_up": b["shared_mlp"]["w_up"]["kernel"],
                "s_down": b["shared_mlp"]["w_down"]["kernel"]}
    d, m = p["dense_blocks"], p["mtp"]
    return {
        "embed": p["embed"], "final_norm": p["final_norm"]["scale"],
        "lm_head": p["lm_head"]["kernel"],
        "dense": {**attention(d),
                  "w_gate": d["mlp"]["w_gate"]["kernel"],
                  "w_up": d["mlp"]["w_up"]["kernel"],
                  "w_down": d["mlp"]["w_down"]["kernel"]},
        "layers": experts(p["blocks"]),
        "mtp": {"enorm": m["enorm"]["scale"], "hnorm": m["hnorm"]["scale"],
                "norm": m["norm"]["scale"],
                "eh_proj": m["eh_proj"]["kernel"],
                "block": experts(m["block"])},
    }


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rnd(a, bits):
    """``a`` as a float of ``bits`` mantissa bits would hold it (3:
    float8_e4m3's), whatever its exponent; None: as it is.  Only the
    low-precision control rounds."""
    if bits is None:
        return a
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=bits)


def _rope_halves(x, theta):
    """x [S, heads, hd]; position i turns the pair (j, j + hd/2) by
    i / theta**(2j/hd)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(q, k, v, window, bits=None):
    """q [S, H, hd], k, v [S, KV, hd] -> [S, H*hd]: key j visible to
    query i iff ``i - window < j <= i``; a block of ``Q_BLOCK`` queries
    at a time against all keys (``S`` is a multiple of ``Q_BLOCK``)."""
    s, heads, hd = q.shape
    group = heads // k.shape[1]
    q, k, v = _rnd(q, bits), _rnd(k, bits), _rnd(v, bits)
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, lo = args
        i = lo + jnp.arange(Q_BLOCK)[:, None]
        seen = (j <= i) & (j > i - window)
        logits = jnp.einsum("qhk,thk->hqt", qb, k) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        return jnp.einsum("hqt,thk->qhk", _rnd(probs, bits), v)
    out = jax.lax.map(block, (q.reshape(s // Q_BLOCK, Q_BLOCK, heads, hd),
                              jnp.arange(0, s, Q_BLOCK)))
    return out.reshape(s, -1)


def _attention(y, w, f, sliding, kd, bits):
    """``Attn(y)`` (module docstring); ``sliding`` 1.0 / 0.0 the layer's
    kind, ``f`` the faults' switches."""
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731
    y = _rnd(y, bits)
    q = jnp.einsum("sd,dhk->shk", y, lo(w["wq"]))
    k = jnp.einsum("sd,dhk->shk", y, lo(w["wk"]))
    v = jnp.einsum("sd,dhk->shk", y, lo(w["wv"]))
    normed = f["no_qk_norm"] == 0
    q = jnp.where(normed, _rms_norm(q, w["q_norm"].astype(jnp.float32),
                                    kd["eps"]), q)
    k = jnp.where(normed, _rms_norm(k, w["k_norm"].astype(jnp.float32),
                                    kd["eps"]), k)
    rotates = (sliding > 0) | (f["global_rotates"] > 0)
    q = jnp.where(rotates, _rope_halves(q, kd["theta"]), q)
    k = jnp.where(rotates, _rope_halves(k, kd["theta"]), k)
    window = jnp.where((sliding > 0) & (f["no_window"] == 0),
                       kd["window"], 1 << 30)
    out = _attend(q, k, v, window, bits)
    return _rnd(out, bits) @ lo(w["wo"]).reshape(out.shape[-1], -1)


def _swiglu(z, w_gate, w_up, w_down, bits):
    """``down(silu(gate z) * up z)``, ``F_BLOCK`` of the hidden units at
    a time where there are more (the dense layer's 18432: its three
    matrices in float32 are 453 MB apiece, cast a block at a time, and
    its activations of 4,096 positions 300 MB)."""
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731

    def part(wg, wu, wd):
        mid = _rnd(jax.nn.silu(z @ lo(wg)) * (z @ lo(wu)), bits)
        return mid @ lo(wd)
    f = w_gate.shape[-1]
    if f <= F_BLOCK or f % F_BLOCK:
        return part(w_gate, w_up, w_down)
    cut = lambda w, j, axis: jax.lax.dynamic_slice_in_dim(   # noqa: E731
        w, j * F_BLOCK, F_BLOCK, axis)
    return jax.lax.fori_loop(
        0, f // F_BLOCK, lambda j, y: y + part(
            cut(w_gate, j, 1), cut(w_up, j, 1), cut(w_down, j, 0)),
        jnp.zeros_like(z))


def route(logits, bias, top_k: int, scale, in_gates=0.0):
    """Router logits ``[S, E]`` -> ``c [S, E]``: each token's gates at
    the places of its ``top_k`` largest ``sigmoid(logits) + bias``, 0
    elsewhere; the gates are the chosen SCORES (without the bias; with
    it where ``in_gates``, a fault), renormalised, times ``scale``."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s + in_gates * bias, idx, -1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(g)


def held_experts(z, c, w, first: int, held: int, clamp=0.0, bits=None,
                 layer=None):
    """This chip's share of an expert layer's routed sum: ``sum_{e held}
    c[:, e] down_e(silu(gate_e z) * up_e z)`` over the ``held`` experts
    from ``first`` on, ``w`` holding those experts' weights alone
    (``[held, ...]``, or a stack ``[L, held, ...]`` of which layer
    ``layer`` is read); every held expert on every token weighted by its
    gate (0 where not chosen: exact zeros), one expert read and cast to
    float32 at a time.  ``clamp``: the fault that sends a pair whose
    expert is elsewhere to the nearest expert held here."""
    here = c[:, first:first + held]
    clamped = here.at[:, 0].add(c[:, :first].sum(-1)).at[:, -1].add(
        c[:, first + held:].sum(-1))
    here = jnp.where(clamp > 0, clamped, here)
    z = _rnd(z, bits)

    def one(y, xs):
        e, ce = xs
        wg, wu, wd = (w[k][e] if layer is None else w[k][layer, e]
                      for k in ("w_gate", "w_up", "w_down"))
        return y + ce[:, None] * _swiglu(z, wg, wu, wd, bits), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(z), (jnp.arange(held), here.T))
    return y


@functools.partial(jax.jit, static_argnames=("dense", "kd", "bits"))
def _layer(x, w, layer, f, sliding, *, dense: bool, kd: tuple, bits=None):
    """One block on one sequence ``x [S, d]``; ``w`` a stack of layers
    of which ``layer`` is this one (None: ``w`` is one layer's own),
    ``sliding`` its attention's kind, ``f`` the faults' switches (all 0:
    the block as published), ``kd`` ``kinds()`` as a tuple of items.
    Returns ``(x, z, logits)``: the router's own input and output (None
    in a dense layer)."""
    kd = dict(kd)
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    stack, experts = w, ("w_gate", "w_up", "w_down")
    # this layer's own leaves, but the experts', read one at a time
    w = {k: a if layer is None or (k in experts and not dense) else a[layer]
         for k, a in stack.items()}
    x = x + _attention(_rms_norm(x, f32(w["attn_norm"]), kd["eps"]), w, f,
                       sliding, kd, bits)
    z = _rms_norm(x, f32(w["mlp_norm"]), kd["eps"])
    if dense:
        return x + _swiglu(_rnd(z, bits), w["w_gate"], w["w_up"],
                           w["w_down"], bits), None, None
    logits = z @ f32(w["router"])
    scale = jnp.where(f["no_route_scale"] > 0, 1.0, kd["scale"])
    c = route(logits, w["bias"], kd["top_k"], scale, f["bias_in_gates"])
    shared = _swiglu(_rnd(z, bits), w["s_gate"], w["s_up"], w["s_down"],
                     bits) * (1.0 - f["no_shared_expert"])
    routed = held_experts(z, c, w, kd["first"], kd["held"],
                          f["absent_experts_added"], bits, layer)
    return x + routed + shared, z, logits


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, scale, *, eps):
    return _rms_norm(x, scale.astype(jnp.float32), eps)


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "bits"))
def _mtp_input(emb, hidden, m, swapped, *, eps, bits=None):
    """``[n_e(Emb(t_{i+1})) ; n_h(h_i)] M`` (``swapped``: the fault that
    puts the hidden state's half first)."""
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    e = _rms_norm(emb, f32(m["enorm"]), eps)
    h = _rms_norm(hidden, f32(m["hnorm"]), eps)
    both = jnp.where(swapped > 0, jnp.concatenate([h, e], -1),
                     jnp.concatenate([e, h], -1))
    return _rnd(both, bits) @ _rnd(f32(m["eh_proj"]), bits)


def hidden(weights: dict, tokens, config: dict, *, fault: str = None,
           bits: int = None, router_io: bool = False, pad_to: int = 0,
           mtp: bool = False, last_next: int = 0):
    """Post-final-norm hidden states ``[S, d]`` of one sequence of token
    ids ``[S]``: the main model's one forward.  ``mtp``: the result is
    ``(hidden, module's normed output [S, d])``, the module's forward
    over ``(h_i, t_{i+1})`` with ``t_S`` = ``last_next``.  ``fault`` (one
    of ``FAULTS``) and ``bits`` (every product's operands rounded to that
    many mantissa bits: 3 is a float8_e4m3 pass) build WRONG references
    on purpose.  ``router_io``: also the routers' inputs ``[L, S, d]``
    and logits ``[L, S, E]`` of the stack's expert layers (then the
    module's, last).  ``pad_to``: run at that length at least (requests
    of several lengths then share one compiled layer)."""
    kd = kinds(config)
    key = tuple(sorted((k, v) for k, v in kd.items()))
    f = {name: jnp.float32(name == fault) for name in FAULTS}
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    nxt = jnp.concatenate([tokens[1:], jnp.asarray([last_next],
                                                   tokens.dtype)])
    pad = lambda a: jnp.pad(a, (0, max(pad_to, n) - n + (             # noqa: E731
        -max(pad_to, n) % PAD)))
    tokens, nxt = pad(tokens), pad(nxt)
    zs, rs = [], []
    with jax.default_matmul_precision(HIGHEST):
        embed = lambda t: jnp.take(weights["embed"], t,      # noqa: E731
                                   axis=0).astype(jnp.float32)
        x = embed(tokens)
        for i in range(kd["n_layers"]):
            dense = i < kd["n_dense"]
            x, z, r = _layer(x, weights["dense" if dense else "layers"],
                             jnp.int32(i if dense else i - kd["n_dense"]),
                             f, jnp.float32(kd["sliding"][i]),
                             dense=dense, kd=key, bits=bits)
            if router_io and not dense:
                zs.append(z[:n])
                rs.append(r[:n])
        out = _norm(x, weights["final_norm"], eps=kd["eps"])
        if mtp:
            m = weights["mtp"]
            t = jnp.where(f["mtp_same_token"] > 0, tokens, nxt)
            y, z, r = _layer(
                _mtp_input(embed(t), x, m, f["mtp_halves_swapped"],
                           eps=kd["eps"], bits=bits),
                m["block"], None, f, jnp.float32(0), dense=False, kd=key,
                bits=bits)
            drafted = _norm(y, m["norm"], eps=kd["eps"])[:n]
            if router_io:
                zs.append(z[:n])
                rs.append(r[:n])
    out = (out[:n], drafted) if mtp else out[:n]
    return (out, jnp.stack(zs), jnp.stack(rs)) if router_io else out


def logits(weights: dict, tokens, config: dict, **wrong):
    """float32 logits [S, V] of one sequence of token ids [S]; with
    ``mtp`` the pair ``(the model's, the module's)``."""
    with jax.default_matmul_precision(HIGHEST):
        out = hidden(weights, tokens, config, **wrong)
        if wrong.get("mtp"):
            return tuple(_head(a, weights["lm_head"]) for a in out)
        return _head(out, weights["lm_head"])


def accept(p1, p2, q, d: int, u) -> list:
    """The tokens one step of self-speculative decoding emits for a row
    whose draft ``d`` was drawn from ``q [V]``, ``p1 [V]`` being the
    model's distribution of that position and ``p2 [V]`` of the next
    given ``d``; ``u = (u_accept, u_first, u_second)`` uniforms on [0,
    1).  ``d`` stands iff ``u_accept < min(1, p1(d) / q(d))``; then the
    row emits ``d`` and a token of ``p2``; else ONE token of ``max(p1 -
    q, 0)`` normalised (Leviathan et al., arXiv:2211.17192).  Tokens are
    drawn by inverting the cumulative sum at the uniform."""
    p1, p2, q = (jnp.asarray(a, jnp.float32) for a in (p1, p2, q))

    def draw(p, u):
        cum = jnp.cumsum(p / p.sum())
        return int(jnp.minimum(jnp.sum(cum <= u), p.shape[0] - 1))
    if u[0] * q[d] < p1[d]:
        return [int(d), draw(p2, u[2])]
    rest = jnp.maximum(p1 - q, 0.0)
    return [draw(jnp.where(rest.sum() > 0, rest, p1), u[1])]


def accept_probability(p1, q, d):
    """``min(1, p1(d) / q(d))`` a row, float32: ``p1``, ``q`` [N, V],
    ``d`` [N]."""
    p1, q = (jnp.asarray(a, jnp.float32) for a in (p1, q))
    at = jnp.asarray(d)[:, None]
    return jnp.minimum(1.0, jnp.take_along_axis(p1, at, 1)[:, 0]
                       / jnp.take_along_axis(q, at, 1)[:, 0])


# What the program's numbers are held to ---------------------------------

def _row_err(got, want):
    """``|got_i - want_i| / |want_i|`` for each row."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.linalg.norm(got - want, axis=-1)
            / jnp.linalg.norm(want, axis=-1))


def _projection(got, want, wrong) -> float:
    """How much of the step from the reference to a WRONG reference the
    program's numbers take: ``<got - want, wrong - want> / |wrong -
    want|^2`` over all rows: about 0 for a program that computes the
    block as published, about 1 for one that makes ``wrong``'s mistake
    (rounding noise is not aligned with the step and averages out)."""
    got, want, wrong = (a.astype(jnp.float32) for a in (got, want, wrong))
    step = wrong - want
    size = float(jnp.sum(step * step))
    return float(jnp.sum((got - want) * step)) / size if size else 0.0


def hidden_check(weights: dict, tokens, got, got_mtp, config: dict,
                 faults=FAULTS, pad_to: int = 0, last_next: int = 0,
                 served_from: int = 0, temperature: float = 0.0) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` and its module's normed outputs ``got_mtp [S, d]``
    against the reference's one forward, row by row (``hidden_rel_err``,
    ``mtp_hidden_rel_err``: the mean over rows of |got - want| /
    |want|), and against each reference made wrong on purpose:
    ``<fault>_projection`` (``_projection``; a module fault on the
    module's outputs, any other on the stack's) and ``<fault>_control``
    (how far that reference lies from the right one, mean row error);
    ``fp8_control`` / ``mtp_fp8_control``: the reference with every
    product's operands rounded to float8_e4m3's mantissa, the precision
    under the stated one, as the two ``rel_err`` read it.  With
    ``temperature`` above 0, ``sampling_check`` of the tokens from
    position ``served_from`` on (``last_next`` behind them) besides."""
    kw = dict(pad_to=pad_to, mtp=True, last_next=last_next)
    want, want_mtp = hidden(weights, tokens, config, **kw)
    err, err_mtp = _row_err(got, want), _row_err(got_mtp, want_mtp)
    out = {"positions": len(tokens),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err)),
           "mtp_hidden_rel_err": float(jnp.mean(err_mtp)),
           "mtp_hidden_rel_err_max": float(jnp.max(err_mtp))}
    for fault in faults:
        wrong = hidden(weights, tokens, config, fault=fault, **kw)
        pick = 1 if fault.startswith("mtp_") else 0
        mine, right = ((got_mtp, want_mtp) if pick else (got, want))
        out[f"{fault}_projection"] = _projection(mine, right, wrong[pick])
        out[f"{fault}_control"] = float(jnp.mean(
            _row_err(wrong[pick], right)))
    if faults:
        low = hidden(weights, tokens, config, bits=3, **kw)
        out["fp8_control"] = float(jnp.mean(_row_err(low[0], want)))
        out["mtp_fp8_control"] = float(jnp.mean(_row_err(low[1], want_mtp)))
    if temperature > 0:
        out.update(sampling_check(
            weights, want, want_mtp, [*map(int, tokens), int(last_next)],
            max(served_from, 2), temperature))
    return out


def router_check(weights: dict, z, r) -> dict:
    """The program's router logits ``r [L, N, E]`` against float32
    products of the inputs it read, ``z [L, N, d]`` (the stack's expert
    layers, then the module's): ``router_rel_err`` is |r - z W_r| / |z
    W_r| over everything.  Products of bfloat16 values are exact in
    float32, so a float32 router reads some 1e-7 here;
    ``router_bf16_control`` is what one reads that does no more than
    round its float32 logits to bfloat16 (some 2e-3)."""
    w_r = jnp.concatenate([weights["layers"]["router"],
                           weights["mtp"]["block"]["router"][None]])
    with jax.default_matmul_precision(HIGHEST):
        want = jnp.einsum("lnd,lde->lne", z.astype(jnp.float32),
                          w_r.astype(jnp.float32))
    size = jnp.linalg.norm(want)
    low = want.astype(jnp.bfloat16).astype(jnp.float32)
    return {"router_rows": int(r.shape[0] * r.shape[1]),
            "router_rel_err": float(
                jnp.linalg.norm(r.astype(jnp.float32) - want) / size),
            "router_bf16_control": float(
                jnp.linalg.norm(low - want) / size)}


def accept_check(logits1, q_logits, drafts, got_probability,
                 temperature: float = 1.0) -> dict:
    """The program's acceptance probabilities ``got_probability [N]``
    (``min(1, P_1(d) / Q(d))`` as its step computed them from its own
    logits ``logits1``, ``q_logits [N, V]`` and drafts ``[N]``) against
    the same ratio of float32 softmaxes of the same logits:
    ``accept_ratio_err`` is the largest difference.  A float32 ratio
    reads some 1e-6; ``accept_bf16_control`` is what one reads whose
    softmaxes are rounded to bfloat16 (some 1e-2): the band between them
    is what decides whether a draft near the edge stands."""
    soft = lambda lg: jax.nn.softmax(                        # noqa: E731
        jnp.asarray(lg, jnp.float32) / temperature, -1)
    p1, q = soft(logits1), soft(q_logits)
    want = accept_probability(p1, q, drafts)
    low = accept_probability(*(a.astype(jnp.bfloat16).astype(jnp.float32)
                               for a in (p1, q)), drafts)
    return {"accept_rows": int(want.shape[0]),
            "accept_probability_mean": float(jnp.mean(want)),
            "accept_ratio_err": float(jnp.max(jnp.abs(
                jnp.asarray(got_probability, jnp.float32) - want))),
            "accept_bf16_control": float(jnp.max(jnp.abs(low - want)))}


@jax.jit
def _draws(logits1, q_logits, tokens, temperature):
    """Row by row, with ``P`` and ``Q`` the float32 softmaxes of the two
    logits at ``temperature``: ``sum_x min(P(x), Q(x))``; ``log P(token)
    + H(P)``; its expectation for a token drawn from ``Q``, ``sum_x Q(x)
    log P(x) + H(P)``; the variance of ``log P(x)`` under ``P``."""
    lp = jax.nn.log_softmax(logits1 / temperature, -1)
    q = jax.nn.softmax(q_logits / temperature, -1)
    p = jnp.exp(lp)
    entropy = -jnp.sum(p * lp, -1)
    return (jnp.sum(jnp.minimum(p, q), -1),
            jnp.take_along_axis(lp, tokens[:, None], 1)[:, 0] + entropy,
            jnp.sum(q * lp, -1) + entropy,
            jnp.sum(p * lp * lp, -1) - entropy ** 2)


def draw_stats(weights: dict, hidden, mtp_hidden, tokens, first: int,
               temperature: float):
    """What the sampler and the acceptance rule have to do at each
    position ``j`` of ``first .. len(tokens) - 1`` (``first`` 2 at
    least), from the reference's own hidden states ``hidden``,
    ``mtp_hidden [>= len(tokens) - 1, d]`` of ``tokens``: ``P_j`` is the
    model's distribution of ``t_j`` (the head on ``hidden[j - 1]``) and
    ``Q_j`` the module's (on ``mtp_hidden[j - 2]``: the module's entry i
    is of ``t_{i + 2}``), both at ``temperature``.  Four float64 arrays
    ``[len(tokens) - first]``: ``overlap`` ``sum_x min(P_j, Q_j)``, the
    probability that a draft for position j stands whatever it is;
    ``surprise`` ``log P_j(t_j) + H(P_j)``, whose mean is 0 for tokens
    drawn from ``P_j``; ``drafted``, what the surprise is on average
    for a token drawn from ``Q_j`` (a draft that stood unexamined);
    ``variance`` of ``log P_j(x)`` under ``P_j``.  ``ROWS_BLOCK``
    positions' logits at a time."""
    import numpy as np
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens) - first
    out = [[] for _ in range(4)]
    for lo in range(0, n, ROWS_BLOCK):
        j = first + np.minimum(lo + np.arange(ROWS_BLOCK), n - 1)
        with jax.default_matmul_precision(HIGHEST):
            stats = _draws(_head(hidden[j - 1], weights["lm_head"]),
                           _head(mtp_hidden[j - 2], weights["lm_head"]),
                           jnp.asarray(tokens[j]), jnp.float32(temperature))
        for kept, a in zip(out, stats):
            kept.append(np.asarray(a, np.float64)[:n - lo])
    return tuple(np.concatenate(a) for a in out)


def _z(total, variance) -> float:
    return float(total / max(variance, 1e-30) ** 0.5)


def sampling_check(weights: dict, hidden, mtp_hidden, tokens, first: int,
                   temperature: float) -> dict:
    """The tokens a request was SERVED, ``tokens[first:]``, against what
    the reference says of them (``draw_stats``).  ``served_loglik_z``:
    the sum of their surprises over its standard deviation: N(0, 1) for
    tokens that were drawn from the model's own distributions, which
    speculative sampling promises whatever the module proposes; a draft
    that stands too often, a token drawn from the module's distribution
    or at another temperature push it down.  (Where the model's
    distributions move with the context, as a trained model's and the
    CPU tests' tiny one do, so do a K/V row at the wrong position and a
    pair handed out in the wrong order; seeded random weights at the
    published widths give nearly one distribution whatever the context,
    and there ``replay_q_rel_err`` sees those.)
    ``module_drawn_control``: |z| had every token been the module's
    draw.  ``accept_expected_mean`` (and ``_sd``, over
    ``accept_positions``): the share of drafts that has to stand over
    these positions, ``sum_x min(P_1, Q)``, for the runner to hold the
    window's own count against."""
    overlap, surprise, drafted, variance = draw_stats(
        weights, hidden, mtp_hidden, tokens, first, temperature)
    return {"accept_positions": len(overlap),
            "accept_expected_mean": float(overlap.mean()),
            "accept_expected_sd": float(overlap.std()),
            "served_loglik_gap": float(surprise.mean()),
            "served_loglik_z": _z(surprise.sum(), variance.sum()),
            "module_drawn_control": abs(_z(drafted.sum(), variance.sum()))}


def replay_check(weights: dict, config: dict, rows: list,
                 temperature: float, pad_to: int = 0) -> dict:
    """Rows that the program's own block program stepped, live together
    (``lib/replica_mtp.py block_replay``), against one forward of the
    reference over each row's tokens.  A row: ``tokens`` (the context it
    was installed with, its last confirmed token at position ``start``,
    then every token it emitted, in order), ``counts`` (how many each
    step emitted), ``q_logits`` (``(steps done, the logits [V] its next
    draft was drawn from)`` where the program's state was read).

    ``replay_accept_z``: (drafts that stood - ``sum_k overlap`` at the
    positions the steps' drafts were FOR) over the binomial deviation;
    ``replay_always_accept_control`` the same had every draft stood.
    ``replay_loglik_z`` / ``replay_module_drawn_control``:
    ``sampling_check``'s over every emitted token.  ``replay_q_rel_err``: the largest |program
    - reference| / |reference| of the next draft's logits, the reference's
    being the module's at the row's new last position less one;
    ``replay_q_other_row_control``: the same against the module's entry
    one position earlier (the other of the two a step computes: what a
    wrong choice after a draft stood would hold)."""
    import numpy as np
    stood = expected = spread = surprise = drafted = variance = 0.0
    drafts, errs, others = 0, [], []
    for row in rows:
        toks = np.asarray(row["tokens"], np.int32)
        start, counts = int(row["start"]), np.asarray(row["counts"])
        want, want_mtp = hidden(weights, toks[:-1], config, mtp=True,
                                last_next=int(toks[-1]), pad_to=pad_to)
        o, s, w, v = draw_stats(weights, want, want_mtp, toks, start + 1,
                                temperature)
        # step k's draft was for the position behind its row's last
        a = o[np.cumsum(counts) - counts]
        drafts += len(counts)
        stood += float((counts - 1).sum())
        expected += float(a.sum())
        spread += float((a * (1 - a)).sum())
        surprise, drafted, variance = (surprise + s.sum(), drafted + w.sum(),
                                       variance + v.sum())
        at = np.asarray([start + counts[:done].sum() - 1
                         for done, _ in row["q_logits"]])
        mine = jnp.stack([jnp.asarray(q, jnp.float32)
                          for _, q in row["q_logits"]])
        with jax.default_matmul_precision(HIGHEST):
            right, wrong = (_head(want_mtp[at - back], weights["lm_head"])
                            for back in (0, 1))
        errs += np.asarray(_row_err(mine, right)).tolist()
        others += np.asarray(_row_err(mine, wrong)).tolist()
    return {"replay_rows": len(rows), "replay_drafts": drafts,
            "replay_accept_share": stood / max(drafts, 1),
            "replay_accept_expected": expected / max(drafts, 1),
            "replay_accept_z": _z(stood - expected, spread),
            "replay_always_accept_control": _z(drafts - expected, spread),
            "replay_loglik_z": _z(surprise, variance),
            "replay_module_drawn_control": abs(_z(drafted, variance)),
            "replay_q_rel_err": float(np.mean(errs)),
            "replay_q_rel_err_max": float(np.max(errs)),
            "replay_q_other_row_control": float(np.mean(others))}


@functools.partial(jax.jit, static_argnames=("window",))
def verify_attention(q, k, v, lengths, *, window: int = None):
    """Plain attention of ``T`` queries a row over the first ``lengths[i]
    - T + t + 1`` of the same cached keys: ``q [R, T, H, hd]``, ``k, v
    [S, KV, hd]`` -> ``[R, T, H, hd]`` float32; under a ``window`` each
    query sees the last ``window`` of its own span.  What the paged
    decode kernel at ``T`` queries a row has to equal."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    t, heads = q.shape[1], q.shape[2]
    k, v = (jnp.repeat(a, heads // k.shape[1], axis=1) for a in (k, v))
    ends = lengths[:, None] - (t - 1) + jnp.arange(t)          # [R, T]
    pos = jnp.arange(k.shape[0])[None, None, :]
    seen = pos < ends[:, :, None]
    if window is not None:
        seen = seen & (pos >= ends[:, :, None] - window)
    with jax.default_matmul_precision(HIGHEST):
        logits = jnp.einsum("rthk,shk->rhts", q, k) * q.shape[-1] ** -0.5
        probs = jax.nn.softmax(
            jnp.where(seen[:, None], logits, -jnp.inf), -1)
        return jnp.einsum("rhts,shk->rthk", probs, v)


def token_agreement(rows, want) -> float:
    """Share of positions whose largest logit (``rows [N, V]``) is the
    token ``want [N]``."""
    return float(jnp.mean(jnp.argmax(rows, -1) == jnp.asarray(want)))
