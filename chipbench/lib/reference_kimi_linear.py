"""Plain reference of Kimi-Linear-48B-A3B's blocks (moonshotai,
``model_type: kimi_linear``; arXiv:2510.26692), as ISSUE 55 writes them
down, for ONE chip of the sixteen that share each layer.  On one
sequence ``x [S, d]``, ``n`` an RMSNorm with a weight (eps 1e-5)::

    h = x + Mixer(n1(x));   out = h + FFN(n2(h))

*KDA mixer* (``kda_layers``; 32 heads, keys and values of 128)::

    q, k, v = SiLU(conv4(y Wq)), SiLU(conv4(y Wk)), SiLU(conv4(y Wv))
              three causal depthwise convolutions, zeros before 0
    q = q / |q| / sqrt(128),  k = k / |k|                    a head
    g_t    = -exp(A_log) softplus(f_b(f_a(y_t)) + dt_bias)   [H, 128]
    beta_t = sigmoid(y_t Wb)                                 [H]
    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                       S_0 = 0, S in R^(128 x 128)
    Mixer = [RMSNorm_128(o) * sigmoid(g_b(g_a(y)))] Wo

*MLA mixer* (``full_attn_layers``; ``mla_use_nope``: NO rotation)::

    q = y Wq  (32 x [128 | 64]);  [c' 512 | k_r 64] = y Wkva;  c = n_kv(c')
    [k_nope 128 | v 128] = c Wkvb a head
    s = (q_nope . k_nope + q_r . k_r) / sqrt(192), causal softmax

*FFN*: layer 0 SwiGLU(9216); the others ``s = sigmoid(z Wr)`` over 256,
the 8 largest of ``s + b``, gates ``s[idx] / sum s[idx] * 2.446``, the
experts HELD HERE (``num_experts`` of the file, from
``experts_held_first``) of those, and one shared SwiGLU(1024) beside the
sum; what the other chips' experts would add is left out.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence TOKEN BY
TOKEN, full softmax over expanded keys in blocks of ``Q_BLOCK`` queries,
a loop over the held experts, one layer (and inside it one expert) cast
to float32 at a time, the head in blocks of ``V_BLOCK`` rows.  No
kernels, no cache, no chunks, nothing imported from the program
(``from_program_params`` / ``from_program_state`` are the adapters that
know its trees).  The references made WRONG on purpose (``FAULTS``) are
operands of the one compiled layer.  Departures from the published
model are the configuration file's ``assumed`` list.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
Q_BLOCK = 512
V_BLOCK = 20480
PAD = 512
L2_EPS = 1e-6

# each names what a program with that fault computes
FAULTS = (
    "scalar_decay",          # one decay a head: the channels' mean
    "rotated",               # the latent layers rotate q_r and k_r
    "silu_gate",             # Gated DeltaNet's output gate
    "beta_range",            # write strength in (0, 2)
    "bias_in_gates",         # g from s + b, not from s
    "no_route_scale",        # g not multiplied by routed_scaling_factor
    "no_shared_expert",      # the routed sum alone
    "absent_experts_added",  # a pair whose expert is another chip's runs
                             # the nearest expert held here
)


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    c, lin = config, config["linear_attn_config"]
    n = int(c["num_hidden_layers"])
    kda = {i - 1 for i in lin["kda_layers"]}        # the file counts from 1
    return {
        "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"]),
        "layer_kinds": tuple("kda" if i in kda else "mla" for i in range(n)),
        "heads": int(lin["num_heads"]), "dk": int(lin["head_dim"]),
        "taps": int(lin["short_conv_kernel_size"]),
        "r": int(c["kv_lora_rank"]), "dn": int(c["qk_nope_head_dim"]),
        "dr": int(c["qk_rope_head_dim"]), "dv": int(c["v_head_dim"]),
        "top_k": int(c["num_experts_per_token"]),
        "scale": float(c["routed_scaling_factor"]),
        "held": int(c["num_experts"]),
        "first": int(c.get("experts_held_first", 0)),
        "n_dense": int(c["first_k_dense_replace"])}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py GPT._stack_periods``:
    the unrolled runs ``head`` and ``tail``, the scanned periods
    ``blocks`` stacked over periods, one subtree ``layer_<j>`` a place)
    -> the reference's weights.  Nothing is copied: ``layer_weights``
    takes one layer out when it is that layer's turn."""
    import flax.linen as nn
    p = nn.unbox(params)
    places = lambda run: sorted(                             # noqa: E731
        p.get(run, {}), key=lambda name: int(name.split("_")[1]))
    periods = jax.tree.leaves(p["blocks"])[0].shape[0]
    return {"embed": p["embed"], "final_norm": p["final_norm"]["scale"],
            "lm_head": p["lm_head"]["kernel"], "tree": p,
            "layers": [("head", name, None) for name in places("head")]
            + [("blocks", name, i) for i in range(periods)
               for name in places("blocks")]
            + [("tail", name, None) for name in places("tail")]}


def layer_weights(weights: dict, index: int) -> dict:
    """Layer ``index``'s matrices, by the reference's own names."""
    run, name, i = weights["layers"][index]
    b = weights["tree"][run][name]
    if i is not None:
        b = jax.tree.map(lambda a: a[i], b)
    a = b["attn"]
    w = {"attn_norm": b["attn_norm"]["scale"],
         "mlp_norm": b["mlp_norm"]["scale"],
         "wq": a["wq"]["kernel"], "wo": a["wo"]["kernel"]}
    if "conv" in a:
        w.update(wk=a["wk"]["kernel"], wv=a["wv"]["kernel"],
                 wf_a=a["wf_a"]["kernel"], wf_b=a["wf_b"]["kernel"],
                 wg_a=a["wg_a"]["kernel"], wg_b=a["wg_b"]["kernel"],
                 wb=a["wb"]["kernel"], conv=a["conv"], A_log=a["A_log"],
                 dt_bias=a["dt_bias"], o_norm=a["o_norm"])
    else:
        w.update(wkv_a=a["wkv_a"]["kernel"], kv_norm=a["kv_norm"]["scale"],
                 wkv_b=a["wkv_b"])
    if "mlp" in b:
        w.update({k: b["mlp"][k]["kernel"]
                  for k in ("w_gate", "w_up", "w_down")})
    else:
        w.update(router=b["moe"]["router"]["kernel"],
                 bias=b["moe"]["e_score_correction_bias"],
                 w_gate=b["moe"]["w_gate"], w_up=b["moe"]["w_up"],
                 w_down=b["moe"]["w_down"],
                 s_gate=b["shared_mlp"]["w_gate"]["kernel"],
                 s_up=b["shared_mlp"]["w_up"]["kernel"],
                 s_down=b["shared_mlp"]["w_down"]["kernel"])
    return w


def from_program_state(state, tail, heads: int, taps: int):
    """One layer's state entry and convolution tail as the program's
    leaves hold them (``[dk, heads * dv]``; the last ``taps - 1`` inputs
    flat in rows of 128) -> ``(S [heads, dk, dv], tail [taps - 1,
    channels])``."""
    dk, hdv = state.shape
    return (jnp.moveaxis(state.reshape(dk, heads, hdv // heads), 1, 0),
            tail.reshape(taps - 1, -1))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rnd(a, bits):
    """``a`` as a float of ``bits`` mantissa bits would hold it (7:
    bfloat16, 3: float8_e4m3), whatever its exponent; None: as it is.
    Only the WRONG references round."""
    if bits is None:
        return a
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=bits)


def recurrence(q, k, v, alpha, beta, state0, state_bits=None, snap_at=None):
    """The delta rule under a decay a key channel, token by token: q, k
    [S, H, dk], v [S, H, dv], alpha [S, H, dk], beta [S, H], state0 [H,
    dk, dv] -> ``(o [S, H, dv], state, snap)``, ``snap`` the state as it
    was after ``snap_at`` tokens (an int32 scalar, traced or not; None:
    ``state0``).  ``state_bits``: a WRONG recurrence that keeps its
    state in that many mantissa bits."""
    def step(carry, xs):
        s, snap = carry
        qt, kt, vt, at, bt, t = xs
        s = s * at[:, :, None]
        ks = jnp.einsum("hk,hkv->hv", kt, s)
        s = _rnd(s + kt[:, :, None] * (bt[:, None] * (vt - ks))[:, None, :],
                 state_bits)
        if snap_at is not None:
            snap = jnp.where(t + 1 == snap_at, s, snap)
        return (s, snap), jnp.einsum("hk,hkv->hv", qt, s)
    with jax.default_matmul_precision(HIGHEST):
        (state, snap), o = jax.lax.scan(step, (state0, state0), (
            q, k, v, alpha, beta, jnp.arange(q.shape[0])))
    return o, state, snap


def _rope_pairs(x, theta):
    """x [S, heads, dr]; position i turns the pair (2j, 2j+1) by
    i / theta**(2j/dr) (the ``rotated`` fault's rotation)."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1
                     ).reshape(x.shape)


def _kda(y, w, f, kd, bits, cut):
    """``Mixer(y)`` of a KDA layer -> ``(out [S, d], (states, tails))``:
    what the layer holds after ``cut[0]`` and after all ``S`` tokens,
    the state ``[2, H, dk, dv]`` and the convolution's last ``taps - 1``
    inputs ``[2, taps - 1, channels]``.  ``cut`` [2] int32, an operand:
    that place and the sequence's end (what follows is padding)."""
    heads, dk, taps = kd["heads"], kd["dk"], kd["taps"]
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    s = y.shape[0]
    y = _rnd(y, bits)
    u = jnp.concatenate([
        jnp.einsum("sd,dhk->shk", y, lo(w[n])).reshape(s, -1)
        for n in ("wq", "wk", "wv")], -1)
    taps_w = f32(w["conv"])
    c = sum(taps_w[j] * jnp.pad(u, ((taps - 1 - j, 0), (0, 0)))[:s]
            for j in range(taps))       # tap j reads u_{t - (taps-1) + j}
    c = jax.nn.silu(c)
    qk = heads * dk
    q, k = (c[:, a:a + qk].reshape(s, heads, dk) for a in (0, qk))
    v = c[:, 2 * qk:].reshape(s, heads, -1)
    unit = lambda a: a * jax.lax.rsqrt(                      # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    low_rank = lambda a, b: jnp.einsum(                      # noqa: E731
        "sr,rhk->shk", _rnd(y @ lo(w[a]), bits), lo(w[b]))
    g = -jnp.exp(f32(w["A_log"]))[:, None] * jax.nn.softplus(
        low_rank("wf_a", "wf_b") + f32(w["dt_bias"]))
    g = jnp.where(f["scalar_decay"] > 0, g.mean(-1, keepdims=True), g)
    beta = jax.nn.sigmoid(y @ lo(w["wb"])) * (1.0 + f["beta_range"])
    z = low_rank("wg_a", "wg_b")
    gate = jnp.where(f["silu_gate"] > 0, jax.nn.silu(z), jax.nn.sigmoid(z))
    q, k, v = _rnd(q, bits), _rnd(k, bits), _rnd(v, bits)
    alpha = jnp.exp(g)
    # nothing moves past the sequence's end (what follows is padding)
    real = jnp.arange(s) < cut[1]
    o, s2, s1 = recurrence(
        q, k, v, jnp.where(real[:, None, None], alpha, 1.0),
        jnp.where(real[:, None], beta, 0.0),
        jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), snap_at=cut[0])
    o = _rms_norm(o, f32(w["o_norm"]), kd["eps"]) * gate
    window = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    tails = jnp.stack([jax.lax.dynamic_slice_in_dim(window, n, taps - 1)
                       for n in (cut[0], cut[1])])
    return (_rnd(o.reshape(s, -1), bits) @ lo(w["wo"]),
            (jnp.stack([s1, s2]), tails))


def _attend(q, k, v, scale, bits=None):
    """q, k [S, H, dq], v [S, H, dv] -> [S, H*dv]: causal, a block of
    ``Q_BLOCK`` queries at a time against all keys."""
    s, heads, _ = q.shape
    q, k, v = _rnd(q, bits), _rnd(k, bits), _rnd(v, bits)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, lo = args
        i = lo + jnp.arange(Q_BLOCK)[:, None]
        logits = jnp.einsum("qhk,thk->hqt", qb, k) * scale
        probs = jax.nn.softmax(jnp.where((j <= i)[None], logits, -jnp.inf),
                               -1)
        return jnp.einsum("hqt,thk->qhk", _rnd(probs, bits), v)
    out = jax.lax.map(block, (q.reshape(s // Q_BLOCK, Q_BLOCK, heads, -1),
                              jnp.arange(0, s, Q_BLOCK)))
    return out.reshape(s, -1)


def _mla(y, w, f, kd, bits):
    """``Mixer(y)`` of a latent-attention layer."""
    r, dn = kd["r"], kd["dn"]
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731
    y = _rnd(y, bits)
    q = jnp.einsum("sd,dhk->shk", y, lo(w["wq"]))
    ckv = y @ lo(w["wkv_a"])
    c = _rms_norm(ckv[:, :r], w["kv_norm"].astype(jnp.float32), kd["eps"])
    kv = jnp.einsum("sr,rhe->she", _rnd(c, bits), lo(w["wkv_b"]))
    turned = f["rotated"] > 0
    q_r = jnp.where(turned, _rope_pairs(q[..., dn:], kd["theta"]),
                    q[..., dn:])
    k_r = jnp.where(turned, _rope_pairs(ckv[:, None, r:], kd["theta"]),
                    ckv[:, None, r:])
    q = jnp.concatenate([q[..., :dn], q_r], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r, kv.shape[:2] + k_r.shape[-1:])], -1)
    return _rnd(_attend(q, k, kv[..., dn:], (dn + kd["dr"]) ** -0.5, bits),
                bits) @ lo(w["wo"])


def _swiglu(z, w_gate, w_up, w_down, bits):
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731
    mid = _rnd(jax.nn.silu(z @ lo(w_gate)) * (z @ lo(w_up)), bits)
    return mid @ lo(w_down)


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


def _experts(z, c, w, f, kd, bits):
    """``sum_{e held} c[:, e] down_e(silu(gate_e z) * up_e z)``, every
    held expert on every token weighted by its gate (0 where not
    chosen), one expert cast to float32 at a time."""
    first, held = kd["first"], kd["held"]
    here = c[:, first:first + held]
    # the fault: an index past the held range clamped to its nearest end
    clamped = here.at[:, 0].add(c[:, :first].sum(-1)).at[:, -1].add(
        c[:, first + held:].sum(-1))
    here = jnp.where(f["absent_experts_added"] > 0, clamped, here)
    z = _rnd(z, bits)

    def one(y, xs):
        wg, wu, wd, ce = xs
        return y + ce[:, None] * _swiglu(z, wg, wu, wd, bits), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        (w["w_gate"], w["w_up"], w["w_down"], here.T))
    return y


@functools.partial(jax.jit, static_argnames=("kind", "dense", "kd", "bits"))
def _layer(x, w, f, cut, *, kind: str, dense: bool, kd: tuple, bits=None):
    """One block on one sequence ``x [S, d]``; ``w`` one layer's
    matrices, ``f`` the faults' switches (all 0: the block as
    published), ``kd`` ``kinds()`` as a sorted tuple of items.  Returns
    ``(x, left)``: what a KDA layer holds (``_kda``; None of a latent
    layer)."""
    kd = dict(kd)
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    y = _rms_norm(x, f32(w["attn_norm"]), kd["eps"])
    left = None
    if kind == "kda":
        y, left = _kda(y, w, f, kd, bits, cut)
    else:
        y = _mla(y, w, f, kd, bits)
    x = x + y
    z = _rms_norm(x, f32(w["mlp_norm"]), kd["eps"])
    if dense:
        return x + _swiglu(_rnd(z, bits), w["w_gate"], w["w_up"],
                           w["w_down"], bits), left
    logits = z @ f32(w["router"])
    scale = jnp.where(f["no_route_scale"] > 0, 1.0, kd["scale"])
    c = route(logits, w["bias"], kd["top_k"], scale, f["bias_in_gates"])
    shared = _swiglu(_rnd(z, bits), w["s_gate"], w["s_up"], w["s_down"],
                     bits) * (1.0 - f["no_shared_expert"])
    return x + _experts(z, c, w, f, kd, bits) + shared, left


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, rows):
    return x @ rows.astype(jnp.float32)


def _head(x, lm_head):
    """``x @ lm_head`` with ``V_BLOCK`` of the head's columns cast to
    float32 at a time (the whole of it is 1.5 GB)."""
    v = lm_head.shape[1]
    return jnp.concatenate([_head_block(x, lm_head[:, lo:lo + V_BLOCK])
                            for lo in range(0, v, V_BLOCK)], -1)


def hidden(weights: dict, tokens, config: dict, *, fault: str = None,
           bits: int = None, pad_to: int = 0, cut: int = None,
           more: bool = False):
    """Post-final-norm hidden states ``[S, d]`` of one sequence.
    ``fault`` (one of ``FAULTS``) and ``bits`` (every product's operands
    rounded to that many mantissa bits: 3 is a float8_e4m3 pass) build
    WRONG references on purpose.  ``pad_to``: run at that length at
    least, and at a multiple of ``PAD`` (token 0 AFTER the end: every
    layer is causal).  ``more``: also ``{"states" [K, 2, H, dk, dv],
    "tails" [K, 2, taps - 1, channels]}`` of the K KDA layers after
    ``cut`` tokens and after all of them, and the routers' inputs ``[L,
    S, d]`` and logits ``[L, S, E]`` of the expert layers."""
    kd = kinds(config)
    key = tuple(sorted(kd.items()))
    f = {name: jnp.float32(name == fault) for name in FAULTS}
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, max(pad_to, n) - n))
    tokens = jnp.pad(tokens, (0, -tokens.shape[0] % PAD))
    # the recurrence's account stops at the sequence's end: what it
    # holds "after all of them" is after ``n``, not after the pad
    ends = jnp.asarray([n if cut is None else cut, n], jnp.int32)
    lefts = []
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        for i, kind in enumerate(kd["layer_kinds"]):
            dense = i < kd["n_dense"]
            x, left = _layer(x, layer_weights(weights, i), f, ends,
                             kind=kind, dense=dense, kd=key, bits=bits)
            if more and left is not None:
                lefts.append(left)
        out = _norm(x, weights["final_norm"], eps=kd["eps"])[:n]
    if not more:
        return out
    return out, {"states": jnp.stack([a for a, _ in lefts]),
                 "tails": jnp.stack([b for _, b in lefts])}


def logits(weights: dict, tokens, config: dict, **wrong):
    """float32 logits [S, V] of one sequence of token ids [S]."""
    with jax.default_matmul_precision(HIGHEST):
        return _head(hidden(weights, tokens, config, **wrong),
                     weights["lm_head"])


# What the program's numbers are held to ---------------------------------

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
    want|^2`` over all rows: about 0 for a program that computes the
    block as published, about 1 for one with that fault."""
    got, want, wrong = (a.astype(jnp.float32) for a in (got, want, wrong))
    step = wrong - want
    size = float(jnp.sum(step * step))
    return float(jnp.sum((got - want) * step)) / size if size else 0.0


def hidden_check(weights: dict, tokens, got, config: dict, *,
                 n_prompt: int, faults=FAULTS, left=None, got_logits=None,
                 logit_rows=None, pad_to: int = 0) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` (a prompt of ``n_prompt`` tokens prefilled, then one
    decode step a position) against the reference's, row by row
    (``hidden_rel_err``: the mean of |got - want| / |want|), and against
    each reference made wrong on purpose: ``<fault>_projection`` and
    ``<fault>_control`` (how far that reference lies from the right one:
    a fault that moved nothing would prove nothing); ``fp8_control``:
    the reference with every product's operands rounded to
    float8_e4m3's mantissa, as ``hidden_rel_err`` reads it.

    ``left``: ``{"prompt": (states [K, H, dk, dv], tails [K, taps - 1,
    channels]), "end": (..)}``, what the program's state entry held in
    each KDA layer after the served prompt and after the last decode
    step: ``state_rel_err`` / ``tail_rel_err`` the worst layer's
    distance from the reference's at either point, ``*_control`` the
    same against the NEXT layer's (an entry or a layer index off by
    one).  ``got_logits [R, V]`` at ``logit_rows``: ``logits_rel_err``
    through the reference's own head, ``logits_fp8_control`` the float8
    reference's."""
    run = functools.partial(hidden, weights, tokens, config, pad_to=pad_to,
                            cut=n_prompt)
    want, more = run(more=True)
    err = _row_err(got, want)
    out = {"positions": len(tokens),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err)),
           "hidden_rel_err_decode": float(jnp.mean(err[n_prompt:]))
           if len(tokens) > n_prompt else None}
    if left is not None:
        for at, when in enumerate(("prompt", "end")):
            for key, value in _left_errors(
                    left[when], more["states"][:, at],
                    more["tails"][:, at]).items():
                pick = np.minimum if key.endswith("_control") \
                    else np.maximum
                out[key] = float(pick(out.get(key, value), value))
    for fault in faults:
        wrong = run(fault=fault)
        out[f"{fault}_projection"] = _projection(got, want, wrong)
        out[f"{fault}_control"] = float(jnp.mean(_row_err(wrong, want)))
    low = None
    if faults:
        low = run(bits=3)
        out["fp8_control"] = float(jnp.mean(_row_err(low, want)))
    if got_logits is not None:
        with jax.default_matmul_precision(HIGHEST):
            ref_logits = _head(want[logit_rows], weights["lm_head"])
            out["logits_rel_err"] = float(jnp.mean(_row_err(got_logits,
                                                            ref_logits)))
            if low is not None:
                out["logits_fp8_control"] = float(jnp.mean(_row_err(
                    _head(low[logit_rows], weights["lm_head"]),
                    ref_logits)))
    return out


def router_check(weights: dict, z, r) -> dict:
    """The program's router logits ``r [L, N, E]`` against float32
    products of the inputs it read, ``z [L, N, d]`` (the feed-forward's
    normalised input, the program's own activations): ``router_rel_err``
    is |r - z W_r| / |z W_r| over everything; ``router_bf16_control``
    what a router reads that rounds its float32 logits to bfloat16."""
    routers = [_leaf(weights, i, "moe", "router", "kernel")
               for i in range(len(weights["layers"]))
               if "moe" in _subtree(weights, i)]
    with jax.default_matmul_precision(HIGHEST):
        want = jnp.stack([zl.astype(jnp.float32) @ w.astype(jnp.float32)
                          for zl, w in zip(z, routers)])
    size = jnp.linalg.norm(want)
    low = jax.lax.reduce_precision(want, exponent_bits=8, mantissa_bits=7)
    return {"router_rows": int(r.shape[0] * r.shape[1]),
            "router_rel_err": float(
                jnp.linalg.norm(r.astype(jnp.float32) - want) / size),
            "router_bf16_control": float(
                jnp.linalg.norm(low - want) / size)}


def _subtree(weights: dict, index: int):
    run, name, _ = weights["layers"][index]
    return weights["tree"][run][name]


def _leaf(weights: dict, index: int, *path):
    """One leaf of layer ``index`` (``layer_weights`` takes the whole
    layer out of its stack, experts and all)."""
    leaf = _subtree(weights, index)
    for key in path:
        leaf = leaf[key]
    i = weights["layers"][index][2]
    return leaf if i is None else leaf[i]


def latent_up_projection(weights: dict, index: int):
    """``wkv_b [r, H, dn + dv]`` of latent layer ``index``."""
    return _leaf(weights, index, "attn", "wkv_b")


def _left_errors(left, want_states, want_tails, prefix="") -> dict:
    """The worst KDA layer's distance of what a program's state entry
    held (``left = (states [K, H, dk, dv], tails [K, taps - 1,
    channels])``) from the reference's, and the least distance from the
    NEXT layer's (an entry or a layer index off by one)."""
    out = {}
    for name, mine, ref in (("state", left[0], want_states),
                            ("tail", left[1], want_tails)):
        k = mine.shape[0]
        # numpy's, not max(): a NaN reading has to come out as NaN
        out[f"{prefix}{name}_rel_err"] = float(np.max(
            [_rel(mine[i], ref[i]) for i in range(k)]))
        out[f"{prefix}{name}_control"] = float(np.min(
            [_rel(mine[i], ref[(i + 1) % k]) for i in range(k)]))
    return out


def handover_check(weights: dict, tokens, left, config: dict) -> dict:
    """What the engine's OWN compiled prefill and decode block left in a
    state entry after ``tokens [S]`` (a prompt, its first token and a
    block's worth of the engine's own answer): ``left = (states,
    tails)`` of the K KDA layers against the reference's after the same
    tokens (``handover_state_rel_err``, ``handover_tail_rel_err``,
    ``*_control``: ``_left_errors``)."""
    _, more = hidden(weights, tokens, config, more=True)
    return _left_errors(left, more["states"][:, 1], more["tails"][:, 1],
                        "handover_")


def recurrence_check(o, q, k, v, g, beta, state0, mask=None) -> dict:
    """A decode kernel's or a prompt form's outputs ``o [T, R, H, dv]``
    over ``T`` tokens of ``R`` rows against the float32 recurrence from
    the same ``state0 [R, H, dk, dv]`` on the same inputs (q, k [T, R,
    H, dk], v [T, R, H, dv], g [T, R, H, dk], beta [T, R, H]):
    ``rel_err`` is the largest row's |o - want| / |want| over all its
    tokens; ``bf16_state_control`` what a recurrence reads that rounds
    its state to bfloat16 every step; ``scalar_decay_control`` one that
    decays every channel of a head by their mean.  ``mask [T, R]``: the
    positions that count (None: all)."""
    want, low, scalar = _recurrences(q, k, v, g, beta, state0)
    if mask is not None:
        want, low, scalar = (jnp.where(mask[..., None, None], a, 0.0)
                             for a in (want, low, scalar))
    flat = lambda a: a.reshape(a.shape[0], a.shape[1], -1)   # noqa: E731
    size = jnp.linalg.norm(flat(want), axis=(0, 2))
    dist = lambda a: float(jnp.max(jnp.linalg.norm(          # noqa: E731
        flat(a.astype(jnp.float32) - want), axis=(0, 2)) / size))
    return {"rel_err": dist(o), "bf16_state_control": dist(low),
            "scalar_decay_control": dist(scalar)}


@jax.jit
def _recurrences(q, k, v, g, beta, state0):
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731

    def rows(g, state_bits=None):
        run = lambda q, k, v, a, b, s: recurrence(           # noqa: E731
            q, k, v, a, b, s, state_bits)[0]
        return jax.vmap(run, in_axes=(1, 1, 1, 1, 1, 0), out_axes=1)(
            f32(q), f32(k), f32(v), jnp.exp(f32(g)), f32(beta), f32(state0))
    mean = jnp.broadcast_to(f32(g).mean(-1, keepdims=True), g.shape)
    return rows(g), rows(g, 7), rows(mean)


@functools.partial(jax.jit, static_argnames=("dn", "r", "scale_dim"))
def decode_attention(q, rows, wkv_b, lengths, *, dn: int, r: int,
                     scale_dim: int = None):
    """Plain EXPANDED attention of one query a row over the first
    ``lengths[i]`` of the same cached latent rows: ``q [R, H, dn + dr]``,
    ``rows [T, >= r + dr]`` as a latent pool holds them (``[c | k_r]``,
    anything past ``r + dr`` ignored), ``wkv_b [r, H, dn + dv]`` -> ``[R,
    H, dv]`` float32.  What an absorbed decode kernel followed by ``Wuv``
    has to equal.  ``scale_dim``: the softmax scale's head size where it
    is not ``dn + dr`` (a control)."""
    q, rows, wkv_b = (a.astype(jnp.float32) for a in (q, rows, wkv_b))
    dr = q.shape[-1] - dn
    with jax.default_matmul_precision(HIGHEST):
        kv = jnp.einsum("tr,rhe->the", rows[:, :r], wkv_b)
        logits = (jnp.einsum("ihk,thk->iht", q[..., :dn], kv[..., :dn])
                  + jnp.einsum("ihk,tk->iht", q[..., dn:],
                               rows[:, r:r + dr])
                  ) * (scale_dim or dn + dr) ** -0.5
        seen = jnp.arange(rows.shape[0])[None, :] < lengths[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], logits, -jnp.inf), -1)
        return jnp.einsum("iht,the->ihe", probs, kv[..., dn:])
