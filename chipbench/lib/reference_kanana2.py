"""Plain reference of Kanana-2-30B-A3B's blocks (kakaocorp,
``model_type: deepseek_v3``), as ISSUE 37 writes them down, for ONE chip
of the eight that share each layer.  On one sequence ``x [S, d]``, with
``n`` an RMSNorm with a weight (eps 1e-6)::

    h   = x + Attn(n1(x));   out = h + FFN(n2(h))
    Attn(y):  q = y Wq            32 heads of [q_nope 128 | q_rope 64]
              ckv = y Wkva        [c' 512 | k_rope' 64]
              c = n_kv(c');  k_rope = rope(k_rope'), ONE for all heads
              [k_nope 128 | v 128] = c Wkvb   a head
              s = (q_nope . k_nope + rope(q_rope) . k_rope) / sqrt(192)
              causal softmax in float32, o = p v, Attn = o Wo
    rope:     theta 1e6 over the 64 dims, the pairs (2j, 2j+1) in place
    FFN, layer 0:   SwiGLU(6144)
    FFN, layers 1..: s = sigmoid(z Wr), 128 wide, float32
              idx = the 6 largest of s + b        (b: the choice only)
              g = s[idx] / (sum s[idx] + 1e-20) * 2.448
              sum_{e in idx, e HELD HERE} g_e down_e(silu(gate_e z) * up_e z)
                + Shared(z),   Shared one SwiGLU of 2 x 768

EXPANDED attention only: keys and values a head from ``c Wkvb``, no
cache, no absorption.  The experts held here are ``n_routed_experts`` of
the file (16) starting at ``experts_held_first``; the router is
``published.n_routed_experts`` (128) wide; what the other experts would
add is left out, as in the program: that partial sum is the layer's
output on this chip.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sort,
nothing imported from the program (``from_program_params`` is the one
adapter that knows its parameter tree).  Its own weight layout::

    embed [V, d]; final_norm [d]; lm_head [d, V]
    dense / layers (leading layer axis): attn_norm, mlp_norm [L, d],
        wq [L, d, H, 192], wkv_a [L, d, 576], kv_norm [L, 512],
        wkv_b [L, 512, H, 256], wo [L, H*128, d]
    dense:  w_gate, w_up [L, d, F], w_down [L, F, d]
    layers: router [L, d, E], bias [L, E], w_gate, w_up [L, held, d, f],
        w_down [L, held, f, d], s_gate, s_up [L, d, fs], s_down [L, fs, d]

So that 9,000 positions of the published widths fit beside a serving
engine that holds 11 GB: one layer, and inside it one expert, is cast to
float32 at a time; attention runs in blocks of ``Q_BLOCK`` queries; the
comparisons are of hidden states, so the 128k-row head runs only where a
test asks for ``logits``.

The references made WRONG on purpose (``FAULTS``) are operands of the
one compiled layer, not programs of their own (a compile a fault a
length would be most of the check's time); a sequence is padded to a
multiple of ``PAD`` with token 0 (every layer is causal: what follows a
position does not reach it).
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 512
PAD = 512

# each names what a program with that fault computes
FAULTS = (
    "no_rope_part",          # scores from q_nope . k_nope alone
    "latent_unnormed",       # c' cached and expanded without its norm
    "bias_in_gates",         # g from s + b, not from s
    "no_route_scale",        # g not multiplied by routed_scaling_factor
    "no_shared_expert",      # the routed sum alone
    "absent_experts_added",  # a pair whose expert is another chip's runs
                             # the nearest expert held here (an index
                             # clamped into range, not dropped)
    "scale_128",             # 1/sqrt(qk_nope_head_dim) for 1/sqrt(192)
)


def kinds(config: dict) -> dict:
    """What the reference needs of the published ``config.json`` keys."""
    c = config
    return {
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "r": int(c["kv_lora_rank"]), "dn": int(c["qk_nope_head_dim"]),
        "dr": int(c["qk_rope_head_dim"]), "dv": int(c["v_head_dim"]),
        "top_k": int(c["num_experts_per_tok"]),
        "scale": float(c["routed_scaling_factor"]),
        "held": int(c["n_routed_experts"]),
        "first": int(c.get("experts_held_first", 0)),
        "n_dense": int(c["first_k_dense_replace"]),
        "n_layers": int(c["num_hidden_layers"])}


def from_program_params(params) -> dict:
    """The program's flax tree (``models/gpt.py``: the dense prefix stack
    ``dense_blocks``, the scanned expert stack ``blocks``) -> the
    reference's layout.  Shapes only move; no value changes."""
    import flax.linen as nn
    p = nn.unbox(params)

    def attention(b):
        a = b["attn"]
        return {"attn_norm": b["attn_norm"]["scale"],
                "mlp_norm": b["mlp_norm"]["scale"],
                "wq": a["wq"]["kernel"], "wkv_a": a["wkv_a"]["kernel"],
                "kv_norm": a["kv_norm"]["scale"], "wkv_b": a["wkv_b"],
                "wo": a["wo"]["kernel"]}
    d, b = p["dense_blocks"], p["blocks"]
    return {
        "embed": p["embed"], "final_norm": p["final_norm"]["scale"],
        "lm_head": p["lm_head"]["kernel"],
        "dense": {**attention(d),
                  "w_gate": d["mlp"]["w_gate"]["kernel"],
                  "w_up": d["mlp"]["w_up"]["kernel"],
                  "w_down": d["mlp"]["w_down"]["kernel"]},
        "layers": {**attention(b),
                   "router": b["moe"]["router"]["kernel"],
                   "bias": b["moe"]["e_score_correction_bias"],
                   "w_gate": b["moe"]["w_gate"], "w_up": b["moe"]["w_up"],
                   "w_down": b["moe"]["w_down"],
                   "s_gate": b["shared_mlp"]["w_gate"]["kernel"],
                   "s_up": b["shared_mlp"]["w_up"]["kernel"],
                   "s_down": b["shared_mlp"]["w_down"]["kernel"]},
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


def _rope_pairs(x, theta):
    """x [S, heads, dr]; position i turns the pair (2j, 2j+1) by
    i / theta**(2j/dr), in place."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1
                     ).reshape(x.shape)


def _attend(q, k, v, scale, bits=None):
    """q, k [S, H, dq], v [S, H, dv] -> [S, H*dv]: causal, a block of
    ``Q_BLOCK`` queries at a time against all keys (``lax.map``: one
    block's program whatever the length; ``S`` is a multiple of
    ``Q_BLOCK``, ``hidden`` pads to it)."""
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


def _attention(y, w, f, kd, bits):
    """``Attn(y)`` (module docstring); ``f``: the faults' switches."""
    r, dn = kd["r"], kd["dn"]
    lo = lambda a: _rnd(a.astype(jnp.float32), bits)         # noqa: E731
    y = _rnd(y, bits)
    q = jnp.einsum("sd,dhk->shk", y, lo(w["wq"]))
    ckv = y @ lo(w["wkv_a"])
    c = jnp.where(f["latent_unnormed"] > 0, ckv[:, :r], _rms_norm(
        ckv[:, :r], w["kv_norm"].astype(jnp.float32), kd["eps"]))
    kv = jnp.einsum("sr,rhe->she", _rnd(c, bits), lo(w["wkv_b"]))
    keep_rope = 1.0 - f["no_rope_part"]
    q_rope = _rope_pairs(q[..., dn:], kd["theta"]) * keep_rope
    k_rope = _rope_pairs(ckv[:, None, r:], kd["theta"])
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope, kv.shape[:2] + k_rope.shape[-1:])], -1)
    scale = jnp.where(f["scale_128"] > 0, dn ** -0.5,
                      (dn + kd["dr"]) ** -0.5)
    return _rnd(_attend(q, k, kv[..., dn:], scale, bits), bits) @ lo(w["wo"])


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
    chosen: exact zeros), one expert cast to float32 at a time."""
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


@functools.partial(jax.jit, static_argnames=("dense", "kd", "bits"))
def _layer(x, w, f, *, dense: bool, kd: tuple, bits=None):
    """One block on one sequence ``x [S, d]``; ``w`` one layer's slice,
    ``f`` the faults' switches (all 0: the block as published), ``kd``
    ``kinds()`` as a sorted tuple of items.  Returns ``(x, z, logits)``:
    the router's own input and output (None in a dense layer)."""
    kd = dict(kd)
    f32 = lambda a: a.astype(jnp.float32)                    # noqa: E731
    x = x + _attention(_rms_norm(x, f32(w["attn_norm"]), kd["eps"]), w, f,
                       kd, bits)
    z = _rms_norm(x, f32(w["mlp_norm"]), kd["eps"])
    if dense:
        return x + _swiglu(_rnd(z, bits), w["w_gate"], w["w_up"],
                           w["w_down"], bits), None, None
    logits = z @ f32(w["router"])
    scale = jnp.where(f["no_route_scale"] > 0, 1.0, kd["scale"])
    c = route(logits, w["bias"], kd["top_k"], scale, f["bias_in_gates"])
    shared = _swiglu(_rnd(z, bits), w["s_gate"], w["s_up"], w["s_down"],
                     bits) * (1.0 - f["no_shared_expert"])
    return x + _experts(z, c, w, f, kd, bits) + shared, z, logits


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(jnp.float32)


def hidden(weights: dict, tokens, config: dict, *, fault: str = None,
           bits: int = None, router_io: bool = False, pad_to: int = 0):
    """Post-final-norm hidden states ``[S, d]`` of one sequence.
    ``fault`` (one of ``FAULTS``) and ``bits`` (every product's operands
    rounded to that many mantissa bits: 3 is a float8_e4m3 pass) build
    WRONG references on purpose.  ``router_io``: also the routers'
    inputs ``[L, S, d]`` and logits ``[L, S, E]`` of the expert layers.
    ``pad_to``: run at that length at least (requests of several lengths
    then share one compiled layer)."""
    kd = kinds(config)
    key = tuple(sorted(kd.items()))
    f = {name: jnp.float32(name == fault) for name in FAULTS}
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, max(pad_to, n) - n))
    tokens = jnp.pad(tokens, (0, -tokens.shape[0] % PAD))
    zs, rs = [], []
    with jax.default_matmul_precision(HIGHEST):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(kd["n_layers"]):
            dense = i < kd["n_dense"]
            stack = weights["dense" if dense else "layers"]
            w = jax.tree.map(
                lambda a: a[i if dense else i - kd["n_dense"]], stack)
            x, z, r = _layer(x, w, f, dense=dense, kd=key, bits=bits)
            if router_io and not dense:
                zs.append(z[:n])
                rs.append(r[:n])
        out = _norm(x, weights["final_norm"], eps=kd["eps"])[:n]
    return (out, jnp.stack(zs), jnp.stack(rs)) if router_io else out


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


def hidden_check(weights: dict, tokens, got, config: dict,
                 faults=FAULTS, pad_to: int = 0) -> dict:
    """The program's post-final-norm hidden states ``got [S, d]`` of
    ``tokens [S]`` against the reference's, row by row
    (``hidden_rel_err``: the mean over rows of |got - want| / |want|),
    and against each reference made wrong on purpose: ``<fault>_projection``
    (``_projection``) and ``<fault>_control`` (how far that reference
    lies from the right one, mean row error: a fault that moved nothing
    would prove nothing); ``fp8_control``: the reference with every
    product's operands rounded to float8_e4m3's mantissa, the precision
    under the stated one, as ``hidden_rel_err`` reads it (run with the
    faults: a request checked without them has ``hidden_rel_err``
    alone)."""
    want = hidden(weights, tokens, config, pad_to=pad_to)
    err = _row_err(got, want)
    out = {"positions": len(tokens),
           "hidden_rel_err": float(jnp.mean(err)),
           "hidden_rel_err_max": float(jnp.max(err))}
    for fault in faults:
        wrong = hidden(weights, tokens, config, fault=fault, pad_to=pad_to)
        out[f"{fault}_projection"] = _projection(got, want, wrong)
        out[f"{fault}_control"] = float(jnp.mean(_row_err(wrong, want)))
    if faults:
        low = hidden(weights, tokens, config, bits=3, pad_to=pad_to)
        out["fp8_control"] = float(jnp.mean(_row_err(low, want)))
    return out


def router_check(weights: dict, z, r) -> dict:
    """The program's router logits ``r [L, N, E]`` against float32
    products of the inputs it read, ``z [L, N, d]`` (the feed-forward's
    normalised input, the program's own activations in the dtype it
    holds them): ``router_rel_err`` is |r - z W_r| / |z W_r| over
    everything.  Products of bfloat16 values are exact in float32, so a
    float32 router reads some 1e-7 here; ``router_bf16_control`` is what
    one reads that does no more than round its float32 logits to
    bfloat16 (some 2e-3)."""
    with jax.default_matmul_precision(HIGHEST):
        want = jnp.einsum("lnd,lde->lne", z.astype(jnp.float32),
                          weights["layers"]["router"].astype(jnp.float32))
    size = jnp.linalg.norm(want)
    low = want.astype(jnp.bfloat16).astype(jnp.float32)
    return {"router_rows": int(r.shape[0] * r.shape[1]),
            "router_rel_err": float(
                jnp.linalg.norm(r.astype(jnp.float32) - want) / size),
            "router_bf16_control": float(
                jnp.linalg.norm(low - want) / size)}


@functools.partial(jax.jit, static_argnames=("dn", "r", "scale_dim"))
def decode_attention(q, rows, wkv_b, lengths, *, dn: int, r: int,
                     scale_dim: int = None):
    """Plain EXPANDED attention of one query a row over the first
    ``lengths[i]`` of the same cached latent rows: ``q [R, H, dn + dr]``
    (rotated already), ``rows [T, >= r + dr]`` as a latent pool holds
    them (``[c | k_rope]``, anything past ``r + dr`` ignored), ``wkv_b
    [r, H, dn + dv]`` -> ``[R, H, dv]`` float32.  What an absorbed
    decode kernel followed by ``Wuv`` has to equal.  ``scale_dim``: the
    softmax scale's head size where it is not ``dn + dr`` (a control)."""
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


def token_agreement(rows, want) -> float:
    """Share of positions whose largest logit (``rows [N, V]``) is the
    token ``want [N]``."""
    return float(jnp.mean(jnp.argmax(rows, -1) == jnp.asarray(want)))
