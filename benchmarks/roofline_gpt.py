"""Component roofline for the GPT train step on one chip.

VERDICT round-4 task #5: gpt-small MFU has been flat at ~54.6% across
rounds while the builder's sweeps exhausted every schedule knob.  This
measures WHERE the step time must go: each GEMM family in the model at
the bench shapes (fwd + dgrad + wgrad), the flash-attention kernel
fwd+bwd, and the norm/rope elementwise chains — then composes the best
MFU any schedule could reach given those measured kernel efficiencies.
If the composed ceiling matches the observed step MFU, the gap is MXU
shape efficiency at d_model-sized tiles, not missing fusion.

Measurement note: a per-dispatch timing loop measures host launch
latency as much as the kernel.  Every probe therefore compiles the same
dependent chain at TWO iteration counts and reports
(T(N2) - T(N1)) / (N2 - N1): the dispatch and fence overheads are
identical between the two and difference away, leaving device time per
iteration.

  python benchmarks/roofline_gpt.py [--preset gpt-small] [--batch 16]

Prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

def _time_two_point(make_chain, x0, n1, n2, calls=3):
    """Seconds per chained iteration via the two-point difference (see
    module docstring).  `make_chain(iters)` returns a jitted fn(x).
    Callers size n2 so the differenced device work is >= ~1 s, well
    above host call-to-call jitter; min-of-calls rejects the positive
    outliers."""
    times = {}
    for n in (n1, n2):
        fn = make_chain(n)
        jax.block_until_ready(fn(x0))             # compile + warm
        best = float("inf")
        for _ in range(calls):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x0))
            best = min(best, time.perf_counter() - t0)
        times[n] = best
    return max((times[n2] - times[n1]) / (n2 - n1), 1e-9)


def time_gemm_pair(m, k, n):
    """One chained iteration = GEMM [m,k]x[k,n] + GEMM [m,n]x[n,k]
    (exactly a fwd + dgrad pair).  Returns seconds per PAIR."""
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.bfloat16)
    b2 = jnp.asarray(rng.standard_normal((n, k)), jnp.bfloat16)
    x0 = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)

    def make_chain(iters):
        @jax.jit
        def chain(x):
            def body(i, x):
                y = jax.lax.dot(x, b)              # [m, n]
                # the relu breaks dot reassociation — without it XLA
                # rewrites dot(dot(x,b),b2) as dot(x, hoisted b@b2) and
                # the probe times ONE matmul while crediting two (first
                # run measured 239 "TFLOPs" on a 197-peak chip); it
                # fuses into the matmul epilogue, costing nothing
                y = jnp.maximum(y, 0) * jnp.bfloat16(3e-2)
                return jax.lax.dot(y, b2)
            out = jax.lax.fori_loop(0, iters, body, x)
            # scalar output: nothing of the chain's size is left live
            # between calls
            return out[0, 0].astype(jnp.float32)
        return chain

    # ~0.5 ms/pair at the small shapes: 2048 extra iters ~ 1-4 s
    return _time_two_point(make_chain, x0, 8, 2056)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt-small")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()

    from ray_tpu.models.configs import get_config

    cfg = get_config(args.preset, max_seq_len=args.seq)
    B, S, D, F, V = (args.batch, args.seq, cfg.d_model, cfg.d_ff,
                     cfg.vocab_size)
    M = B * S
    import bench
    dev = jax.devices()[0]
    peak = bench.peak_flops(dev)
    L = cfg.n_layers

    # GEMM families.  A pair probe (fwd+dgrad shapes) and a transposed
    # pair for the wgrad character; per-family fwd+bwd cost = 1.5 pairs.
    fams = {
        "qkv_o": ((M, D, D), 4 * L),
        "mlp_up": ((M, D, F), 2 * L),
        "mlp_down": ((M, F, D), 1 * L),
        "lm_head": ((M, D, V), 1),
    }
    rows = {}
    total_t = 0.0
    total_fl = 0.0
    for name, ((m, k, n), count) in fams.items():
        dt_pair = time_gemm_pair(m, k, n)
        dt_wg = time_gemm_pair(k, m, n) if name != "lm_head" else dt_pair
        pair_fl = 2 * 2 * m * k * n
        # fwd + dgrad from the pair, wgrad as half the transposed pair
        fam_t = dt_pair + dt_wg / 2
        fam_fl = 3 * 2 * m * k * n
        rows[name] = {
            "shape": [m, k, n],
            "pair_tflops": round(pair_fl / dt_pair / 1e12, 1),
            "fwd_bwd_efficiency": round(fam_fl / (fam_t * peak), 3)}
        total_t += count * fam_t
        total_fl += count * fam_fl

    # flash attention fwd+bwd at the model's shapes (chained via q)
    from ray_tpu.ops.attention import attention
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal(
        (B, S, cfg.n_heads, cfg.head_dim)), jnp.bfloat16)
    k0, v0 = q0 + 0, q0 * jnp.bfloat16(0.5)

    def attn_loss(q, k, v):
        return attention(q, k, v, causal=True, impl="flash").astype(
            jnp.float32).sum()

    grad = jax.grad(attn_loss, argnums=(0, 1, 2))

    def make_attn(iters):
        @jax.jit
        def chain(q):
            def body(i, q):
                dq, _, _ = grad(q, k0, v0)
                return (q - dq * jnp.bfloat16(1e-3)).astype(jnp.bfloat16)
            out = jax.lax.fori_loop(0, iters, body, q)
            return out[0, 0, 0, 0].astype(jnp.float32)
        return chain

    dt_attn = _time_two_point(make_attn, q0, 8, 136)
    # causal ~0.5x of full; fwd(1x) + bwd(2.5x) of the fwd flops
    attn_fl = 4 * B * cfg.n_heads * S * S * cfg.head_dim * 0.5 * 3.5
    rows["flash_attn_fwd_bwd"] = {
        "ms": round(dt_attn * 1e3, 2),
        "tflops": round(attn_fl / dt_attn / 1e12, 1),
        "efficiency": round(attn_fl / dt_attn / peak, 3)}
    total_t += L * dt_attn
    total_fl += L * attn_fl

    # norm + rope elementwise chains as XLA actually compiles them
    from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies
    x0 = jnp.asarray(rng.standard_normal((B, S, D)), jnp.bfloat16)
    w = jnp.ones((D,), jnp.float32)
    cos, sin = rope_frequencies(cfg.head_dim, S, cfg.rope_theta)

    def make_norm(iters):
        return jax.jit(lambda x: jax.lax.fori_loop(
            0, iters, lambda i, x: rms_norm(x, w), x
            )[0, 0, 0].astype(jnp.float32))

    def make_rope(iters):
        return jax.jit(lambda q: jax.lax.fori_loop(
            0, iters, lambda i, q: apply_rope(q, cos, sin), q
            )[0, 0, 0, 0].astype(jnp.float32))

    dt_norm = _time_two_point(make_norm, x0, 8, 8200)
    dt_rope = _time_two_point(make_rope, q0, 8, 8200)
    rows["rms_norm"] = {"us": round(dt_norm * 1e6, 1),
                        "gbps": round(2 * x0.nbytes / dt_norm / 1e9, 1)}
    rows["rope"] = {"us": round(dt_rope * 1e6, 1),
                    "gbps": round(2 * q0.nbytes / dt_rope / 1e9, 1)}
    # per step: 2 norms + 2 ropes per layer + final norm; bwd ~2x traffic
    ew_t = L * (2 * dt_norm + 2 * dt_rope) * 3 + dt_norm * 3
    total_t += ew_t

    composed_mfu = total_fl / (total_t * peak)
    out = {
        "metric": "gpt_roofline",
        "preset": args.preset,
        "batch": B, "seq": S,
        "device": getattr(dev, "device_kind", dev.platform),
        "components": rows,
        "elementwise_share_pct": round(100 * ew_t / total_t, 1),
        "composed_kernel_time_ms": round(total_t * 1e3, 1),
        "composed_mfu_ceiling": round(composed_mfu, 4),
        "note": "ceiling composes MEASURED per-kernel efficiencies at "
                "the model's exact shapes with zero overhead between "
                "them; the bench.py step MFU can only approach this",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
