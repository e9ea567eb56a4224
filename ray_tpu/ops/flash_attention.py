"""Flash attention as a Pallas TPU kernel (forward + backward).

Blockwise online-softmax attention: O(S) memory, [block_q, block_k] tiles on
the MXU, fp32 accumulators in VMEM, causal block skipping via dynamic loop
bounds.  The reference framework has no attention kernel at all (its compute
lives in torch user code — SURVEY.md §2.6); this is the framework-native hot
op that Train/Serve model families build on.

On the CPU backend the same kernels run under ``interpret=True`` so unit
tests exercise the identical code path (SURVEY.md §4 device-simulation
strategy).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import backend_platform

_CompilerParams = pltpu.CompilerParams

NEG_INF = -1e30


def _interpret() -> bool:
    """Interpret mode only where the platform really is the CPU (unit
    tests).  Any other platform gets the compiled kernel, and a backend
    that fails to start raises instead of silently interpreting."""
    return backend_platform() == "cpu"


def _pick_block(seq: int, target: int) -> int:
    b = min(target, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


# --------------------------------------------------------------------------- #
# Forward                                                                     #
# --------------------------------------------------------------------------- #

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale: float, causal: bool, block_k: int):
    block_q = q_ref.shape[1]
    kv_len = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, d]

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)

    if causal:
        # blocks strictly above the diagonal contribute nothing
        num_kb = jnp.minimum(
            (qi * block_q + block_q + block_k - 1) // block_k,
            kv_len // block_k)
    else:
        num_kb = kv_len // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


def _fwd(q3, k3, v3, causal: bool, sm_scale: float,
         block_q: int, block_k: int, interpret: bool):
    bh, q_len, d = q3.shape
    kv_len = k3.shape[1]
    grid = (bh, q_len // block_q)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, kv_len, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, kv_len, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, q_len, 1), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return o, lse


# --------------------------------------------------------------------------- #
# Backward                                                                    #
# --------------------------------------------------------------------------- #

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   sm_scale: float, causal: bool, block_k: int):
    block_q = q_ref.shape[1]
    kv_len = k_ref.shape[1]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]

    if causal:
        num_kb = jnp.minimum(
            (qi * block_q + block_q + block_k - 1) // block_k,
            kv_len // block_k)
    else:
        num_kb = kv_len // block_k

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_kb, body, jnp.zeros((block_q, q_ref.shape[2]), jnp.float32))
    # q was pre-scaled; k inside the loop is unscaled, so dq is exact.
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *,
                    sm_scale: float, causal: bool, block_q: int):
    block_k = k_ref.shape[1]
    q_len = q_ref.shape[1]
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    num_qb = q_len // block_q
    start_qb = (ki * block_k) // block_q if causal else 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32) * sm_scale
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        # dk = sm_scale * ds^T @ q; q here is pre-scaled, so this is exact.
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk_new, dv_new

    d = k_ref.shape[2]
    dk, dv = jax.lax.fori_loop(
        start_qb, num_qb, body,
        (jnp.zeros((block_k, d), jnp.float32), jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q3, k3, v3, o3, lse, do3, causal: bool, sm_scale: float,
         block_q: int, block_k: int, interpret: bool):
    bh, q_len, d = q3.shape
    kv_len = k3.shape[1]
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)

    qspec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    full_q = pl.BlockSpec((1, q_len, d), lambda i, j: (i, 0, 0))
    full_kv = pl.BlockSpec((1, kv_len, d), lambda i, j: (i, 0, 0))
    vec_q = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    full_vec_q = pl.BlockSpec((1, q_len, 1), lambda i, j: (i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_k=block_k),
        grid=(bh, q_len // block_q),
        in_specs=[qspec, full_kv, full_kv, qspec, vec_q, vec_q],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)

    kspec = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q),
        grid=(bh, kv_len // block_k),
        in_specs=[full_q, kspec, kspec, full_q, full_vec_q, full_vec_q],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, kv_len, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, kv_len, d), v3.dtype)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# custom-vjp wrapper                                                          #
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _fwd(q3, k3, v3, causal, sm_scale, block_q, block_k, interpret)
    return o


def _flash_fwd(q3, k3, v3, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _fwd(q3, k3, v3, causal, sm_scale, block_q, block_k, interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, do3):
    q3, k3, v3, o3, lse = res
    return _bwd(q3, k3, v3, o3, lse, do3, causal, sm_scale,
                block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention on [B, S, H, D] / [B, Sk, H, D] inputs (heads equal;
    GQA expansion happens in ops.attention)."""
    b, q_len, h, d = q.shape
    kv_len = k.shape[1]
    if causal and q_len != kv_len:
        raise ValueError(
            "causal flash attention requires q_len == kv_len (got "
            f"{q_len} vs {kv_len}); use ops.attention with q_offset for "
            "decode-style queries")
    scale = sm_scale if sm_scale is not None else d ** -0.5
    bq = _pick_block(q_len, block_q)
    bk = _pick_block(kv_len, block_k)
    if interpret is None:
        interpret = _interpret()

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    o3 = _flash(to3(q), to3(k), to3(v), causal, scale, bq, bk, bool(interpret))
    return o3.reshape(b, h, q_len, d).transpose(0, 2, 1, 3)
