"""Flash attention forward kernel (Pallas TPU).

Blockwise streaming softmax: the (S x S) score matrix is never materialized
in HBM.  Grid is (B, H, nQ, nK) with the KV index innermost; the running
max / denominator / accumulator live in VMEM scratch across the nK sweep and
the output block is written on the last KV step.

Block sizes default to (128, 128): MXU-aligned (multiples of 128 on both
matmul dims) and small enough that q/k/v/acc tiles fit VMEM:
  bq*hd + bk*hd (bf16) + bq*bk + bq*hd (f32)  ~= 0.35 MB at hd=128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _precision(dtype):
    """Matmul precision for operands of ``dtype``.  The TPU's matrix unit
    rounds f32 operands to bf16 unless asked for full precision (a 1e-2
    error against the f32 reference on a v5e); bf16 operands are exact in
    its single pass."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # operands go to the matrix unit in their own dtype; products
    # accumulate, and the softmax runs, in f32
    q = q_ref[0, 0]                                # (bq, hd)
    k = k_ref[0, 0]                                # (bk, hd)
    v = v_ref[0, 0]                                # (bk, hd)
    prec = _precision(q.dtype)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1)
    acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, precision=prec,
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ki == n_k - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, scale: float | None
                        = None, block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """q, k, v: (B, H, S, hd) (k/v length may differ from q).  Returns
    (B, H, Sq, hd).  ``interpret`` runs the Pallas interpreter instead of
    compiling to Mosaic (see ``repro.kernels.interpret_mode``)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_q, n_k = Sq // block_q, Sk // block_k
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            # VMEM scratch persisting across the innermost (KV) grid dim
            pltpu.VMEM((block_q,), jnp.float32),       # running max
            pltpu.VMEM((block_q,), jnp.float32),       # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
