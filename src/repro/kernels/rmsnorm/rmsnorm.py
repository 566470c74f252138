"""Fused RMSNorm kernel (Pallas TPU).

One pass over the rows: mean-of-squares, rsqrt, scale — fused so the
normalized intermediate never round-trips to HBM.  Grid tiles rows; each
block holds (block_rows, d) in VMEM (d up to ~8k fits comfortably:
256 rows x 8192 x 4 B = 8 MB < 16 MB VMEM at block_rows=256... default 128)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (rows, d)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_fwd(x, scale, *, eps: float = 1e-6, block_rows: int = 128,
                interpret: bool):
    """x: (rows, d); scale: (d,).  ``interpret`` runs the Pallas
    interpreter instead of compiling to Mosaic (see
    ``repro.kernels.interpret_mode``)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, scale)
