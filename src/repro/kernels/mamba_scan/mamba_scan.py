"""Mamba1 selective-scan kernel (Pallas TPU).

TPU adaptation of the CUDA selective-scan kernel: instead of one thread-block
per channel with warp shuffles, the sequence is tiled into chunks along the
grid's innermost dimension; the recurrent state h lives in VMEM scratch and
is carried across chunk steps.  The decay a = exp(dt*A) and drive dt*x*B are
computed IN the kernel, so the (B, L, D, N) tensors the naive jnp path
materializes never reach HBM — that is the kernel's memory win:

  HBM traffic: naive  ~ L*D*N*(reads+writes)   (the a/b tensors)
               kernel ~ L*(2D + 2N) in + L*D out (just the projections)

Grid: (B, D // block_d, n_chunks) with the chunk index innermost (sequential
on TPU), so the scratch state persists from chunk j to j+1.  The state is
kept as (N, block_d) — channels on the 128-wide lane axis, the small state
dimension on sublanes — and D is tiled so wide models (falcon-mamba-7b's
d_inner 8192) stay inside the scoped VMEM budget.  Each step reads row t of
the chunk with ``pl.ds`` from f32 VMEM copies (Mosaic has no dynamic slice
of a loaded value) and turns the (1, N) rows of B and C into (N, 1) columns
with a masked lane reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Widest channel tile: at chunk 128 the f32 dt/x/y tiles, their double
# buffers and the (N, block_d) state stay well under v5e's 16 MiB default
# scoped VMEM.
MAX_BLOCK_D = 2048


def _block_d(D: int) -> int:
    """The channel tile: all of D when it fits, else the widest multiple of
    128 that divides D and is at most ``MAX_BLOCK_D``."""
    if D <= MAX_BLOCK_D:
        return D
    for bd in range(MAX_BLOCK_D, 127, -128):
        if D % bd == 0:
            return bd
    raise ValueError(f"mamba_scan: no 128-multiple channel tile divides "
                     f"D={D}")


def _scan_kernel(dt_ref, x_ref, At_ref, B_ref, C_ref, y_ref,
                 h_scr, dt_scr, x_scr, b_scr, c_scr, y_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    dt_scr[...] = dt_ref[0].astype(jnp.float32)    # (Q, bd)
    x_scr[...] = x_ref[0].astype(jnp.float32)      # (Q, bd)
    b_scr[...] = B_ref[0].astype(jnp.float32)      # (Q, N)
    c_scr[...] = C_ref[0].astype(jnp.float32)      # (Q, N)
    At = At_ref[...].astype(jnp.float32)           # (N, bd)
    n = At.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
           ).astype(jnp.float32)

    def column(row):                                # (1, N) -> (N, 1)
        return jnp.sum(eye * row, axis=1, keepdims=True)

    def body(t, h):                                 # h: (N, bd)
        dt_t = dt_scr[pl.ds(t, 1), :]               # (1, bd)
        x_t = x_scr[pl.ds(t, 1), :]
        b_t = column(b_scr[pl.ds(t, 1), :])         # (N, 1)
        c_t = column(c_scr[pl.ds(t, 1), :])
        h = jnp.exp(dt_t * At) * h + b_t * (dt_t * x_t)   # never hits HBM
        y_scr[pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, body, h_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)


def mamba_scan_fwd(dt, x, A, B, C, *, chunk: int = 64, interpret: bool):
    """dt, x: (Bt, L, D); A: (D, N); B, C: (Bt, L, N) -> y (Bt, L, D).

    Computes h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y_t = C_t . h_t.
    ``interpret`` runs the Pallas interpreter instead of compiling to
    Mosaic (see ``repro.kernels.interpret_mode``)."""
    Bt, L, D = x.shape
    N = A.shape[1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    n_c = L // chunk
    bd = _block_d(D)

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(Bt, D // bd, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((N, bd), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((Bt, L, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32),       # state h
                        pltpu.VMEM((chunk, bd), jnp.float32),   # dt rows
                        pltpu.VMEM((chunk, bd), jnp.float32),   # x rows
                        pltpu.VMEM((chunk, N), jnp.float32),    # B rows
                        pltpu.VMEM((chunk, N), jnp.float32),    # C rows
                        pltpu.VMEM((chunk, bd), jnp.float32)],  # y rows
        interpret=interpret,
    )(dt, x, A.T, B, C)
