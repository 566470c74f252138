"""Public jit'd wrapper for the fused RMSNorm kernel."""

from functools import partial

import jax

from .. import interpret_mode
from .rmsnorm import rmsnorm_fwd


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: int = 128):
    """x: (..., d) -> fused rms-normalized x * scale."""
    shape = x.shape
    y = rmsnorm_fwd(x.reshape(-1, shape[-1]), scale, eps=eps,
                    block_rows=block_rows, interpret=interpret_mode())
    return y.reshape(shape)
