"""Public jit'd wrapper for the mamba selective-scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .mamba_scan import mamba_scan_fwd


@partial(jax.jit, static_argnames=("chunk",))
def mamba_scan(dt, x, A, B, C, *, chunk: int = 64):
    """Selective scan: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t; y = C.h."""
    return mamba_scan_fwd(dt, x, A, B, C, chunk=chunk,
                          interpret=interpret_mode())
