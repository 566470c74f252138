"""Public jit'd wrapper for the flash-attention kernel.

On the CPU backend the kernel body runs in the Pallas interpreter, for
validation; on any other backend it lowers to Mosaic.
"""

from __future__ import annotations

from functools import partial

import jax

from .. import interpret_mode
from .flash_attention import flash_attention_fwd


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q, k, v: (B, H, S, hd) -> (B, H, Sq, hd)."""
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())
