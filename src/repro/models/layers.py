"""Shared neural-net layers: norms, rotary embeddings, MLPs, initializers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(dt)


def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, hd, 2) / hd))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor ``0.1 m ln(s) + 1`` (1 for
    ``s <= 1``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * float(np.log(scale)) + 1.0


def yarn_freqs(hd: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN inverse frequencies (``DeepseekV2YarnRotaryEmbedding``): the
    plain ``theta ** -(2i/hd)`` below rotary index ``low``, the same over
    ``factor`` above ``high``, a linear ramp between.  ``low``/``high`` are
    the floor/ceil of ``hd ln(L / (2 pi b)) / (2 ln theta)`` for ``b`` =
    ``beta_fast``/``beta_slow`` and ``L`` the original context."""
    def dim(rot):
        return hd * np.log(original_max_pos / (rot * 2 * np.pi)) \
            / (2 * np.log(theta))
    low = max(int(np.floor(dim(beta_fast))), 0)
    high = min(int(np.ceil(dim(beta_slow))), hd - 1)
    extra = rope_freqs(hd, theta)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp                 # 1: the plain frequency, 0: over factor
    return extra / factor * (1 - keep) + extra * keep


def apply_rope(x, positions, theta: float = 1e4, freqs=None,
               scale: float = 1.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  ``freqs``
    (hd/2 inverse frequencies) replaces the plain ones, and ``scale``
    multiplies cos and sin (YaRN)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)
    freqs = jnp.asarray(freqs, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # explicit broadcast (no singleton head dim): keeps SPMD shardings of the
    # head axis intact instead of forcing a full rematerialization
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    cos = jnp.broadcast_to(cos[..., None, :], x1.shape)
    sin = jnp.broadcast_to(sin[..., None, :], x1.shape)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float = 1e4,
                sections=(0.25, 0.375, 0.375)):
    """M-RoPE (Qwen2-VL): rotary frequency channels split into temporal /
    height / width sections, each driven by its own position id.

    x: (B, S, H, hd); positions3: (B, S, 3)."""
    hd = x.shape[-1]
    half = hd // 2
    bounds = np.cumsum([int(half * s) for s in sections])
    bounds[-1] = half
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)     # (half,)
    sec = np.zeros(half, np.int32)
    sec[bounds[0]:bounds[1]] = 1
    sec[bounds[1]:] = 2
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                          # (B, S, 3)
        jnp.broadcast_to(jnp.asarray(sec)[None, None, :],
                         positions3.shape[:2] + (half,)), axis=-1)  # (B,S,half)
    ang = pos * freqs                                            # (B, S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU MLP: down( silu(x@gate) * (x@up) )."""
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, w_gate))
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", g * u, w_down)


def softmax_cross_entropy(logits, labels):
    """logits: (..., V) fp32-accumulated; labels: int (...,)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold
