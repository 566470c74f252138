"""Mixture-of-Experts blocks.

Two execution modes:

* ``dense``  — reference implementation: every expert computes every token,
  combined with the top-k gate mask.  O(E) compute — used at smoke scale and
  as the numerical oracle for the EP path.
* ``ep_a2a`` — TPU expert parallelism: experts sharded over the ``model``
  mesh axis, tokens dispatched with capacity-C buffers through a pair of
  ``all_to_all`` collectives inside ``shard_map`` (DeepSeek-style EP).  This
  is the mode the multi-pod dry-run lowers.

Experts whose count does not divide the mesh (granite's 40 experts on a
16-way axis) are zero-padded to ``expert_pad``; padded router columns are
masked to -inf so they are never selected.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import ModelConfig
from .layers import dense_init, swiglu

NEG_INF = -1e30


def expert_pad(cfg: ModelConfig, n_shards: int = 1) -> int:
    e = cfg.n_experts
    return int(-(-e // n_shards) * n_shards)


def init_moe(key, cfg: ModelConfig, dtype, n_expert_shards: int = 1) -> dict:
    d, ff = cfg.d_model, cfg.moe_d_ff
    ep = expert_pad(cfg, n_expert_shards)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (ep, d, ff), in_axis=1, dtype=dtype),
        "w_up": dense_init(ks[2], (ep, d, ff), in_axis=1, dtype=dtype),
        "w_down": dense_init(ks[3], (ep, ff, d), in_axis=1, dtype=dtype),
    }
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["sh_gate"] = dense_init(ks2[0], (d, sff), dtype=dtype)
        p["sh_up"] = dense_init(ks2[1], (d, sff), dtype=dtype)
        p["sh_down"] = dense_init(ks2[2], (sff, d), dtype=dtype)
    return p


def _route(x2, router, n_experts, top_k):
    """x2: (n, d) -> (weights (n,k), indices (n,k)) with normalized gates."""
    logits = jnp.einsum("nd,de->ne", x2.astype(jnp.float32), router)
    gates = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-9)
    return w.astype(x2.dtype), idx


def _shared(p, x):
    if "sh_gate" not in p:
        return 0.0
    return swiglu(x, p["sh_gate"], p["sh_up"], p["sh_down"])


# --------------------------------------------------------------------------
# dense reference
# --------------------------------------------------------------------------

def moe_dense(p, cfg: ModelConfig, x):
    """x: (B, S, d).  Computes all experts (reference / smoke scale)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    x2 = x.reshape(-1, d)
    w, idx = _route(x2, p["router"], E, k)
    onehot = jax.nn.one_hot(idx, p["w_gate"].shape[0], dtype=x.dtype)
    combine = jnp.einsum("nk,nke->ne", w, onehot)                # (n, E_pad)
    g = jax.nn.silu(jnp.einsum("nd,edf->enf", x2, p["w_gate"]))
    u = jnp.einsum("nd,edf->enf", x2, p["w_up"])
    ye = jnp.einsum("enf,efd->end", g * u, p["w_down"])
    y = jnp.einsum("end,ne->nd", ye, combine)
    y = y + _shared(p, x2)
    return y.reshape(B, S, d)


def moe_ep_a2a_decode(p, cfg: ModelConfig, x, *, expert_axis: str = "model",
                      capacity_factor: float = 2.0):
    """Decode-path expert parallelism, for use INSIDE shard_map where ``x``
    (n_loc, d) is REPLICATED across the expert axis (decode batches are too
    small to shard over data x model).

    Each expert-axis rank takes the token stripe ``j % m == rank``,
    dispatches it through the usual capacity-C all_to_all, and a final psum
    over the expert axis reassembles the batch.  Wire bytes per step are
    O(tokens * d) instead of the O(top_k * d * ff) per token that weight
    gathering costs — 3 orders of magnitude on the 671B decode cell
    (EXPERIMENTS.md §Perf)."""
    n, d = x.shape
    m = jax.lax.axis_size(expert_axis)
    rank = jax.lax.axis_index(expert_axis)
    mine = (jnp.arange(n) % m) == rank
    y = moe_ep_a2a(p, cfg, x, expert_axis=expert_axis,
                   capacity_factor=capacity_factor, valid=mine)
    y = jnp.where(mine[:, None], y, 0.0)
    return jax.lax.psum(y, expert_axis)


def moe_gather(p, cfg: ModelConfig, x):
    """Decode-path MoE: gather the k selected experts' weights per token.

    For small token counts (one decode step) this moves k*d*ff weight bytes
    per token instead of dispatching tokens — the right trade at batch sizes
    far below the expert count.  x: (B, S, d) with tiny B*S."""
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    w, idx = _route(x2, p["router"], cfg.n_experts, cfg.top_k)
    wg = jnp.take(p["w_gate"], idx, axis=0)                  # (n, k, d, ff)
    wu = jnp.take(p["w_up"], idx, axis=0)
    wd = jnp.take(p["w_down"], idx, axis=0)
    g = jax.nn.silu(jnp.einsum("nd,nkdf->nkf", x2, wg))
    u = jnp.einsum("nd,nkdf->nkf", x2, wu)
    y = jnp.einsum("nkf,nkfd->nd", (g * u) * w[..., None], wd)
    y = y + _shared(p, x2)
    return y.reshape(B, S, d)


# --------------------------------------------------------------------------
# expert-parallel all_to_all (shard_map)
# --------------------------------------------------------------------------

def _dispatch_local(x2, w, idx, e_pad, capacity, valid=None):
    """Build the (E_pad, C, d) dispatch buffer + combine metadata."""
    n, d = x2.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                                    # (n*k,)
    flat_w = w.reshape(-1)
    tok = jnp.repeat(jnp.arange(n), k)
    onehot = jax.nn.one_hot(flat_e, e_pad, dtype=jnp.int32)     # (n*k, E)
    if valid is not None:  # invalid tokens neither claim nor consume slots
        onehot = onehot * valid[tok].astype(jnp.int32)[:, None]
    pos = jnp.cumsum(onehot, axis=0) - 1                        # (n*k, E)
    pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]
    keep = pos_in_e < capacity
    if valid is not None:
        keep = keep & valid[tok]
    pos_in_e = jnp.where(keep, pos_in_e, 0)
    src = jnp.where(keep[:, None], x2[tok], 0.0)
    buf = jnp.zeros((e_pad, capacity, d), x2.dtype)
    buf = buf.at[flat_e, pos_in_e].add(src)
    return buf, (flat_e, pos_in_e, keep, flat_w, tok)


def _combine_local(buf, meta, n, d):
    flat_e, pos_in_e, keep, flat_w, tok = meta
    gathered = buf[flat_e, pos_in_e]                            # (n*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0) * flat_w[:, None]
    y = jnp.zeros((n, d), buf.dtype).at[tok].add(gathered)
    return y


def moe_ep_a2a(p, cfg: ModelConfig, x, *, expert_axis: str = "model",
               capacity_factor: float = 1.25, valid=None):
    """Expert-parallel MoE for use INSIDE shard_map over ``expert_axis``.

    ``x``: (n_local, d) tokens already local to this shard.  Expert weights
    arrive sharded: (E_pad/M, d, ff) blocks.  Router is replicated."""
    n, d = x.shape
    m = jax.lax.axis_size(expert_axis)
    e_local = p["w_gate"].shape[0]
    e_pad = e_local * m
    k = cfg.top_k
    cap = int(np.ceil(n * k / e_pad * capacity_factor / 8.0) * 8)

    w, idx = _route(x, p["router"], cfg.n_experts, k)
    buf, meta = _dispatch_local(x, w, idx, e_pad, cap, valid)   # (E_pad, C, d)
    # send expert-slices to their owners; receive my experts' tokens from all
    # peers.  tiled a2a: rows [j*e_loc:(j+1)*e_loc] -> peer j; received chunks
    # stack along the token axis, so the reverse a2a is the exact inverse.
    recv = jax.lax.all_to_all(buf, expert_axis, split_axis=0, concat_axis=1,
                              tiled=True)                        # (E_loc, mC, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, p["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", recv, p["w_up"])
    ye = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"])          # (E_loc, mC, d)
    back = jax.lax.all_to_all(ye, expert_axis, split_axis=1, concat_axis=0,
                              tiled=True)                        # (E_pad, C, d)
    y = _combine_local(back, meta, n, d)
    return y + _shared(p, x)
