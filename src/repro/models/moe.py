"""Mixture-of-Experts blocks.

Three execution paths:

* :func:`moe_share` — the layer without a mesh, for prefill and decode alike:
  routes every token over all ``n_experts`` and computes the (token, expert)
  pairs whose expert this chip holds (``experts_held`` experts from
  ``expert_block * experts_held``; all of them by default).  A call of at
  most ``DENSE_MAX_ROWS`` rows (decode) computes every held expert for every
  row and zeroes the pairs not routed; a larger call (prefill) computes only
  the routed pairs, sorted by expert, with a dropless grouped product
  (``lax.ragged_dot``).  With every expert held it computes what
  :func:`moe_dense` computes.
* :func:`moe_dense` — reference implementation: every expert computes every
  token, combined with the top-k gate mask.  O(E) compute — the numerical
  oracle for the other two.
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

# The most rows for which moe_share computes every held expert densely.  That
# form does n FLOPs per byte of expert weight it reads (2n per bf16 value), so
# it stays bound by the weights' bytes while n is under the chip's ridge
# (~240 on a TPU v5e: 197 TFLOP/s over 819 GB/s); 128 leaves margin.  Beyond
# it the grouped product computes only the routed pairs.
DENSE_MAX_ROWS = 128


def computes_densely(n_rows: int) -> bool:
    """Whether :func:`moe_share` computes every held expert for a call of
    ``n_rows`` rows (``B * S``), rather than the routed pairs alone."""
    return n_rows <= DENSE_MAX_ROWS


def expert_pad(cfg: ModelConfig, n_shards: int = 1) -> int:
    e = cfg.n_experts
    return int(-(-e // n_shards) * n_shards)


def init_moe(key, cfg: ModelConfig, dtype, n_expert_shards: int = 1) -> dict:
    d, ff = cfg.d_model, cfg.moe_d_ff
    ep = (cfg.experts_held if cfg.experts_held
          else expert_pad(cfg, n_expert_shards))
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


def _route(x2, router, cfg: ModelConfig):
    """x2: (n, d) -> (weights (n,k), indices (n,k)): softmax over every
    expert, greedy top-k, the k gates renormalised to sum 1 only with
    ``norm_topk_prob``."""
    logits = jnp.einsum("nd,de->ne", x2.astype(jnp.float32), router)
    gates = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk_prob:
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
    x2 = x.reshape(-1, d)
    w, idx = _route(x2, p["router"], cfg)
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


def moe_share(p, cfg: ModelConfig, x):
    """The chip's share of an expert layer.  x: (B, S, d).

    Every token is routed over all ``n_experts``; of its ``top_k`` pairs,
    those whose expert is held here (``p["w_gate"]`` holds experts
    ``expert_block * G`` to ``expert_block * G + G - 1``, ``G`` its length)
    are computed, whatever the load, and return to their tokens weighted by
    the gate; the shared experts are added once.  A token's output depends on
    its own row alone.  The form depends on the static row count
    ``n = B * S``:

    * ``n <= DENSE_MAX_ROWS`` (decode): one product per projection computes
      every held expert for every row, and an (n, G) matrix of the gates
      (0 where a row did not route to that expert) combines them.  The
      products read the stacked weights in place and are bound by their
      bytes (``DENSE_MAX_ROWS`` says why).
    * otherwise (prefill): the pairs are sorted by expert and computed by
      one dropless grouped product (``lax.ragged_dot``) per projection,
      which does only the routed pairs' FLOPs.

    Returns ``(y, n_here)``: the output and, per row of ``x``, the number of
    its pairs routed to held experts ((B,) int32)."""
    B, S, d = x.shape
    x2 = x.reshape(-1, d)
    n, k = x2.shape[0], cfg.top_k
    G = p["w_gate"].shape[0]
    w, idx = _route(x2, p["router"], cfg)
    local = idx - cfg.expert_block * G                          # (n, k)
    here = (local >= 0) & (local < G)
    gate = jnp.where(here, w, 0)
    if computes_densely(n):
        y = _held_dense(p, x2, local, gate)
    else:
        y = _held_grouped(p, x2, local, here, gate)
    y = y + _shared(p, x2)
    return y.reshape(B, S, d), jnp.sum(here.reshape(B, S * k), -1,
                                       dtype=jnp.int32)


def _held_dense(p, x2, local, gate):
    """Every held expert on every row of x2 (n, d), combined by ``gate``
    scattered to the rows' local expert indices (``local`` (n, k))."""
    G = p["w_gate"].shape[0]
    combine = jnp.einsum("nk,nke->ne", gate,
                         jax.nn.one_hot(local, G, dtype=gate.dtype))
    g = jax.nn.silu(jnp.einsum("nd,edf->enf", x2, p["w_gate"]))
    u = jnp.einsum("nd,edf->enf", x2, p["w_up"])
    ye = jnp.einsum("enf,efd->end", g * u, p["w_down"])
    return jnp.einsum("end,ne->nd", ye, combine)


def _held_grouped(p, x2, local, here, gate):
    """The routed pairs of x2 (n, d) held here, sorted by expert, through
    one grouped product per projection, returned to their rows in top-k
    order and weighted by ``gate`` (0 for pairs not held)."""
    n, k = local.shape
    G = p["w_gate"].shape[0]
    group = jnp.where(here, local, G).reshape(-1)  # pairs not held sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(group, G, dtype=jnp.int32), axis=0)
    xs = x2[order // k]                                         # (n*k, d)
    g = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes))
    u = jax.lax.ragged_dot(xs, p["w_up"], sizes)
    ys = jax.lax.ragged_dot(g * u, p["w_down"], sizes)          # rows past
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    ys = ys[back].reshape(n, k, -1)                             # sizes: 0
    return jnp.einsum("nk,nkd->nd", gate.astype(ys.dtype), ys)


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

    w, idx = _route(x, p["router"], cfg)
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
