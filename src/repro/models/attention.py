"""Attention blocks: GQA (bias / qk-norm / RoPE / M-RoPE variants) and MLA
(DeepSeek multi-head latent attention, with compressed-cache absorbed decode).

All functions are pure and global-semantics (einsum + lax); under pjit the
GSPMD partitioner inserts the collectives implied by the shardings chosen in
``launch/shardings.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import ModelConfig
from .layers import (apply_mrope, apply_rope, dense_init, rms_norm,
                     yarn_freqs, yarn_mscale)

NEG_INF = -1e30


def _shard(x, dist, *axes):
    """Activation sharding constraint (no-op without a mesh)."""
    if dist is None or not getattr(dist, "active", False):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*axes) if len(axes) == x.ndim else P()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dist.mesh, spec))


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attn(key, cfg: ModelConfig, dtype) -> dict:
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 8)
    if cfg.mla:
        r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        if r_q:
            q = {"wq_a": dense_init(ks[0], (d, r_q), dtype=dtype),
                 "q_norm": jnp.ones((r_q,), dtype),
                 "wq_b": dense_init(ks[1], (r_q, H, nope + rope),
                                    dtype=dtype)}
        else:
            q = {"wq": dense_init(ks[0], (d, H, nope + rope), in_axis=0,
                                  dtype=dtype)}
        return {
            **q,
            "wkv_a": dense_init(ks[2], (d, r_kv + rope), dtype=dtype),
            "kv_norm": jnp.ones((r_kv,), dtype),
            "wkv_b": dense_init(ks[3], (r_kv, H, nope + vdim), dtype=dtype),
            "wo": dense_init(ks[4], (H, vdim, d), in_axis=0, dtype=dtype),
        }
    p = {
        "wq": dense_init(ks[0], (d, H, hd), in_axis=0, dtype=dtype),
        "wk": dense_init(ks[1], (d, K, hd), in_axis=0, dtype=dtype),
        "wv": dense_init(ks[2], (d, K, hd), in_axis=0, dtype=dtype),
        "wo": dense_init(ks[3], (H, hd, d), in_axis=0, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((K, hd), dtype)
        p["bv"] = jnp.zeros((K, hd), dtype)
    if cfg.qk_norm:
        p["q_scale"] = jnp.ones((hd,), dtype)
        p["k_scale"] = jnp.ones((hd,), dtype)
    return p


# --------------------------------------------------------------------------
# core attention math
# --------------------------------------------------------------------------

def _sdpa(q, k, v, mask, scale):
    """q:(B,S,H,hd) k/v:(B,T,K,*) grouped-query attention with fp32 softmax."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    if G == 1:  # MHA fast path: no grouped reshape (SPMD-friendly)
        logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32) * scale
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthk->bshk", probs, v)
    q = q.reshape(B, S, K, G, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, -1)


def blockwise_sdpa(q, k, v, *, causal: bool, scale: float,
                   block_q: int = 512, block_k: int = 512):
    """Flash-style blockwise attention in pure JAX (XLA-level analogue of
    kernels/flash_attention): O(S·block) live memory instead of the O(S^2)
    score matrix.  q, k, v: (B, S, H, hd) MHA (KV already head-expanded)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    vd = v.shape[-1]                                     # may differ (MLA)
    bq = min(block_q, S)
    bk = min(block_k, T)
    while S % bq:
        bq //= 2
    while T % bk:
        bk //= 2
    nq, nk = S // bq, T // bk
    qb = q.reshape(B, nq, bq, H, hd).swapaxes(0, 1)     # (nq, B, bq, H, hd)
    kb = k.reshape(B, nk, bk, H, hd).swapaxes(0, 1)
    vb = v.reshape(B, nk, bk, H, vd).swapaxes(0, 1)

    def q_step(_, qx):
        qi, qblk = qx

        def kv_step(carry, kx):
            ki, kblk, vblk = kx
            m, l, acc = carry
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk,
                           kblk).astype(jnp.float32) * scale
            if causal:
                qpos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = ki * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, H, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, bq), jnp.float32),
                jnp.zeros((B, H, bq, vd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B, H, bq, hd)
        return None, out.swapaxes(1, 2)                  # (B, bq, H, hd)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    return outs.swapaxes(0, 1).reshape(B, S, H, vd).astype(q.dtype)


def causal_mask(S: int, T: int, offset: int = 0):
    """(1, S, T) True where query i may attend key j (j <= i + offset)."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (S, T), 0) + offset
    kj = jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
    return (kj <= qi)[None]


# --------------------------------------------------------------------------
# GQA forward (train / prefill / decode)
# --------------------------------------------------------------------------

def _project_qkv(p, cfg: ModelConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_scale"], cfg.norm_eps)
        k = rms_norm(k, p["k_scale"], cfg.norm_eps)
    if cfg.mrope:
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    elif cfg.causal:  # encoder-only hubert uses no rotary (conv pos emb stub)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p, cfg: ModelConfig, x, positions, dist=None):
    """Full-sequence attention (training / prefill). Returns (y, kv).

    KV heads are expanded to the full head count (Megatron-style KV
    replication) so the score einsum is plain MHA, and activations carry
    explicit sharding constraints (batch over DP, heads over TP) — without
    them GSPMD falls back to fully replicated attention (observed on the
    16x16 dry-run)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    G = cfg.n_heads // cfg.n_kv_heads
    ke = jnp.repeat(k, G, axis=2) if G > 1 else k
    ve = jnp.repeat(v, G, axis=2) if G > 1 else v
    if dist is not None and getattr(dist, "active", False):
        dp, mdl = dist.batch_axes, dist.model_axis
        q = _shard(q, dist, dp, None, mdl, None)
        ke = _shard(ke, dist, dp, None, mdl, None)
        ve = _shard(ve, dist, dp, None, mdl, None)
    S = x.shape[1]
    scale = 1.0 / np.sqrt(cfg.hd)
    if cfg.attn_impl == "blockwise":
        out = blockwise_sdpa(q, ke, ve, causal=cfg.causal, scale=scale,
                             block_q=cfg.attn_block, block_k=cfg.attn_block)
    else:
        mask = causal_mask(S, S) if cfg.causal else jnp.ones((1, S, S), bool)
        logits = jnp.einsum("bshk,bthk->bhst", q, ke).astype(jnp.float32)
        logits = logits * scale
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(ve.dtype)
        out = jnp.einsum("bhst,bthk->bshk", probs, ve)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (k, v)


def _write_token(cache, layer, index, new):
    """Write one token's rows into a stacked (L, B, T, ...) cache in place:
    ``new[b, 0]`` lands at ``(layer, b, index[b])``.  Only B rows move, so
    a donated cache carried through the layer scan is never copied."""
    rows = jnp.arange(cache.shape[1])
    return cache.at[layer, rows, index].set(new[:, 0].astype(cache.dtype),
                                            unique_indices=True)


def _decode_mask(index, T):
    """(B, 1, T): row b attends positions <= index[b]."""
    kj = jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    return kj <= index[:, None, None]


def gqa_decode(p, cfg: ModelConfig, x, cache_k, cache_v, layer, index,
               positions):
    """One-token decode against layer ``layer`` of stacked (L, B, S_max, K,
    hd) KV caches.

    ``index`` (B,) is each row's current length; the new token's K/V are
    written at ``index[b]`` and row b attends positions <= index[b].
    Returns ``(y, (cache_k, cache_v))`` with the whole stacks updated."""
    q, k, v = _project_qkv(p, cfg, x, positions)           # S == 1
    cache_k = _write_token(cache_k, layer, index, k)
    cache_v = _write_token(cache_v, layer, index, v)
    mask = _decode_mask(index, cache_k.shape[2])
    out = _sdpa(q, cache_k[layer], cache_v[layer], mask,
                1.0 / np.sqrt(cfg.hd))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (cache_k, cache_v)


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2/V3)
# --------------------------------------------------------------------------
#
# Rotary layout: the published model de-interleaves the rope columns of q and
# k before ``rotate_half``.  Here those columns of ``wq``/``wq_b`` and
# ``wkv_a`` are taken as already permuted, so ``apply_rope`` applies
# ``rotate_half`` to them directly: the same model, its weights' rope
# columns stored in the permuted order.


def _mla_rope(cfg: ModelConfig, x, positions):
    """Rotary embedding of MLA's rope part (..., S, heads, rope); YaRN's
    frequencies and cos/sin factor where ``cfg.yarn_factor`` is set."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    f = cfg.yarn_factor
    freqs = yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, f,
                       cfg.yarn_original_max_pos)
    scale = yarn_mscale(f, cfg.yarn_mscale) / yarn_mscale(
        f, cfg.yarn_mscale_all_dim)
    return apply_rope(x, positions, cfg.rope_theta, freqs, scale)


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``(nope + rope) ** -0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim) ** 2`` where YaRN is on."""
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_query(p, cfg: ModelConfig, x, positions):
    """(q_nope, q_rope) of every head: through the q-LoRA (``wq_a``,
    ``q_norm``, ``wq_b``) or, with ``q_lora_rank`` 0, straight from
    ``wq``."""
    nope = cfg.qk_nope_dim
    if cfg.q_lora_rank:
        q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    return q[..., :nope], _mla_rope(cfg, q[..., nope:], positions)


def _mla_latent(p, cfg: ModelConfig, x, positions):
    """The cached latent: normalised ``c_kv`` (B, S, r_kv) and the rotated
    shared ``k_rope`` (B, S, rope)."""
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope = kv[..., :cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    return c_kv, _mla_rope(cfg, k_rope[..., None, :], positions)[..., 0, :]


def mla_forward(p, cfg: ModelConfig, x, positions, dist=None):
    """Full-sequence MLA. Returns (y, (c_kv, k_rope)) — the compressed cache."""
    with jax.named_scope("mla"):
        nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
        q_nope, q_rope = _mla_query(p, cfg, x, positions)
        c_kv, k_rope = _mla_latent(p, cfg, x, positions)
        kvu = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"])
        k_nope, v = kvu[..., :nope], kvu[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[..., None, :],
                                      k_nope.shape[:-1] + (rope,))], -1)
        qk = jnp.concatenate([q_nope, q_rope], -1)
        if dist is not None and getattr(dist, "active", False):
            dp, mdl = dist.batch_axes, dist.model_axis
            qk = _shard(qk, dist, dp, None, mdl, None)
            k = _shard(k, dist, dp, None, mdl, None)
            v = _shard(v, dist, dp, None, mdl, None)

        S = x.shape[1]
        scale = mla_scale(cfg)
        if cfg.attn_impl == "blockwise":
            out = blockwise_sdpa(qk, k, v, causal=True, scale=scale,
                                 block_q=cfg.attn_block,
                                 block_k=cfg.attn_block)
        else:
            out = _sdpa(qk, k, v, causal_mask(S, S), scale)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (c_kv, k_rope)


def mla_decode(p, cfg: ModelConfig, x, cache_ckv, cache_krope, layer,
               index, positions):
    """Absorbed-weight MLA decode: attention runs in the compressed
    kv_lora space, so the cache is (B, S, r_kv) + (B, S, rope) only, here
    layer ``layer`` of stacks with a leading layer dim (``index`` as in
    :func:`gqa_decode`)."""
    with jax.named_scope("mla"):
        nope = cfg.qk_nope_dim
        q_nope, q_rope = _mla_query(p, cfg, x, positions)
        # absorb the k_nope projection into the query:
        # q' = q_nope @ W_kv_b[:, :, :nope]^T
        q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, p["wkv_b"][..., :nope])
        c_kv, k_rope = _mla_latent(p, cfg, x, positions)
        cache_ckv = _write_token(cache_ckv, layer, index, c_kv)
        cache_krope = _write_token(cache_krope, layer, index, k_rope)
        ckv = cache_ckv[layer]
        logits = (jnp.einsum("bshr,btr->bhst", q_abs, ckv)
                  + jnp.einsum("bshk,btk->bhst", q_rope, cache_krope[layer]))
        logits = logits.astype(jnp.float32) * mla_scale(cfg)
        mask = _decode_mask(index, ckv.shape[1])
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, -1).astype(x.dtype)
        ctx = jnp.einsum("bhst,btr->bshr", probs, ckv)
        # un-absorb the value projection
        out = jnp.einsum("bshr,rhk->bshk", ctx, p["wkv_b"][..., nope:])
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, (cache_ckv, cache_krope)
