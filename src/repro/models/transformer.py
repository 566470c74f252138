"""Top-level model: init / train_loss / prefill / decode_step for all
assigned architecture families.

Layers are stacked (leading L dim) and driven by ``lax.scan`` so the lowered
HLO stays compact for 61-80-layer models.  Families:

  dense / vlm / encoder : attention + SwiGLU MLP
  moe / mla_moe         : attention (GQA or MLA) + MoE FFN, after
                          ``first_k_dense_replace`` leading layers whose FFN
                          is a dense SwiGLU (``params["dense_layers"]``)
  ssm                   : mamba1 blocks (attention-free)
  hybrid                : mamba2 backbone + ONE weight-shared attention+MLP
                          block applied every ``attn_every`` layers (zamba2)

Distribution is carried by ``Dist`` (mesh + axis names); everything else is
global-semantics einsum, partitioned by GSPMD according to the shardings in
``launch/shardings.py``.  The MoE FFN is shard_map expert-parallel a2a on a
mesh with ``moe_mode="ep_a2a"``, and otherwise the chip-share layer
(``moe.moe_share``), which serves prefill and decode alike.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .attention import (gqa_decode, gqa_forward, init_attn, mla_decode,
                        mla_forward)
from .common import ModelConfig
from .layers import dense_init, rms_norm, softmax_cross_entropy, swiglu
from .mamba import (init_mamba, mamba1_decode, mamba1_seq, mamba2_decode,
                    mamba2_seq)
from .moe import init_moe, moe_ep_a2a, moe_ep_a2a_decode, moe_share


@dataclass(frozen=True)
class Dist:
    """Distribution context threaded through the model."""
    mesh: Any = None
    batch_axes: tuple = ("data",)
    model_axis: str = "model"

    @property
    def active(self) -> bool:
        return self.mesh is not None


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_mlp(key, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": dense_init(k1, (d, ff), dtype=dtype),
            "up": dense_init(k2, (d, ff), dtype=dtype),
            "down": dense_init(k3, (ff, d), dtype=dtype)}


def _init_layer(key, cfg: ModelConfig, dtype, dense: bool = False):
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {"ln": jnp.ones((cfg.d_model,), dtype),
                "mamba": init_mamba(ks[0], cfg, dtype)}
    if cfg.family == "hybrid":
        return {"ln": jnp.ones((cfg.d_model,), dtype),
                "mamba": init_mamba(ks[0], cfg, dtype)}
    p = {"ln1": jnp.ones((cfg.d_model,), dtype),
         "ln2": jnp.ones((cfg.d_model,), dtype),
         "attn": init_attn(ks[0], cfg, dtype)}
    if cfg.n_experts and not dense:
        p["moe"] = init_moe(ks[1], cfg, dtype,
                            n_expert_shards=cfg.expert_shards)
    else:
        p["mlp"] = _init_mlp(ks[1], cfg, dtype)
    return p


def init_params(cfg: ModelConfig, key=None, dtype=None) -> dict:
    key = jax.random.PRNGKey(0) if key is None else key
    dtype = dtype or _dtype(cfg)
    k_emb, k_lay, k_out, k_sh = jax.random.split(key, 4)
    n_dense = cfg.first_k_dense_replace
    keys = jax.random.split(k_lay, cfg.n_layers)
    params = {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), in_axis=-1,
                            dtype=dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
        "out": dense_init(k_out, (cfg.d_model, cfg.vocab), dtype=dtype),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg, dtype))(
            keys[n_dense:]),
    }
    if n_dense:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, dense=True))(keys[:n_dense])
    if cfg.family == "hybrid":  # one weight-shared attention + MLP block
        ka, km = jax.random.split(k_sh)
        params["shared"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": init_attn(ka, cfg, dtype),
            "mlp": _init_mlp(km, cfg, dtype),
        }
    return params


# --------------------------------------------------------------------------
# FFN dispatch
# --------------------------------------------------------------------------

def _moe_apply(p, cfg: ModelConfig, x, dist: Dist, decoding: bool):
    """The MoE FFN and, per row of ``x``, the number of (token, expert)
    pairs it routed to experts held here ((B,) int32; on the mesh every
    expert is held)."""
    n_pairs = jnp.full(x.shape[:1], x.shape[1] * cfg.top_k, jnp.int32)
    if decoding:
        if cfg.moe_mode == "ep_a2a" and dist.active:
            # EP decode: tokens striped over the expert axis, a2a dispatch;
            # moves O(tokens*d) on the wire instead of O(topk*d*ff) weight
            # gathers per token (1000x on the 671B decode cell, §Perf)
            pspec = {"router": P(), "w_gate": P(dist.model_axis),
                     "w_up": P(dist.model_axis), "w_down": P(dist.model_axis)}
            if "sh_gate" in p:
                pspec.update({"sh_gate": P(), "sh_up": P(), "sh_down": P()})

            def local_dec(xb, pp):  # xb: (B_loc, 1, d), replicated on model
                bl, sl, d = xb.shape
                y = moe_ep_a2a_decode(pp, cfg, xb.reshape(bl * sl, d),
                                      expert_axis=dist.model_axis)
                return y.reshape(bl, sl, d)

            fn = jax.shard_map(
                local_dec, mesh=dist.mesh,
                in_specs=(P(dist.batch_axes, None, None), pspec),
                out_specs=P(dist.batch_axes, None, None), check_vma=False)
            return fn(x, p), n_pairs
    if cfg.moe_mode == "ep_a2a" and dist.active:
        pspec = {"router": P(), "w_gate": P(dist.model_axis),
                 "w_up": P(dist.model_axis), "w_down": P(dist.model_axis)}
        if "sh_gate" in p:
            pspec.update({"sh_gate": P(), "sh_up": P(), "sh_down": P()})
        def local_moe(xb, pp):  # xb: (B_loc, S_loc, d) block
            bl, sl, d = xb.shape
            y = moe_ep_a2a(pp, cfg, xb.reshape(bl * sl, d),
                           expert_axis=dist.model_axis)
            return y.reshape(bl, sl, d)

        fn = jax.shard_map(
            local_moe, mesh=dist.mesh,
            in_specs=(P(dist.batch_axes, dist.model_axis, None), pspec),
            out_specs=P(dist.batch_axes, dist.model_axis, None),
            check_vma=False)
        return fn(x, p), n_pairs
    with jax.named_scope("moe"):
        return moe_share(p, cfg, x)


# --------------------------------------------------------------------------
# layer bodies
# --------------------------------------------------------------------------

def _attn_layer_fwd(lp, cfg, x, positions, dist, decoding=False,
                    cache=None, index=None, layer=None):
    """One attention-family layer.  Returns ``(x, new_cache, n_here)``:
    ``n_here`` counts the pairs its MoE FFN routed to experts held here
    (None for a dense FFN)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla:
        if decoding:
            a, new_cache = mla_decode(lp["attn"], cfg, h, cache[0], cache[1],
                                      layer, index, positions)
        else:
            a, new_cache = mla_forward(lp["attn"], cfg, h, positions,
                                       dist=dist)
    else:
        if decoding:
            a, new_cache = gqa_decode(lp["attn"], cfg, h, cache[0], cache[1],
                                      layer, index, positions)
        else:
            a, new_cache = gqa_forward(lp["attn"], cfg, h, positions,
                                       dist=dist)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    n_here = None
    if "moe" in lp:
        f, n_here = _moe_apply(lp["moe"], cfg, h, dist, decoding)
    else:
        f = swiglu(h, lp["mlp"]["gate"], lp["mlp"]["up"], lp["mlp"]["down"])
    return x + f, new_cache, n_here


def _mamba_layer_fwd(lp, cfg, x, decoding=False, cache=None):
    from .mamba import mamba2_seq_naive
    if cfg.ssm_version == 1:
        seq = mamba1_seq
    else:
        seq = mamba2_seq if cfg.ssm_impl == "ssd" else mamba2_seq_naive
    dec = mamba1_decode if cfg.ssm_version == 1 else mamba2_decode
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    if decoding:
        y, new_cache = dec(lp["mamba"], cfg, h, cache[0], cache[1])
    else:
        y, new_cache = seq(lp["mamba"], cfg, h)
    return x + y, new_cache


# --------------------------------------------------------------------------
# cache construction
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> dict:
    """Per-layer decode caches, stacked with a leading layer dim."""
    dtype = dtype or _dtype(cfg)
    L = cfg.n_layers
    if cfg.family == "ssm" or cfg.family == "hybrid":
        di, n = cfg.d_inner, cfg.ssm_state
        if cfg.ssm_version == 1:
            h = jnp.zeros((L, batch, di, n), jnp.float32)
        else:
            H = cfg.ssm_heads or di // 64
            h = jnp.zeros((L, batch, H, di // H, n), jnp.float32)
        cache = {"conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, di), dtype),
                 "ssm": h}
        if cfg.family == "hybrid":
            G = cfg.n_layers // cfg.attn_every
            cache["shared_k"] = jnp.zeros(
                (G, batch, max_len, cfg.n_kv_heads, cfg.hd), dtype)
            cache["shared_v"] = jnp.zeros(
                (G, batch, max_len, cfg.n_kv_heads, cfg.hd), dtype)
        return cache
    if cfg.mla:
        return {"ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((L, batch, max_len, cfg.qk_rope_dim), dtype)}
    return {"k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd), dtype)}


# --------------------------------------------------------------------------
# full stack
# --------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, batch: dict):
    if cfg.embedding_inputs:
        x = batch["embeds"].astype(_dtype(cfg))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    B, S = x.shape[:2]
    if cfg.mrope:
        positions = batch["positions3"]          # (B, S, 3)
    else:
        positions = batch.get(
            "positions",
            jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)))
    return x, positions


def _stack_attn(params, cfg, x, positions, dist, decoding=False,
                caches=None, index=None):
    """scan over stacked attention-family layers: the leading dense layers
    (``params["dense_layers"]``), if any, then ``params["layers"]``.  The
    caches stay one stack over every layer, in layer order.  Decoding
    carries the whole stacked caches through the scans, and each layer
    writes its new K/V rows into them in place: the caches are never
    rebuilt as scan outputs, so a donated cache is updated without a copy.
    Returns ``(x, caches, n_here)``, ``n_here`` the (MoE layers, B) counts
    of each row's pairs routed to held experts, or None without MoE
    layers."""
    names = ("ckv", "krope") if cfg.mla else ("k", "v")
    stacks = [(params["dense_layers"], 0)] if "dense_layers" in params \
        else []
    stacks.append((params["layers"], cfg.first_k_dense_replace))
    if decoding:
        def dec_body(carry, xs):
            h, c0, c1 = carry
            lp, layer = xs
            h, (c0, c1), n_here = _attn_layer_fwd(
                lp, cfg, h, positions, dist, True, (c0, c1), index, layer)
            return (h, c0, c1), n_here

        c0, c1 = caches[names[0]], caches[names[1]]
        for layers, first in stacks:
            n = jax.tree.leaves(layers)[0].shape[0]
            (x, c0, c1), n_here = lax.scan(
                dec_body, (x, c0, c1), (layers, first + jnp.arange(n)))
        return x, {names[0]: c0, names[1]: c1}, n_here

    def body(carry, lp):
        h, c, n_here = _attn_layer_fwd(lp, cfg, carry, positions, dist)
        return h, (c, n_here)

    fn = jax.checkpoint(body) if cfg.remat == "full" else body
    out = []
    for layers, _ in stacks:
        x, (c, n_here) = lax.scan(fn, x, layers)
        out.append(c)
    nc0, nc1 = (jnp.concatenate([c[i] for c in out]) if len(out) > 1
                else out[0][i] for i in (0, 1))
    return x, {names[0]: nc0, names[1]: nc1}, n_here


def _stack_ssm(params, cfg, x, dist, decoding=False, caches=None):
    def body(carry, xs):
        if decoding:
            lp, conv, h = xs
            out, (nconv, nh) = _mamba_layer_fwd(lp, cfg, carry, True,
                                                (conv, h))
        else:
            lp = xs
            out, (nconv, nh) = _mamba_layer_fwd(lp, cfg, carry, False)
        return out, (nconv, nh)

    fn = jax.checkpoint(body) if cfg.remat == "full" and not decoding else body
    if decoding:
        xs = (params["layers"], caches["conv"], caches["ssm"])
    else:
        xs = params["layers"]
    x, (nconv, nh) = lax.scan(fn, x, xs)
    return x, {"conv": nconv, "ssm": nh}


def _stack_hybrid(params, cfg, x, positions, dist, decoding=False,
                  caches=None, index=None):
    """zamba2: groups of ``attn_every`` mamba layers + shared attn block.
    Leftover layers (n_layers % attn_every) run as a trailing mamba-only
    scan with no shared-block invocation."""
    k = cfg.attn_every
    G = cfg.n_layers // k
    rem = cfg.n_layers - G * k
    shared = params["shared"]

    def regroup(t):
        return t[:G * k].reshape((G, k) + t.shape[1:])

    def tail(t):
        return t[G * k:]

    layers_g = jax.tree.map(regroup, params["layers"])

    def group_body(carry, xs):
        if decoding:  # shared K/V stacks ride in the carry (in-place writes)
            h, sk, sv = carry
            lp_g, conv_g, ssm_g, g = xs
        else:
            h, lp_g = carry, xs

        def inner(c, ixs):
            if decoding:
                lp, conv, ssm = ixs
                out, ncache = _mamba_layer_fwd(lp, cfg, c, True, (conv, ssm))
            else:
                lp = ixs
                out, ncache = _mamba_layer_fwd(lp, cfg, c, False)
            return out, ncache

        if decoding:
            h, (nconv, nssm) = lax.scan(inner, h, (lp_g, conv_g, ssm_g))
        else:
            h, (nconv, nssm) = lax.scan(inner, h, lp_g)
        # weight-shared attention + MLP block
        hh = rms_norm(h, shared["ln1"], cfg.norm_eps)
        if decoding:
            a, (sk, sv) = gqa_decode(shared["attn"], cfg, hh, sk, sv, g,
                                     index, positions)
        else:
            a, (sk, sv) = gqa_forward(shared["attn"], cfg, hh, positions,
                                      dist=dist)
        h = h + a
        hh = rms_norm(h, shared["ln2"], cfg.norm_eps)
        h = h + swiglu(hh, shared["mlp"]["gate"], shared["mlp"]["up"],
                       shared["mlp"]["down"])
        if decoding:
            return (h, sk, sv), (nconv, nssm)
        return h, (nconv, nssm, sk, sv)

    fn = (jax.checkpoint(group_body)
          if cfg.remat == "full" and not decoding else group_body)
    if decoding:
        xs = (layers_g, regroup(caches["conv"]), regroup(caches["ssm"]),
              jnp.arange(G))
        (x, nsk, nsv), (nconv, nssm) = lax.scan(
            fn, (x, caches["shared_k"], caches["shared_v"]), xs)
    else:
        x, (nconv, nssm, nsk, nsv) = lax.scan(fn, x, layers_g)
    nconv = nconv.reshape((G * k,) + nconv.shape[2:])
    nssm = nssm.reshape((G * k,) + nssm.shape[2:])
    if rem:  # trailing mamba-only layers
        def tail_body(carry, ixs):
            if decoding:
                lp, conv, ssm = ixs
                out, nc = _mamba_layer_fwd(lp, cfg, carry, True, (conv, ssm))
            else:
                lp = ixs
                out, nc = _mamba_layer_fwd(lp, cfg, carry, False)
            return out, nc

        tl = jax.tree.map(tail, params["layers"])
        if decoding:
            txs = (tl, tail(caches["conv"]), tail(caches["ssm"]))
        else:
            txs = tl
        x, (tconv, tssm) = lax.scan(tail_body, x, txs)
        nconv = jnp.concatenate([nconv, tconv], axis=0)
        nssm = jnp.concatenate([nssm, tssm], axis=0)
    out_caches = {"conv": nconv, "ssm": nssm,
                  "shared_k": nsk, "shared_v": nsv}
    return x, out_caches


def _forward(params, cfg: ModelConfig, batch: dict, dist: Dist,
             decoding=False, caches=None, index=None):
    """Returns (final hidden states (B, S, d), new caches, the (MoE
    layers, B) counts of each row's pairs routed to held experts or
    None)."""
    x, positions = _embed(params, cfg, batch)
    if decoding:  # one cache length per row: a scalar serves every row
        index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), x.shape[:1])
    n_here = None
    if cfg.family == "ssm":
        x, new_caches = _stack_ssm(params, cfg, x, dist, decoding, caches)
    elif cfg.family == "hybrid":
        x, new_caches = _stack_hybrid(params, cfg, x, positions, dist,
                                      decoding, caches, index)
    else:
        x, new_caches, n_here = _stack_attn(params, cfg, x, positions, dist,
                                            decoding, caches, index)
    return rms_norm(x, params["ln_f"], cfg.norm_eps), new_caches, n_here


def _head(params, h):
    return jnp.einsum("...d,dv->...v", h, params["out"])


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def train_loss(params, batch: dict, cfg: ModelConfig,
               dist: Dist = Dist()) -> jax.Array:
    """Mean next-token (or frame-label for encoders) cross-entropy.

    With ``cfg.loss_chunk`` the vocabulary head + xent run per sequence
    chunk inside a scan, so the (B, S, V) logits tensor (the dominant
    training memory term for 150k-vocab models) never materializes."""
    h, _, _ = _forward(params, cfg, batch, dist)
    labels = batch["labels"]
    if cfg.loss_chunk and h.shape[1] % cfg.loss_chunk == 0 \
            and h.shape[1] > cfg.loss_chunk:
        B, S, d = h.shape
        nc = S // cfg.loss_chunk
        hc = h.reshape(B, nc, cfg.loss_chunk, d).swapaxes(0, 1)
        lc = labels.reshape(B, nc, cfg.loss_chunk).swapaxes(0, 1)

        def body(acc, xs):
            hx, lx = xs
            losses = softmax_cross_entropy(_head(params, hx), lx)
            return acc + jnp.sum(losses), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
        return total / (B * S)
    return jnp.mean(softmax_cross_entropy(_head(params, h), labels))


def prefill(params, batch: dict, cfg: ModelConfig, dist: Dist = Dist()):
    """Full-sequence forward; returns (last-position logits, caches of
    length S for continuation).  The vocab head runs on the LAST position
    only — serving never needs the (B, S, V) logits."""
    h, caches, _ = _forward(params, cfg, batch, dist)
    return _head(params, h[:, -1]), caches


def decode_step(params, token_batch: dict, caches: dict, index,
                cfg: ModelConfig, dist: Dist = Dist()):
    """One decode step.  ``token_batch`` holds (B, 1) tokens (or (B,1,d)
    embeds) plus positions; ``index`` is the current cache length, a
    scalar shared by every row or a (B,) vector of each row's own.  Each
    row's new K/V are written at its index into ``caches`` in place, so a
    donated cache is updated without a copy."""
    h, new_caches, _ = _forward(params, cfg, token_batch, dist,
                                decoding=True, caches=caches, index=index)
    return _head(params, h[:, -1]), new_caches


def greedy_reference(params, prompt, gen: int, cfg: ModelConfig):
    """The serving oracle: prefill one prompt (B=1), then ``gen - 1``
    lockstep decode steps, greedy.  Returns ``(tokens, logits)`` with the
    logits of every step.  It shares no code with ``core.deploy``'s
    engine, which tests and the chip smoke compare against it."""
    P = len(prompt)
    logits, pre = prefill(params, {"tokens": jnp.asarray(prompt)[None, :]},
                          cfg)
    caches = jax.tree.map(
        lambda full, x: full.at[:, :, :P].set(x) if full.shape != x.shape
        else x, init_cache(cfg, 1, P + gen), pre)
    steps = [logits[0]]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    tokens = [int(tok[0])]
    for t in range(gen - 1):
        tb = {"tokens": tok[:, None],
              "positions": jnp.full((1, 1), P + t, jnp.int32)}
        logits, caches = decode_step(params, tb, caches, jnp.int32(P + t),
                                     cfg)
        steps.append(logits[0])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        tokens.append(int(tok[0]))
    return tokens, steps
