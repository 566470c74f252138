"""DeepSeek-V2-Lite at one chip's share: the weights the benchmark makes, the
work the architecture needs, and the plain reference.

The reference follows the published architecture (``modeling_deepseek.py``
of deepseek-ai/DeepSeek-V2-Lite: ``DeepseekV2Attention``,
``DeepseekV2YarnRotaryEmbedding``, ``MoEGate``, ``DeepseekV2MoE``): token
embedding; per layer RMSNorm, latent attention without q-LoRA (queries from
``wq``; keys and values from the normalised 512-value latent through
``wkv_b``; a 64-value rope key shared by the heads), YaRN rotary frequencies
and the softmax scale ``192 ** -0.5 * mscale(40, 0.707) ** 2``, output
projection, residual; RMSNorm and the FFN, residual; final RMSNorm and the
output head.  Layer 0's FFN is a dense SwiGLU; every later layer's is the
expert layer: softmax over all 64 routed experts, greedy top-6, the six
gates used as they are (``norm_topk_prob`` false; as in ``MoEGate``,
unrenormalised gates are multiplied by ``routed_scaling_factor``, 1 here),
plus the shared experts (one SwiGLU of twice the expert width).  It runs in
float32 at ``highest`` matmul precision over the whole sequence at once,
with no cache and no batching, one layer at a time inside a scan.

Departures from the published model, each the configuration's: the chip
holds 8 of the 64 routed experts (``deployment``), so the expert layer adds
only what those 8 give the tokens routed to them, and the reference runs
them densely under the gate mask; the rope columns of ``wq`` and ``wkv_a``
are taken as stored in the order the published code reaches by
de-interleaving them before ``rotate_half`` (the same model, its weights
permuted), so ``rotate_half`` applies to them directly.

It imports nothing of the program.  The only thing it shares with the
program is the weights' layout, which is the program's interface: the
benchmark makes the weights here and hands them to both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(doc: dict) -> dict:
    dep = doc["deployment"]
    return {"L": doc["num_hidden_layers"], "d": doc["hidden_size"],
            "H": doc["num_attention_heads"], "nope": doc["qk_nope_head_dim"],
            "rope": doc["qk_rope_head_dim"], "vd": doc["v_head_dim"],
            "r": doc["kv_lora_rank"], "ffd": doc["intermediate_size"],
            "ff": doc["moe_intermediate_size"], "G": doc["n_routed_experts"],
            "E": dep["published_n_routed_experts"],
            "block": dep["expert_block"], "k": doc["num_experts_per_tok"],
            "sh": doc["n_shared_experts"], "V": doc["vocab_size"],
            "dense": doc["first_k_dense_replace"]}


def _attn_shapes(n: int, dm: dict) -> dict:
    d, H, r = dm["d"], dm["H"], dm["r"]
    return {
        "attn/wq": ((n, d, H, dm["nope"] + dm["rope"]), (1,)),
        "attn/wkv_a": ((n, d, r + dm["rope"]), (1,)),
        "attn/kv_norm": ((n, r), None),
        "attn/wkv_b": ((n, r, H, dm["nope"] + dm["vd"]), (1,)),
        "attn/wo": ((n, H, dm["vd"], d), (1, 2)),
        "ln1": ((n, d), None),
        "ln2": ((n, d), None),
    }


def _shapes(dm: dict) -> dict:
    """Each weight's shape and the axes it is normalised over (fan-in), in
    the program's layout: ``dense_layers`` (layer 0) and ``layers`` (the
    expert layers)."""
    d, V, ffd, ff, G = dm["d"], dm["V"], dm["ffd"], dm["ff"], dm["G"]
    n0, n1 = dm["dense"], dm["L"] - dm["dense"]
    sff = dm["sh"] * ff
    out = {"embed": ((V, d), (1,)), "out": ((d, V), (0,)),
           "ln_f": ((d,), None)}
    for path, v in _attn_shapes(n0, dm).items():
        out["dense_layers/" + path] = v
    out.update({
        "dense_layers/mlp/gate": ((n0, d, ffd), (1,)),
        "dense_layers/mlp/up": ((n0, d, ffd), (1,)),
        "dense_layers/mlp/down": ((n0, ffd, d), (1,)),
    })
    for path, v in _attn_shapes(n1, dm).items():
        out["layers/" + path] = v
    out.update({
        "layers/moe/router": ((n1, d, dm["E"]), (1,)),
        "layers/moe/w_gate": ((n1, G, d, ff), (2,)),
        "layers/moe/w_up": ((n1, G, d, ff), (2,)),
        "layers/moe/w_down": ((n1, G, ff, d), (2,)),
        "layers/moe/sh_gate": ((n1, d, sff), (1,)),
        "layers/moe/sh_up": ((n1, d, sff), (1,)),
        "layers/moe/sh_down": ((n1, sff, d), (1,)),
    })
    return out


# --------------------------------------------------------------------------
# the work the algorithm needs: operations and bytes
# --------------------------------------------------------------------------
#
# Only what the algorithm needs: attention over the positions a token may see
# (not a padded cache), the output head where the server computes it (the
# last prompt position at prefill, every decoded token), the routed experts
# held here at the share of the top-6 that lands on them under uniform
# routing (6 x 8/64 expert evaluations a token), and nothing for idle lanes
# or recomputation.  Multiply-adds count as two operations.  Prefill counts
# the latent attention as published (keys and values expanded from the
# latent), decode as the program runs it (absorbed: attention in the
# latent).

SERVED_BYTES = 2          # bfloat16: the weights' and the latent cache's type


def _flop_sizes(doc: dict) -> dict:
    dm = _dims(doc)
    d, H, r, rope = dm["d"], dm["H"], dm["r"], dm["rope"]
    nope, vd = dm["nope"], dm["vd"]
    proj = d * H * (nope + rope) + d * (r + rope) + H * vd * d
    expert = 3 * d * dm["ff"]
    return {
        "attn_prefill": 2 * (proj + r * H * (nope + vd)),
        "attn_decode": 2 * (proj + H * nope * r + H * r * vd),
        "pair_prefill": 2 * H * (nope + rope + vd),
        "pair_decode": 2 * H * (2 * r + rope),
        "dense_ffn": 2 * 3 * d * dm["ffd"],
        "moe_ffn": 2 * (dm["sh"] * expert + d * dm["E"]
                        + dm["k"] * dm["G"] / dm["E"] * expert),
        "head": 2 * d * dm["V"],
        "n_dense": dm["dense"], "n_moe": dm["L"] - dm["dense"],
    }


def _ffn_flops(f: dict) -> float:
    return f["n_dense"] * f["dense_ffn"] + f["n_moe"] * f["moe_ffn"]


def prefill_flops(doc: dict, prompt_len: int) -> float:
    """A prompt's prefill: every layer over every position, causal
    attention, the head at the last position."""
    f = _flop_sizes(doc)
    n, L = prompt_len, f["n_dense"] + f["n_moe"]
    return float(n * (L * f["attn_prefill"] + _ffn_flops(f))
                 + L * f["pair_prefill"] * n * (n + 1) / 2 + f["head"])


def decode_flops(doc: dict, position: int) -> float:
    """One decoded token at ``position`` (0-based), which attends to
    ``position + 1`` latent rows."""
    f = _flop_sizes(doc)
    L = f["n_dense"] + f["n_moe"]
    return float(L * (f["attn_decode"] + f["pair_decode"] * (position + 1))
                 + _ffn_flops(f) + f["head"])


def decode_bytes(doc: dict, positions) -> float:
    """Bytes one decode execution must move for the lanes whose processed
    tokens sit at the 0-based ``positions``: every weight read once except
    the embedding table (the rows of those tokens) and the held routed
    experts, of which each expert layer reads the expected number that the
    ``n`` busy tokens reach under uniform routing, ``G (1 - ((E - k)/E)^n)``;
    and per lane ``position + 1`` latent rows (512 + 64 values) in every
    layer (``position`` read, one written).  Idle lanes and cache rows past
    a lane's position are not counted."""
    dm = _dims(doc)
    shapes = _shapes(dm)
    experts = {"layers/moe/w_gate", "layers/moe/w_up", "layers/moe/w_down"}
    params = sum(int(np.prod(shape)) for path, (shape, _) in shapes.items()
                 if path != "embed" and path not in experts)
    one_expert = 3 * dm["d"] * dm["ff"]
    n = len(positions)
    reached = dm["G"] * (1 - ((dm["E"] - dm["k"]) / dm["E"]) ** n)
    latent_row = dm["L"] * (dm["r"] + dm["rope"])
    rows = sum(int(p) + 1 for p in positions)
    return float(SERVED_BYTES * (
        params + n * dm["d"] + (dm["L"] - dm["dense"]) * reached * one_expert
        + rows * latent_row))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def make_weights(doc: dict, key, device):
    """Random weights in the program's layout and serving type (bfloat16),
    made on ``device`` in one jitted call from ``key``: matrices normal
    over the square root of their fan-in, norm scales ``1 + 0.1 normal``."""
    shapes = _shapes(_dims(doc))

    def init(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, (shape, fan_axes)) in zip(keys, sorted(shapes.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            if fan_axes is None:
                w = 1.0 + 0.1 * z
            else:
                w = z / np.sqrt(np.prod([shape[a] for a in fan_axes]))
            flat[path] = w.astype(jnp.bfloat16)
        return _nest(flat)

    out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=out)(key)


# --------------------------------------------------------------------------
# the plain reference
# --------------------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _yarn_mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn(doc: dict):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale) of
    ``DeepseekV2YarnRotaryEmbedding`` and ``DeepseekV2Attention``."""
    rs = doc["rope_scaling"]
    dim, base = doc["qk_rope_head_dim"], float(doc["rope_theta"])
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = inter * (1 - mask) + extra * mask
    cos_sin = (_yarn_mscale(factor, rs["mscale"])
               / _yarn_mscale(factor, rs["mscale_all_dim"]))
    m = _yarn_mscale(factor, rs["mscale_all_dim"])
    scale = (doc["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return inv_freq, cos_sin, scale


def _rope(x, pos, inv_freq, factor):
    """x: (S, heads, rope); rotate_half over the two halves."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq,
                                                         jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * factor
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _quant(x, axes):
    """Symmetric int8 rounding of ``x`` with one scale per slice over
    ``axes`` (kept in float32: values on the int8 grid times the scale)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(int8):
    """``einsum(spec, a, w)``; with ``int8`` on int8-rounded operands:
    activations per token (over ``a_axes``, the input axes), weights per
    output channel (over ``w_axes``)."""
    def mm(a, w, spec, w_axes, a_axes=(-1,)):
        if int8:
            a = _quant(a, a_axes)
            w = _quant(w, w_axes)
        return jnp.einsum(spec, a, w)
    return mm


def _attention(doc, ap, h, pos, int8):
    dm = _dims(doc)
    H, nope, r = dm["H"], dm["nope"], dm["r"]
    eps = doc["rms_norm_eps"]
    inv_freq, factor, scale = yarn(doc)
    mm = _mm(int8)
    S = h.shape[0]
    q = mm(h, ap["wq"], "sd,dhk->shk", (0,))
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, inv_freq, factor)
    kv = mm(h, ap["wkv_a"], "sd,dr->sr", (0,))
    c = _rms(kv[:, :r], ap["kv_norm"], eps)
    k_pe = _rope(kv[:, None, r:], pos, inv_freq, factor)        # (S, 1, rope)
    kvu = mm(c, ap["wkv_b"], "sr,rhk->shk", (0,))
    k_nope, v = kvu[..., :nope], kvu[..., nope:]
    scores = (jnp.einsum("shk,thk->hst", q_nope, k_nope)
              + jnp.einsum("shk,tk->hst", q_pe, k_pe[:, 0])) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    att = jnp.einsum("hst,thk->shk", probs, v)
    return mm(att, ap["wo"], "shk,hkd->sd", (0, 1), (-2, -1))


def _swiglu(h, gate, up, down, mm):
    g = jax.nn.silu(mm(h, gate, "sd,df->sf", (0,)))
    return mm(g * mm(h, up, "sd,df->sf", (0,)), down, "sf,fd->sd", (0,))


def _experts(doc, mp, h, int8):
    """The expert layer at this chip's share: the gate over every routed
    expert, and of the top-k the held experts' outputs (computed for every
    token, kept under the gate mask), plus the shared experts."""
    dm = _dims(doc)
    mm = _mm(int8)
    gates = jax.nn.softmax(mm(h, mp["router"], "sd,de->se", (0,)), -1)
    w, idx = jax.lax.top_k(gates, dm["k"])
    if doc["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    else:
        w = w * doc["routed_scaling_factor"]
    held = dm["block"] * dm["G"] + jnp.arange(dm["G"])
    combine = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                      1)                                        # (S, G)
    g = jax.nn.silu(mm(h, mp["w_gate"], "sd,edf->sef", (1,)))
    u = mm(h, mp["w_up"], "sd,edf->sef", (1,))
    y = jnp.einsum("sed,se->sd", mm(g * u, mp["w_down"], "sef,efd->sed",
                                    (1,)), combine)
    return y + _swiglu(h, mp["sh_gate"], mp["sh_up"], mp["sh_down"], mm)


def _layer(doc, lp, x, pos, int8, dense):
    eps = doc["rms_norm_eps"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    x = x + _attention(doc, lp["attn"], _rms(x, lp["ln1"], eps), pos, int8)
    h = _rms(x, lp["ln2"], eps)
    if dense:
        f = _swiglu(h, lp["mlp"]["gate"], lp["mlp"]["up"], lp["mlp"]["down"],
                    _mm(int8))
    else:
        f = _experts(doc, lp["moe"], h, int8)
    return x + f


def _logits(doc, weights, tokens, int8):
    pos = jnp.arange(tokens.shape[0])
    embed = weights["embed"]
    x = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    if int8:
        row = jnp.take(jnp.max(jnp.abs(embed.astype(jnp.float32)), -1),
                       tokens) / 127.0
        row = jnp.where(row == 0, 1.0, row)[:, None]
        x = jnp.clip(jnp.round(x / row), -127, 127) * row

    for name, dense in (("dense_layers", True), ("layers", False)):
        def body(x, lp, _dense=dense):
            return _layer(doc, lp, x, pos, int8, _dense), None
        x, _ = jax.lax.scan(body, x, weights[name])
    x = _rms(x, weights["ln_f"].astype(jnp.float32), doc["rms_norm_eps"])
    return _mm(int8)(x, weights["out"].astype(jnp.float32), "sd,dv->sv",
                     (0,))


def _gaps_fn(doc, control: bool):
    @jax.jit
    def gaps(weights, tokens, targets):
        with jax.default_matmul_precision("highest"):
            ref = _logits(doc, weights, tokens, False)
            if control:
                pick = jnp.argmax(_logits(doc, weights, tokens, True), -1)
            else:
                pick = jnp.maximum(targets, 0)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return jnp.where(targets >= 0, best - got, 0.0)
    return gaps


_GAPS: dict = {}


def token_gaps(doc: dict, weights, tokens: np.ndarray, targets: np.ndarray,
               control: bool = False) -> np.ndarray:
    """For each position ``p`` with ``targets[p] >= 0``: how far the
    reference's logit of the token served after position ``p`` lies below
    its best logit there.  With ``control`` the token is instead the one that
    the int8 computation puts first (the control, which needs no decode).
    ``tokens`` is the prompt and the served tokens, padded to a fixed length
    so that one compile serves every request; padding lies after every
    compared position, so causal attention never reads it."""
    key = (id(doc), control)
    if key not in _GAPS:
        _GAPS[key] = _gaps_fn(doc, control)
    out = _GAPS[key](weights, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(targets, jnp.int32))
    return np.asarray(out, np.float64)
