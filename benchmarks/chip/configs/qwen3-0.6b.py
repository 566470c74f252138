"""Qwen3-0.6B: the weights the benchmark makes, and the plain reference.

The reference follows the published architecture (Hugging Face
``Qwen3ForCausalLM``): token embedding; per layer RMSNorm, grouped-query
attention with RMSNorm on each query and key head before rotary embedding
(``rotate_half`` convention, ``inv_freq = theta ** -(2i / head_dim)``),
softmax over causal scores scaled by ``head_dim ** -0.5``, output
projection, residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm and the
output head.  It runs in float32 at ``highest`` matmul precision over the
whole sequence at once, with no cache and no batching, one layer at a time
inside a scan.  Departure from the published model: the head is untied (the
configuration's ``reduced``).

It imports nothing of the program.  The only thing it shares with the
program is the weights' layout, which is the program's interface: the
benchmark makes the weights here and hands them to both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dims(doc: dict) -> dict:
    return {"L": doc["num_hidden_layers"], "d": doc["hidden_size"],
            "H": doc["num_attention_heads"], "K": doc["num_key_value_heads"],
            "hd": doc["head_dim"], "ff": doc["intermediate_size"],
            "V": doc["vocab_size"]}


def _shapes(dm: dict) -> dict:
    """Each weight's shape and the axes it is normalised over (fan-in)."""
    L, d, H, K, hd, ff, V = (dm[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                             "V"))
    return {
        "embed": ((V, d), (1,)),
        "out": ((d, V), (0,)),
        "ln_f": ((d,), None),
        "layers/ln1": ((L, d), None),
        "layers/ln2": ((L, d), None),
        "layers/attn/wq": ((L, d, H, hd), (1,)),
        "layers/attn/wk": ((L, d, K, hd), (1,)),
        "layers/attn/wv": ((L, d, K, hd), (1,)),
        "layers/attn/wo": ((L, H, hd, d), (1, 2)),
        "layers/attn/q_scale": ((L, hd), None),
        "layers/attn/k_scale": ((L, hd), None),
        "layers/mlp/gate": ((L, d, ff), (1,)),
        "layers/mlp/up": ((L, d, ff), (1,)),
        "layers/mlp/down": ((L, ff, d), (1,)),
    }


# --------------------------------------------------------------------------
# the work the algorithm needs: operations and bytes
# --------------------------------------------------------------------------
#
# Only what the algorithm needs: attention over the positions a token may see
# (not a padded cache), the output head where the server computes it (the
# last prompt position at prefill, every decoded token), and nothing for idle
# lanes or recomputation.  Multiply-adds count as two operations.

SERVED_BYTES = 2          # bfloat16: the weights' and the KV cache's type


def _flop_sizes(doc: dict):
    dm = _dims(doc)
    L, d, H, K, hd, ff, V = (dm[k] for k in ("L", "d", "H", "K", "hd", "ff",
                                             "V"))
    per_token = 2 * L * (d * H * hd + 2 * d * K * hd + H * hd * d
                         + 3 * d * ff)
    return per_token, 2 * d * V, 2 * 2 * L * H * hd


def prefill_flops(doc: dict, prompt_len: int) -> float:
    """A prompt's prefill: every layer over every position, causal
    attention, the head at the last position."""
    per_token, head, attn = _flop_sizes(doc)
    n = prompt_len
    return float(n * per_token + attn * n * (n + 1) / 2 + head)


def decode_flops(doc: dict, position: int) -> float:
    """One decoded token at ``position`` (0-based), which attends to
    ``position + 1`` keys."""
    per_token, head, attn = _flop_sizes(doc)
    return float(per_token + attn * (position + 1) + head)


def decode_bytes(doc: dict, positions) -> int:
    """Bytes one decode execution must move for the lanes whose processed
    tokens sit at the 0-based ``positions``: every weight but the embedding
    table read once, the embedding rows of those tokens, and per lane
    ``position + 1`` rows of K and V in every layer (``position`` read, one
    written).  Idle lanes and cache rows past a lane's position are not
    counted."""
    dm = _dims(doc)
    params = sum(int(np.prod(shape)) for path, (shape, _)
                 in _shapes(dm).items() if path != "embed")
    kv_row = dm["L"] * 2 * dm["K"] * dm["hd"]
    rows = sum(int(p) + 1 for p in positions)
    return SERVED_BYTES * (params + len(positions) * dm["d"]
                           + rows * kv_row)


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


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate_half over the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _quant(x, axes):
    """Symmetric int8 rounding of ``x`` with one scale per slice over
    ``axes`` (kept in float32: values on the int8 grid times the scale)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _layer(doc, lp, x, pos, int8):
    """One decoder layer over the whole sequence ``x`` (S, d), float32.
    With ``int8`` every matmul runs on int8-rounded operands: weights per
    output channel, activations per token (the control)."""
    dm = _dims(doc)
    H, K, hd = dm["H"], dm["K"], dm["hd"]
    eps = doc["rms_norm_eps"]
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)

    def mm(a, w, spec, w_axes):
        if int8:
            a = _quant(a, (-1,))
            w = _quant(w, w_axes)
        return jnp.einsum(spec, a, w)

    h = _rms(x, lp["ln1"], eps)
    q = mm(h, lp["attn"]["wq"], "sd,dhk->shk", (0,))
    k = mm(h, lp["attn"]["wk"], "sd,dhk->shk", (0,))
    v = mm(h, lp["attn"]["wv"], "sd,dhk->shk", (0,))
    q = _rope(_rms(q, lp["attn"]["q_scale"], eps), pos, doc["rope_theta"])
    k = _rope(_rms(k, lp["attn"]["k_scale"], eps), pos, doc["rope_theta"])
    k = jnp.repeat(k, H // K, axis=1)           # query head h reads kv h//G
    v = jnp.repeat(v, H // K, axis=1)
    S = x.shape[0]
    scores = jnp.einsum("shk,thk->hst", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hst,thk->shk", probs, v)
    if int8:
        att = _quant(att.reshape(S, H * hd), (-1,)).reshape(S, H, hd)
        wo = _quant(lp["attn"]["wo"], (0, 1))
    else:
        wo = lp["attn"]["wo"]
    x = x + jnp.einsum("shk,hkd->sd", att, wo)
    h = _rms(x, lp["ln2"], eps)
    g = jax.nn.silu(mm(h, lp["mlp"]["gate"], "sd,df->sf", (0,)))
    u = mm(h, lp["mlp"]["up"], "sd,df->sf", (0,))
    return x + mm(g * u, lp["mlp"]["down"], "sf,fd->sd", (0,))


def _logits(doc, weights, tokens, int8):
    pos = jnp.arange(tokens.shape[0])
    embed = weights["embed"]
    x = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    if int8:
        row = jnp.take(jnp.max(jnp.abs(embed.astype(jnp.float32)), -1),
                       tokens) / 127.0
        row = jnp.where(row == 0, 1.0, row)[:, None]
        x = jnp.clip(jnp.round(x / row), -127, 127) * row

    def body(x, lp):
        return _layer(doc, lp, x, pos, int8), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rms(x, weights["ln_f"].astype(jnp.float32), doc["rms_norm_eps"])
    out = weights["out"].astype(jnp.float32)
    if int8:
        return jnp.einsum("sd,dv->sv", _quant(x, (-1,)), _quant(out, (0,)))
    return jnp.einsum("sd,dv->sv", x, out)


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
