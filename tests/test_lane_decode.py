"""Decode over a lane batch: each row at its own cache position, the lane
cache updated in place.

``decode_step`` with a (B,) index must give every row what a B=1 call at
that row's scalar index gives, for every family that shares the path
(dense, MoE, MLA, M-RoPE, hybrid, SSM).  The engine's compiled decode
program must write the donated lane cache in place: no copy of the whole
cache, each cache input aliased to its output."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.deploy.engine import _jitted
from repro.models.transformer import decode_step, init_cache, init_params

FAMILIES = ("qwen3-0.6b",            # dense
            "granite-moe-3b-a800m",  # MoE
            "deepseek-v3-671b",      # MLA
            "deepseek-v2-lite",      # MLA without q-LoRA, YaRN, dense lead
            "qwen2-vl-72b",          # M-RoPE
            "zamba2-1.2b",           # hybrid
            "falcon-mamba-7b")       # SSM


def _token_batch(cfg, tokens, index):
    pos = jnp.asarray(index, jnp.int32).reshape(-1, 1)
    tb = {"tokens": tokens, "positions": pos}
    if cfg.mrope:
        tb["positions3"] = jnp.broadcast_to(pos[..., None],
                                            pos.shape + (3,))
    return tb


@pytest.mark.parametrize("arch", FAMILIES)
def test_vector_index_matches_per_row_decode(arch):
    """Rows at positions 5, 0, 11 decoded together give the logits and
    caches of three B=1 decodes at those scalar indices."""
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    index = np.array([5, 0, 11], np.int32)
    B, T = len(index), 16
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    # a cache already holding history, so each row's mask is exercised
    caches = jax.tree.map(
        lambda x: jax.random.normal(next(keys), x.shape).astype(x.dtype),
        init_cache(cfg, B, T))
    tokens = jax.random.randint(next(keys), (B, 1), 0, cfg.vocab)
    step = jax.jit(partial(decode_step, cfg=cfg))

    logits, new = step(params, _token_batch(cfg, tokens, index), caches,
                       index)
    for b, i in enumerate(index):
        row = jax.tree.map(lambda x: x[:, b:b + 1], caches)
        want_logits, want = step(
            params, _token_batch(cfg, tokens[b:b + 1], [i]), row,
            jnp.int32(i))
        np.testing.assert_allclose(logits[b], want_logits[0],
                                   rtol=1e-5, atol=1e-5)
        for name in new:
            np.testing.assert_allclose(new[name][:, b], want[name][:, 0],
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def test_serve_decode_updates_the_lane_cache_in_place():
    """The compiled ``jit_serve_decode`` holds no copy of the lane cache's
    full shape and aliases each cache input to its output.  Decoding that
    returns the caches as scan outputs under a vmap over lanes (the layout
    before) compiles to three such copies here."""
    cfg = smoke_config("qwen3-0.6b").scaled(n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_lanes, max_len = 8, 32
    caches = init_cache(cfg, n_lanes, max_len)
    tb = {"tokens": np.zeros((n_lanes, 1), np.int32),
          "positions": np.zeros((n_lanes, 1), np.int32)}
    idx = np.arange(n_lanes, dtype=np.int32)
    _, dec = _jitted(cfg)
    text = dec.lower(params, tb, caches, idx).compile().as_text()

    full = "f32[%s]" % ",".join(map(str, caches["k"].shape))
    copies = re.findall(re.escape(full) + r"\{[^}]*\} copy\(", text)
    assert copies == []
    first = len(jax.tree.leaves(params)) + len(jax.tree.leaves(tb))
    aliases = re.search(r"input_output_alias=\{(.*?) \}", text).group(1)
    for j in range(len(caches)):        # outputs: logits, then the caches
        assert "{%d}: (%d, {}" % (1 + j, first + j) in aliases, aliases
