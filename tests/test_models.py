"""Per-arch smoke tests (reduced configs, 1 fwd/train step on CPU) plus
decode-vs-prefill consistency.

~2 min of XLA compiles across the whole arch zoo, so the module is tier-2
``slow`` (deselected by the default addopts; CI's non-blocking slow job and
``pytest -m slow`` run it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.configs import ARCHS, SHAPES, get_config, runnable_cells, smoke_config
from repro.models.transformer import (Dist, decode_step, init_cache,
                                      init_params, prefill, train_loss)

B, S = 2, 12


def _batch(cfg, train=True, seed=1):
    rng = jax.random.PRNGKey(seed)
    if cfg.embedding_inputs:
        b = {"embeds": jax.random.normal(rng, (B, S, cfg.d_model))}
    else:
        b = {"tokens": jnp.ones((B, S), jnp.int32)}
    if train:
        b["labels"] = jnp.zeros((B, S), jnp.int32)
    if cfg.mrope:
        b["positions3"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3))
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    loss, grads = jax.value_and_grad(
        lambda p: train_loss(p, _batch(cfg), cfg))(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(g)) for g in leaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves)


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_output_shape(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    logits, caches = prefill(params, _batch(cfg, train=False), cfg)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).family != "encoder"])
def test_arch_smoke_decode_step(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    caches = init_cache(cfg, B, 16)
    tb = {"positions": jnp.zeros((B, 1), jnp.int32)}
    if cfg.embedding_inputs:
        tb["embeds"] = jnp.zeros((B, 1, cfg.d_model))
    else:
        tb["tokens"] = jnp.zeros((B, 1), jnp.int32)
    if cfg.mrope:
        tb["positions3"] = jnp.zeros((B, 1, 3), jnp.int32)
    logits, new_caches = decode_step(params, tb, caches, jnp.int32(0), cfg)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert jax.tree.structure(new_caches) == jax.tree.structure(caches)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "falcon-mamba-7b",
                                  "zamba2-1.2b", "deepseek-v3-671b",
                                  "deepseek-v2-lite"])
def test_decode_matches_prefill(arch):
    """Token-by-token decode must reproduce the full-sequence logits —
    validates KV/MLA/SSM caches and (for zamba2) the shared-block caches."""
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    T = 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab)
    batch = {"tokens": toks}
    if cfg.mrope:
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :, None], (B, T, 3))
    last_logits, _ = prefill(params, batch, cfg)

    caches = init_cache(cfg, B, T)
    for t in range(T):
        tb = {"tokens": toks[:, t:t + 1],
              "positions": jnp.full((B, 1), t, jnp.int32)}
        if cfg.mrope:
            tb["positions3"] = jnp.full((B, 1, 3), t, jnp.int32)
        logits, caches = decode_step(params, tb, caches, jnp.int32(t), cfg)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(last_logits, np.float32),
                               atol=2e-3)


def test_runnable_cells_skip_rules():
    cells = runnable_cells()
    assert ("hubert-xlarge", "decode_32k") not in cells
    assert ("hubert-xlarge", "long_500k") not in cells
    assert ("qwen1.5-4b", "long_500k") not in cells
    assert ("zamba2-1.2b", "long_500k") in cells
    assert ("falcon-mamba-7b", "long_500k") in cells
    assert len(cells) == 34


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_numbers(arch):
    """Exact assigned numbers survive in the full configs."""
    cfg = get_config(arch)
    expected = {
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 49155),
        "deepseek-v3-671b": (61, 7168, 128, 128, 129280),
        "qwen2-vl-72b": (80, 8192, 64, 8, 152064),
        "zamba2-1.2b": (38, 2048, 32, 32, 32000),
        "minicpm-2b": (40, 2304, 36, 36, 122753),
        "qwen1.5-4b": (40, 2560, 20, 20, 151936),
        "qwen1.5-32b": (64, 5120, 40, 40, 152064),
        "qwen3-0.6b": (28, 1024, 16, 8, 151936),
        "falcon-mamba-7b": (64, 4096, 0, 0, 65024),
        "hubert-xlarge": (48, 1280, 16, 16, 504),
        "deepseek-v2-lite": (27, 2048, 16, 16, 102400),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab) == expected
