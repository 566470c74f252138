"""chip_smoke.py runs only on a TPU, and the compile cache it and the CLI
entry points share lands where the deployment says."""

import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_tpu(capsys):
    assert jax.default_backend() == "cpu"
    assert _chip_smoke().main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "[serve]" not in out            # no phase ran


@pytest.fixture(scope="module")
def smoke_qwen():
    from repro.configs import smoke_config
    from repro.models.transformer import init_params
    cfg = smoke_config("qwen3-0.6b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve_check(smoke_qwen):
    cfg, params = smoke_qwen
    return _chip_smoke().serve_and_compare(
        cfg, params, seed=0, n_requests=4, prompt_len=8, gen=5,
        n_compared=2)


def test_serve_check_matches_every_step_of_the_direct_loop(smoke_qwen):
    """The serving phase's check at smoke size: the logits the engine
    sampled each compared request's tokens from, step by step, are the
    direct loop's (a request given another's logits would differ)."""
    fields = _serve_check(smoke_qwen)
    rows = json.loads(fields["compared"])
    assert [r["uid"] for r in rows] == ["req000", "req001"]
    assert [r["tokens_agreeing"] for r in rows] == [5, 5]
    assert all(r["max_diff"] <= 1e-5 and "diverge_step" not in r
               for r in rows)
    assert fields["requests"] == 4 and fields["gen_tokens"] == 20


def test_serve_check_fails_when_logits_disagree(smoke_qwen, monkeypatch):
    """Logits beyond 4 bf16 spacings of the reference fail the phase."""
    import repro.models.transformer as tf
    real = tf.greedy_reference

    def shifted(*args):
        tokens, logits = real(*args)
        return tokens, [x + 0.1 * np.max(np.abs(x)) * (np.arange(
            x.shape[-1]) % 2) for x in logits]
    monkeypatch.setattr(tf, "greedy_reference", shifted)
    mod = _chip_smoke()
    cfg, params = smoke_qwen
    with pytest.raises(mod.SmokeFailure, match="step 0 logits differ"):
        mod.serve_and_compare(cfg, params, seed=0, n_requests=2,
                              prompt_len=8, gen=3, n_compared=1)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # left to JAX


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
        assert enable_compile_cache() == REPO_CACHE_DIR   # same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(REPO_CACHE_DIR) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
