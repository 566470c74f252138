"""Compile rehearsal for a TPU v5e that is described, not attached: each
Pallas kernel of the main path compiles to Mosaic at real widths (the
``model_width_shapes`` that ``chip_smoke.py`` also runs on the chip).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
under several pytest workers the one given this file is the one that loads
it.  All of these compiles stay in this one file for that reason.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.mamba_scan.mamba_scan import mamba_scan_fwd
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_fwd
from repro.kernels.workloads import model_width_shapes


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_rmsnorm_compiles_at_qwen3_width(one_chip):
    s = model_width_shapes()["rmsnorm"]                     # d_model 1024
    txt = _compiled_text(
        lambda x, sc: rmsnorm_fwd(x, sc, interpret=False), one_chip,
        ((s["rows"], s["d"]), jnp.bfloat16), ((s["d"],), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_at_qwen3_heads(one_chip):
    s = model_width_shapes()["flash_attention"]     # 16 heads x head_dim 128
    qkv = ((s["B"], s["H"], s["S"], s["hd"]), jnp.bfloat16)
    txt = _compiled_text(
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in txt


def test_mamba_scan_compiles_at_falcon_mamba_width(one_chip):
    s = model_width_shapes()["mamba_scan"]          # d_inner 8192, N 16
    seq = ((s["Bt"], s["L"], s["D"]), jnp.bfloat16)
    bc = ((s["Bt"], s["L"], s["N"]), jnp.bfloat16)
    txt = _compiled_text(
        lambda dt, x, A, B, C: mamba_scan_fwd(dt, x, A, B, C, chunk=64,
                                              interpret=False),
        one_chip, seq, seq, ((s["D"], s["N"]), jnp.float32), bc, bc)
    assert "tpu_custom_call" in txt
