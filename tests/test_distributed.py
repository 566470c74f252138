"""Multi-device behaviours, run in subprocesses so the main pytest process
keeps the default single-device view (smoke tests must see 1 device).

Each test pays a full subprocess JAX+XLA startup and multi-device compile
(~10 minutes for the module), so the whole module is tier-2 ``slow``: the
default run (pyproject ``addopts``) deselects it; run ``pytest -m slow``
(CI's non-blocking slow job) to include it."""

import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow


def _run(code: str, devices: int = 8):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, cwd=".", timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_moe_ep_a2a_matches_dense_oracle():
    _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.models.common import ModelConfig
        from repro.models.moe import init_moe, moe_dense, moe_ep_a2a
        cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                          n_experts=6, top_k=2, moe_d_ff=48,
                          n_shared_experts=1)
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32,
                     n_expert_shards=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        y_ref = moe_dense(p, cfg, x)
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("model",))
        fm = jax.shard_map(
            lambda xb, pp: moe_ep_a2a(pp, cfg, xb, capacity_factor=8.0),
            mesh=mesh,
            in_specs=(P("model"), {"router": P(), "w_gate": P("model"),
                                   "w_up": P("model"), "w_down": P("model"),
                                   "sh_gate": P(), "sh_up": P(),
                                   "sh_down": P()}),
            out_specs=P("model"), check_vma=False)
        y = fm(x.reshape(16, 32), p).reshape(2, 8, 32)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err < 1e-4, err
        print("ok", err)
    """, devices=4)


def test_moe_ep_a2a_decode_matches_dense_oracle():
    _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.models.common import ModelConfig
        from repro.models.moe import init_moe, moe_dense, moe_ep_a2a_decode
        cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                          n_experts=8, top_k=2, moe_d_ff=48,
                          n_shared_experts=1)
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32,
                     n_expert_shards=4)
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 32))
        y_ref = moe_dense(p, cfg, x[None])[0]
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("model",))
        pspecs = {"router": P(), "w_gate": P("model"), "w_up": P("model"),
                  "w_down": P("model"), "sh_gate": P(), "sh_up": P(),
                  "sh_down": P()}
        fm = jax.shard_map(
            lambda xb, pp: moe_ep_a2a_decode(pp, cfg, xb,
                                             capacity_factor=8.0),
            mesh=mesh, in_specs=(P(), pspecs), out_specs=P(),
            check_vma=False)
        err = float(jnp.max(jnp.abs(fm(x, p) - y_ref)))
        assert err < 1e-4, err
        print("ok", err)
    """, devices=4)


def test_moe_share_matches_dense_oracle():
    _run("""
        import jax, jax.numpy as jnp
        from repro.models.common import ModelConfig
        from repro.models.moe import init_moe, moe_dense, moe_share
        cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=16,
                          n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                          n_experts=5, top_k=2, moe_d_ff=24)
        p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16))
        err = float(jnp.max(jnp.abs(moe_share(p, cfg, x)[0]
                                    - moe_dense(p, cfg, x))))
        assert err < 1e-5, err
        print("ok")
    """, devices=1)


def test_sharded_train_step_matches_single_device():
    """The distributed train step must be numerically equivalent to the
    single-device step (same params, same batch)."""
    _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models.transformer import Dist, init_params
        from repro.optim.optimizers import sgd_momentum
        from repro.train.train_step import TrainState, make_train_step
        from repro.launch.shardings import param_specs, to_shardings
        cfg = smoke_config("qwen3-0.6b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = sgd_momentum(lr=0.1)
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        # single device
        s1 = TrainState(params, opt.init(params))
        step1 = jax.jit(make_train_step(cfg, opt))
        s1, m1 = step1(s1, batch)
        # 4x2 mesh
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dist = Dist(mesh=mesh)
        s2 = TrainState(params, opt.init(params))
        step2 = jax.jit(make_train_step(cfg, opt, dist))
        s2, m2 = step2(s2, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        w1 = jax.tree.leaves(s1["params"])[0]
        w2 = jax.tree.leaves(s2["params"])[0]
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-4)
        print("ok")
    """)


def test_compressed_dp_grads_close_to_exact():
    _run("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.models.transformer import Dist, init_params
        from repro.optim.optimizers import sgd_momentum
        from repro.train.train_step import TrainState, make_train_step
        cfg = smoke_config("qwen3-0.6b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = sgd_momentum(lr=0.05)
        batch = {"tokens": jnp.ones((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        mesh = jax.make_mesh((8, 1), ("data", "model"))
        dist = Dist(mesh=mesh, batch_axes=("data",), model_axis="model")
        exact = jax.jit(make_train_step(cfg, opt))
        comp = jax.jit(make_train_step(cfg, opt, dist, compress_grads=True))
        se = TrainState(params, opt.init(params))
        sc = TrainState(params, opt.init(params))
        se, me = exact(se, batch)
        sc, mc = comp(sc, batch)
        assert abs(float(me["loss"]) - float(mc["loss"])) < 1e-3
        we = jax.tree.leaves(se["params"])[-1]
        wc = jax.tree.leaves(sc["params"])[-1]
        rel = float(jnp.max(jnp.abs(we - wc)) / (jnp.max(jnp.abs(we)) + 1e-9))
        assert rel < 0.05, rel
        print("ok", rel)
    """)


def test_elastic_restore_across_meshes():
    """Checkpoint written from a 4x2 mesh restores onto 2x4 (elastic)."""
    _run("""
        import jax, numpy as np, jax.numpy as jnp, tempfile
        from repro.configs import smoke_config
        from repro.models.transformer import init_params
        from repro.launch.shardings import param_specs, to_shardings
        from repro.train.checkpoint import save_checkpoint, load_latest, restore_like
        cfg = smoke_config("qwen3-0.6b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sh_a = to_shardings(mesh_a, param_specs(params, mesh_a))
        pa = jax.device_put(params, sh_a)
        d = tempfile.mkdtemp()
        save_checkpoint(d, {"params": pa}, 5)
        step, flat = load_latest(d)
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        sh_b = to_shardings(mesh_b, param_specs(params, mesh_b))
        # template must mirror the saved pytree structure ({"params": ...})
        restored = restore_like({"params": jax.device_put(params, sh_b)},
                                flat)
        pb = restored["params"]
        w0a = np.asarray(jax.tree.leaves(pa)[0])
        w0b = np.asarray(jax.tree.leaves(pb)[0])
        np.testing.assert_array_equal(w0a, w0b)
        print("ok")
    """)


def test_hlo_analysis_calibration():
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        # exact matmul flops
        a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
        b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
        txt = jax.jit(lambda a, b: a @ b).lower(a, b).compile().as_text()
        c = analyze(txt, 1)
        assert c.flops == 2 * 256 * 512 * 128, c.flops
        # scan multiplies by trip count
        def g(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
        txt = jax.jit(g).lower(x, ws).compile().as_text()
        c = analyze(txt, 1)
        assert c.flops == 10 * 2 * 64**3, c.flops
        # psum wire bytes: ring all-reduce 2*(g-1)/g * payload
        mesh = jax.make_mesh((8,), ("d",))
        f = jax.shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                          in_specs=P("d"), out_specs=P(), check_vma=False)
        xs = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
        txt = jax.jit(f).lower(xs).compile().as_text()
        c = analyze(txt, 8)
        assert abs(c.collective_bytes["all_reduce"] - 2*(7/8)*4096) < 1, \\
            dict(c.collective_bytes)
        print("ok")
    """)


def test_production_mesh_shapes():
    _run("""
        from repro.launch.mesh import make_production_mesh, mesh_axes
        m = make_production_mesh()
        assert m.devices.shape == (16, 16) and m.axis_names == ("data", "model")
        mm = make_production_mesh(multi_pod=True)
        assert mm.devices.shape == (2, 16, 16)
        assert mm.axis_names == ("pod", "data", "model")
        dp, mdl = mesh_axes(mm)
        assert dp == ("pod", "data") and mdl == "model"
        print("ok")
    """, devices=512)
