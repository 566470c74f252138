"""Training launcher: any assigned arch (reduced or full config), any mesh,
with checkpoint/resume, async saves, and the synthetic sharded data pipeline.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt --ckpt-every 20

On a real cluster each host runs this with its own ``--host-id``/``--hosts``
(jax.distributed handles the rest); in this container it drives the
single-process path and, with ``--mesh smoke``, a 2x2 host-device mesh.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--scale", default=None,
                    help="comma k=v config overrides, e.g. d_model=640,n_layers=10")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="none",
                    choices=["none", "wsd", "cosine"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="none", choices=["none", "smoke"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.mesh == "smoke":
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=4")

    import jax
    import jax.numpy as jnp

    from ..configs import get_config, smoke_config
    from ..data.tokens import TokenPipeline
    from ..models.transformer import Dist, init_params
    from ..optim.optimizers import OPTIMIZERS
    from ..optim.schedules import cosine_schedule, wsd_schedule
    from ..train.checkpoint import load_latest, restore_like, save_checkpoint
    from ..train.train_step import TrainState, make_train_step
    from .compile_cache import enable_compile_cache
    from .mesh import make_smoke_mesh
    from .shardings import param_specs, to_shardings

    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.scale:
        kv = dict(s.split("=") for s in args.scale.split(","))
        cfg = cfg.scaled(**{k: (int(v) if v.isdigit() else v)
                            for k, v in kv.items()})
    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family}")

    lr = args.lr
    if args.schedule == "wsd":
        lr = wsd_schedule(args.lr, args.steps // 10, args.steps * 7 // 10,
                          args.steps // 5)
    elif args.schedule == "cosine":
        lr = cosine_schedule(args.lr, args.steps // 10, args.steps)
    opt = OPTIMIZERS[args.optimizer](lr=lr)

    dist = Dist()
    if args.mesh == "smoke":
        mesh = make_smoke_mesh()
        dist = Dist(mesh=mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if dist.active:
        shardings = to_shardings(dist.mesh, param_specs(params, dist.mesh,
                                                        fsdp=cfg.fsdp))
        params = jax.device_put(params, shardings)
    state = TrainState(params, opt.init(params))

    start = 0
    if args.ckpt:
        found = load_latest(args.ckpt)
        if found:
            start, flat = found
            state = restore_like(state, flat)
            print(f"resumed from step {start}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, n_hosts=args.hosts,
                         host_id=args.host_id)
    step_fn = jax.jit(make_train_step(cfg, opt, dist,
                                      microbatches=args.microbatches),
                      donate_argnums=(0,))

    t0 = time.time()
    pending_save = None
    for step in range(start, args.steps):
        batch = pipe.batch_at(step)
        if cfg.embedding_inputs:  # modality stub: tokens -> frame embeddings
            rng = jax.random.PRNGKey(step)
            batch = {"embeds": jax.random.normal(
                rng, (args.batch, args.seq, cfg.d_model), jnp.float32) * 0.02,
                "labels": batch["labels"] % cfg.vocab}
        if cfg.mrope:
            import numpy as np
            pos = np.arange(args.seq, dtype=np.int32)
            batch["positions3"] = np.broadcast_to(
                pos[None, :, None], (args.batch, args.seq, 3))
        state, metrics = step_fn(state, batch)
        if (step + 1) % args.log_every == 0 or step == start:
            print(f"step {step+1:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)",
                  flush=True)
        if args.ckpt and (step + 1) % args.ckpt_every == 0:
            if pending_save is not None:
                pending_save.join()
            pending_save = save_checkpoint(args.ckpt, state, step + 1,
                                           async_save=True)
    if pending_save is not None:
        pending_save.join()
    if args.ckpt:
        save_checkpoint(args.ckpt, state, args.steps)
    print(f"done: {args.steps - start} steps in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
