"""End-to-end training driver: a ~100M-parameter qwen3-family model trained
for a few hundred steps on the synthetic token stream, with periodic async
checkpoints and automatic resume.

    PYTHONPATH=src python examples/train_100m.py [--steps 300]

On this single-CPU container expect ~5-10 s/step (the same script on a TPU
slice just needs --mesh and jax.distributed init via repro.launch.train).
Loss should fall from ~ln(32000)=10.4 toward ~4-6 as the model learns the
order-2 Markov structure of the stream.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.optim.optimizers import adamw
from repro.optim.schedules import wsd_schedule
from repro.train.checkpoint import load_latest, restore_like, save_checkpoint
from repro.train.train_step import TrainState, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_100m_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    # qwen3 family scaled to ~100M params
    cfg = get_config("qwen3-0.6b").scaled(
        n_layers=10, d_model=640, n_heads=10, n_kv_heads=5, head_dim=64,
        d_ff=1792, vocab=32000, dtype="float32", loss_chunk=0)
    print(f"model: {cfg.param_count()/1e6:.0f}M params "
          f"({cfg.n_layers}L d={cfg.d_model})")

    opt = adamw(lr=wsd_schedule(3e-4, args.steps // 10,
                                args.steps * 7 // 10, args.steps // 5))
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    start = 0
    found = load_latest(args.ckpt)
    if found:
        start, flat = found
        state = restore_like(state, flat)
        print(f"resumed from step {start}")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch)
    step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    t0 = time.time()
    pending = None
    for step in range(start, args.steps):
        state, m = step_fn(state, pipe.batch_at(step))
        if (step + 1) % 10 == 0 or step == start:
            print(f"step {step+1:4d}  loss={float(m['loss']):.4f}  "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)",
                  flush=True)
        if (step + 1) % 50 == 0:
            if pending:
                pending.join()
            pending = save_checkpoint(args.ckpt, state, step + 1,
                                      async_save=True)
    if pending:
        pending.join()
    save_checkpoint(args.ckpt, state, args.steps)
    print(f"finished {args.steps - start} steps "
          f"in {time.time()-t0:.0f}s; checkpoints in {args.ckpt}")


if __name__ == "__main__":
    main()
