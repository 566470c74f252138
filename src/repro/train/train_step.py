"""Train / serve step factories.

``make_train_step`` builds the jitted step for any arch config:

* microbatch gradient accumulation (``lax.scan`` over microbatches — also the
  compute/communication overlap lever: GSPMD overlaps each microbatch's
  reduce-scatter with the next microbatch's backward);
* optional int8-compressed data-parallel gradient all-reduce with error
  feedback (replicated-params DP mode; see optim/grad_compress.py);
* ``donate`` of the previous state so params update in place.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import Dist, train_loss
from ..optim.grad_compress import compress_tree_psum
from ..optim.optimizers import Optimizer


def TrainState(params, opt_state, step=0, residuals=None) -> dict:
    s = {"params": params, "opt_state": opt_state,
         "step": jnp.asarray(step, jnp.int32)}
    if residuals is not None:
        s["residuals"] = residuals
    return s


def _split_microbatches(batch: dict, k: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % k == 0, f"batch {b} not divisible by {k} microbatches"
        return x.reshape((k, b // k) + x.shape[1:])
    return jax.tree.map(split, batch)


def _accum_grads(loss_fn, params, batch, k):
    """Mean loss/grads over k microbatches via scan (bounds activation
    memory; lets XLA overlap grad reduction with the next microbatch)."""
    mbs = _split_microbatches(batch, k)

    def body(carry, mb):
        acc_loss, acc_g = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        return (acc_loss + loss / k,
                jax.tree.map(lambda a, b: a + b / k, acc_g, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
    (loss, grads), _ = jax.lax.scan(body, zero, mbs)
    return loss, grads


def make_train_step(cfg, optimizer: Optimizer, dist: Dist = Dist(),
                    microbatches: int = 1, compress_grads: bool = False,
                    grad_shardings=None):
    """Returns jitted ``step(state, batch) -> (state, metrics)``.

    ``grad_shardings``: optional pytree of NamedShardings (matching params)
    pinned onto the gradients before the optimizer update — without this
    GSPMD may replicate stacked-expert gradients (a one-time multi-TB
    all-gather on the 671B config; see EXPERIMENTS.md §Perf)."""

    def loss_fn(params, mb):
        return train_loss(params, mb, cfg, dist)

    def step(state, batch):
        params = state["params"]
        if compress_grads and dist.active:
            # replicated-params DP: per-shard grads + int8 compressed psum.
            # Inside shard_map all axes are manual -> the model runs with an
            # inactive Dist (no with_sharding_constraint on manual axes).
            def local_loss(params, mb):
                return train_loss(params, mb, cfg, Dist())

            def local_grads(params, batch):
                loss, g = jax.value_and_grad(local_loss)(params, batch)
                g, res = compress_tree_psum(g, "data",
                                            state.get("residuals"))
                loss = jax.lax.pmean(loss, "data")
                return loss, g, res

            in_specs = (jax.tree.map(lambda _: P(), params),
                        jax.tree.map(lambda _: P(dist.batch_axes), batch))
            out_specs = (P(), jax.tree.map(lambda _: P(), params),
                         jax.tree.map(lambda _: P(), params))
            loss, grads, res = jax.shard_map(
                local_grads, mesh=dist.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False)(params, batch)
        elif microbatches > 1:
            loss, grads = _accum_grads(loss_fn, params, batch, microbatches)
            res = None
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            res = None
        if grad_shardings is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint, grads,
                                 grad_shardings)
        new_params, new_opt = optimizer.update(grads, state["opt_state"],
                                               params, state["step"])
        if getattr(cfg, "gnorm_vdot", False):
            # the A/B baseline: flattening a 2D-sharded stacked expert grad
            # makes GSPMD all-gather the full tensor (917 GB/device on the
            # 671B config; EXPERIMENTS.md §Perf iteration 2)
            gnorm = jnp.sqrt(sum(jnp.vdot(g, g).real for g in
                                 jax.tree.leaves(grads)).astype(jnp.float32))
        else:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        if res is not None:
            new_state["residuals"] = res
        return new_state, {"loss": loss, "grad_norm": gnorm}

    return step
