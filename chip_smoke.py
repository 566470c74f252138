"""Chip smoke test: GEVO-ML's main path, once, on a TPU.

    python chip_smoke.py              # one chip: serving, GEVO search, kernels
    python chip_smoke.py --chips 4    # four router replicas, one per chip

On one chip it runs three phases through the entry points a user calls:

* ``serve``   -- ``qwen3-0.6b`` at its published widths (bf16, random
  weights from ``--seed``) through ``ServeEngine`` with continuous batching,
  as ``python -m repro.launch.serve --arch qwen3-0.6b`` runs it: 8 requests
  with prompts of 128 and 64 tokens, 32 generated tokens each.  Every
  request must complete and every logit the engine samples from must be
  finite; for two requests the logits the engine sampled each token from
  are compared, step by step up to the first token that differs, with the
  direct prefill/decode loop ``models.transformer.greedy_reference``, which
  shares no serving code.
* ``gevo``    -- the unpatched 2fcNet training step runs strictly (no
  invalid-variant handler), then a 2-generation ``GevoML`` search with
  fitness measured on the device.
* ``kernels`` -- each Pallas kernel's shipped baseline schedule compiles to
  Mosaic (``tpu_custom_call`` in the compiled program), runs strictly and
  agrees with its reference at the search's evaluation shape in f32 and at
  a served model's widths in bf16, and evaluates valid through the
  search's measured-fitness path.

With ``--chips 4`` it runs only the multi-replica router (``--mesh 4x1``:
one replica per chip) and the same trace on a single replica.

Every phase prints one line.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}`` when every phase passed.  The
script exits nonzero, printing no result, when JAX finds no TPU or the
repository's ``src/`` is not next to it, and exits nonzero when any phase
failed.  Everything runs in this one process: a chip belongs to one process
at a time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen3-0.6b"
N_REQUESTS, PROMPT_LEN, GEN = 8, 128, 32
N_COMPARED = 2          # requests checked against the direct loop

# Kernel output tolerance by input dtype: the repo's kernel-test tolerances
# (tests/test_kernels.py), scaled by the output's magnitude because
# rounding error is relative.
KERNEL_TOL = {
    "float32": {"rmsnorm": 1e-5, "flash_attention": 2e-5, "mamba_scan": 1e-4},
    "bfloat16": {"rmsnorm": 3e-2, "flash_attention": 2e-2, "mamba_scan": 5e-2},
}


class SmokeFailure(Exception):
    """A check of a phase failed."""


def _require(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def _log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs


def _hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit") if k in stats}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


def _checked_engine_cls():
    """``ServeEngine`` that counts every logit it samples from and every
    non-finite one among them, and keeps, for the requests named in
    ``watch``, the logits of every step it sampled their tokens from."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.deploy import ServeEngine

    class CheckedEngine(ServeEngine):
        def __init__(self, *args, watch=(), **kwargs):
            super().__init__(*args, **kwargs)
            self.n_logits = self.n_nonfinite = 0
            self.steps = {uid: [] for uid in watch}
            self._prefilling = []

        def _keep(self, uid, logits):
            if uid in self.steps:
                self.steps[uid].append(np.asarray(logits, np.float32))

        def _select_admissions(self, n_take):
            self._prefilling = super()._select_admissions(n_take)
            _require(len(self._prefilling) == 1, "logits are attributed "
                     "to requests only at one admission per prefill")
            return self._prefilling

        def _sample(self, logits, routed=None):
            self.n_logits += int(logits.size)
            self.n_nonfinite += int(jnp.sum(~jnp.isfinite(logits)))
            if self._prefilling:                 # the admitted prompt's
                self._keep(self._prefilling.pop().uid, logits[0])
            return super()._sample(logits, routed)

        def _decode_complete(self, pending):
            if pending is not None:
                active, logits, _ = pending
                for i, lane in active:
                    self._keep(lane.req.uid, logits[i])
            super()._decode_complete(pending)

    return CheckedEngine


def serve_and_compare(cfg, params, *, seed: int, n_requests: int,
                      prompt_len: int, gen: int, n_compared: int,
                      clock: _CompileClock | None = None) -> dict:
    """Serve a demo trace through ``ServeEngine`` with the serve CLI's
    default schedule, check every request and logit, and compare the first
    ``n_compared`` requests step by step with ``greedy_reference``.
    Returns the fields of the phase's line."""
    import numpy as np

    from repro.core.deploy import serve_plan_from
    from repro.core.liveloop.traces import demo_requests
    from repro.models.transformer import greedy_reference

    schedule = serve_plan_from(None)           # the serve CLI's default
    trace = demo_requests(cfg, n_requests=n_requests, prompt_len=prompt_len,
                          gen=gen, seed=seed)
    compared = trace[:n_compared]              # one prompt of each length
    engine = _checked_engine_cls()(
        cfg, params, max_len=prompt_len + gen,
        max_slots=schedule["max_slots"],
        prefill_chunk=schedule["prefill_chunk"], seed=seed,
        watch=[r.uid for r in compared])
    c0, t0 = clock.total if clock else 0.0, time.perf_counter()
    results = {r.uid: r for r in engine.run(trace, stagger=2)}
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0 if clock else None
    _require(len(results) == n_requests,
             f"{len(results)} of {n_requests} requests done")
    _require(all(len(r.tokens) == gen for r in results.values()),
             "a request ended short of its token budget")
    _require(engine.n_nonfinite == 0, f"{engine.n_nonfinite} of "
             f"{engine.n_logits} sampled logits non-finite")

    # bf16 logits carry 8 significant bits: a spacing of 2^-7 of the
    # magnitude.  Engine and reference run the same layers on the same
    # tokens, so they may differ only by rounding in how XLA fuses and
    # orders the work; allow 4 such spacings of the largest logit.  Up to
    # and including the first step whose tokens differ, both saw the same
    # tokens, so each of those steps is compared.
    report = []
    for req in compared:
        ref_tokens, ref_logits = greedy_reference(params, req.tokens, gen,
                                                  cfg)
        got, toks = engine.steps[req.uid], results[req.uid].tokens
        _require(len(got) == gen, f"{req.uid}: {len(got)} of {gen} steps' "
                 "logits recorded")
        k = next((i for i, (a, b) in enumerate(zip(toks, ref_tokens))
                  if a != b), gen)
        row = {"uid": req.uid, "tokens_agreeing": k}
        for j in range(min(k + 1, gen)):
            ref = np.asarray(ref_logits[j], np.float32)
            _require(np.all(np.isfinite(ref)),
                     f"{req.uid}: reference logits non-finite at step {j}")
            diff = float(np.max(np.abs(got[j] - ref)))
            tol = 4 * 2.0 ** -7 * float(np.max(np.abs(ref)))
            _require(diff <= tol, f"{req.uid}: step {j} logits differ by "
                     f"{diff} > {tol}")
            if j == 0:
                row.update(first_diff=diff, first_tol=tol)
            row["max_diff"] = max(row.get("max_diff", 0.0), diff)
        if k < gen:     # step k, the loop's last: shown to be a near tie
            ref = np.asarray(ref_logits[k], np.float32)
            top2 = np.sort(ref)[-2:]
            row.update(diverge_step=k, diverge_diff=diff, diverge_tol=tol,
                       ref_top2_margin=float(top2[1] - top2[0]),
                       ref_gap_to_engine_token=float(
                           ref[ref_tokens[k]] - ref[toks[k]]))
        report.append(row)
    stats = engine.stats()
    return dict(requests=len(results), gen_tokens=stats["gen_tokens"],
                ticks=stats["ticks"], wall_s=wall, compile_s=compile_s,
                logits_checked=engine.n_logits,
                compared=json.dumps(report))


def phase_serve(seed: int, clock: _CompileClock) -> None:
    import jax

    from repro.configs import get_config
    from repro.models.transformer import init_params

    cfg = get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    fields = serve_and_compare(cfg, params, seed=seed, n_requests=N_REQUESTS,
                               prompt_len=PROMPT_LEN, gen=GEN,
                               n_compared=N_COMPARED, clock=clock)
    _log("serve", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
         dtype=cfg.dtype, param_bytes=n_bytes, **fields,
         hbm=json.dumps(_hbm(jax.devices()[0])))


# --------------------------------------------------------------------------
# GEVO search with fitness measured on the device
# --------------------------------------------------------------------------


def phase_gevo(seed: int, clock: _CompileClock) -> None:
    import numpy as np

    from repro.core import GevoML
    from repro.workloads.twofc import build_twofc_training_workload

    wl = build_twofc_training_workload(time_mode="measured", seed=seed)
    c0, t0 = clock.total, time.perf_counter()
    t_orig, e_orig = wl.run(wl.program)      # strict: failures propagate
    t_strict = time.perf_counter() - t0
    search = GevoML(wl, pop_size=8, n_elite=4, seed=seed)
    res = search.run(generations=2)
    search.close()
    wall, compile_s = time.perf_counter() - t0, clock.total - c0
    _require(len(res.history) == 2 and res.pareto,
             "search produced no front")
    _require(np.all(np.isfinite(res.original_fitness)),
             f"original fitness {res.original_fitness} not finite")
    _log("gevo", workload=wl.name, original_time_s=t_orig,
         original_error=e_orig, strict_run_s=t_strict,
         search_original_fitness=list(res.original_fitness),
         evals=search.n_evals, invalid=search.n_invalid,
         best_time_s=res.history[-1]["best_time"],
         best_error=res.history[-1]["best_error"],
         pareto=len(res.pareto), wall_s=wall, compile_s=compile_s)


# --------------------------------------------------------------------------
# Pallas kernel baselines
# --------------------------------------------------------------------------


def _run_baseline(kernel: str, inputs, dtype: str) -> dict:
    """The kernel's shipped baseline schedule, compiled and run strictly on
    ``inputs`` (in ``dtype``) and compared with its reference."""
    import jax
    import numpy as np

    from repro.core.fitness import measured_time
    from repro.kernels.workloads import kernel_reference, scheduled_kernel_fn

    ref = kernel_reference(kernel, inputs)
    compiled = jax.jit(scheduled_kernel_fn(kernel)).lower(inputs).compile()
    out = np.asarray(compiled(inputs), np.float32)
    return {"mosaic": "tpu_custom_call" in compiled.as_text(),
            "max_abs_err": float(np.max(np.abs(out - ref))),
            "tol": KERNEL_TOL[dtype][kernel] * max(
                1.0, float(np.max(np.abs(ref)))),
            "kernel_s": measured_time(compiled, inputs)}


def phase_kernels(seed: int, clock: _CompileClock) -> None:
    from repro.kernels.workloads import (BASELINES, KERNELS, SHAPES,
                                         build_kernel_workload,
                                         kernel_inputs, model_width_shapes)

    wide = model_width_shapes()
    failed = []
    for kernel in KERNELS:                  # every kernel, then the verdict
        # the search's evaluation shape in f32, then a served model's widths
        # in bf16 (more than one channel tile for mamba_scan)
        for shape, dtype in ((SHAPES[kernel], "float32"),
                             (wide[kernel], "bfloat16")):
            c0, t0 = clock.total, time.perf_counter()
            got = _run_baseline(kernel, kernel_inputs(kernel, seed, shape,
                                                      dtype), dtype)
            ok = got["mosaic"] and got["max_abs_err"] <= got["tol"]
            if shape is SHAPES[kernel]:    # and through the search's path
                wl = build_kernel_workload(kernel, time_mode="measured",
                                           seed=seed)
                t_eval, e_eval = wl.evaluate(wl.program)
                got["search_eval"] = json.dumps([t_eval, e_eval])
                ok = ok and e_eval <= got["tol"]
            _log("kernels", kernel=kernel, shape=json.dumps(shape),
                 dtype=dtype,
                 schedule=json.dumps(BASELINES[kernel], sort_keys=True),
                 **got, wall_s=time.perf_counter() - t0,
                 compile_s=clock.total - c0)
            if not ok:
                failed.append(f"{kernel}@{json.dumps(shape)}")
    _require(not failed,
             f"kernels without Mosaic or beyond tolerance: {failed}")


# --------------------------------------------------------------------------
# four chips: the multi-replica router
# --------------------------------------------------------------------------


def _placement(tree) -> set:
    import jax
    devs = set()
    for leaf in jax.tree.leaves(tree):
        devs |= set(leaf.devices())
    return devs


def phase_router(seed: int, clock: _CompileClock) -> None:
    import jax

    from repro.configs import get_config
    from repro.core.deploy import build_router
    from repro.core.liveloop.traces import demo_requests
    from repro.launch.mesh import make_smoke_mesh
    from repro.models.transformer import init_params

    n = 4
    _require(len(jax.devices()) >= n, f"{len(jax.devices())} devices < {n}")
    cfg = get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    max_len = PROMPT_LEN + GEN

    def serve(replicas: int, mesh):
        router = build_router(cfg, params, genome={"replicas": replicas},
                              max_len=max_len, mesh=mesh, seed=seed)
        trace = demo_requests(cfg, n_requests=N_REQUESTS,
                              prompt_len=PROMPT_LEN, gen=GEN, seed=seed)
        c0, t0 = clock.total, time.perf_counter()
        res = {r.uid: r.tokens for r in router.run(trace, stagger=2)}
        return router, res, time.perf_counter() - t0, clock.total - c0

    router, many, wall, compile_s = serve(n, make_smoke_mesh(n, 1))
    homes = []
    for r in router.replicas:
        eng = r.engine
        devs = _placement(eng.params) | _placement(eng.batch.caches)
        _require(len(devs) == 1, f"replica {r.index} spans {devs}")
        homes.append(devs.pop())
    _require(len(set(homes)) == n, f"replicas share devices: {homes}")
    per_replica = [p["n_completed"] for p in router.stats()["per_replica"]]
    _require(len(many) == N_REQUESTS, f"{len(many)} of {N_REQUESTS} done")
    _, one, wall1, compile1 = serve(1, None)
    _require(len(one) == N_REQUESTS, f"{len(one)} of {N_REQUESTS} done")
    mismatched = [u for u in one if one[u] != many[u]]
    _require(not mismatched, f"tokens differ from one replica: {mismatched}")
    _log("router", arch=cfg.name, replicas=n,
         slots_per_replica=router.replicas[0].engine.max_slots,
         devices=json.dumps([str(d) for d in homes]),
         completed_per_replica=per_replica,
         requests=len(many), tokens_match_single_replica=True,
         wall_s=wall, compile_s=compile_s,
         single_replica_wall_s=wall1, single_replica_compile_s=compile1)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run GEVO-ML's main path once on a TPU.")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serving, GEVO search and kernel baselines on "
                         "one chip; 4: the multi-replica router only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no backend: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX backend: {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    _log("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, compile_cache=cache_dir)

    clock = _CompileClock()
    phases = ([phase_router] if args.chips == 4
              else [phase_serve, phase_gevo, phase_kernels])
    failed = []
    for phase in phases:
        try:
            phase(args.seed, clock)
        except Exception:                    # report every phase, then fail
            failed.append(phase.__name__)
            traceback.print_exc()
            print(f"[{phase.__name__}] FAILED", flush=True)
    result = {"ok": not failed,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)}}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
