"""Serving launcher: the continuous-batching :class:`ServeEngine` as a CLI.

Replays a deterministic mixed-length request trace (staggered arrivals)
through the engine for any decoder arch, serving either the default model
configuration or an evolved plan artifact resolved from an
:class:`~repro.core.deploy.ArtifactRegistry`, and optionally publishing the
measured latency into a shared fitness cache under the ``serve`` writer tag.
(The live loop's shadow-replay canary is the A/B path: see
``python -m repro.core.liveloop``.)

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 8 --prompt-len 24 --gen 8

  # engine schedule + evolved plan resolved from the artifact registry
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --artifacts experiments/artifacts --variant evolved

  # the pre-engine one-shot behavior (correctness oracle)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --oneshot --requests 4 --prompt-len 32 --gen 16

  # multi-replica serving through the deploy router (optionally sharded
  # over a mesh; see also `python -m repro.core.deploy.router`): on the
  # CPU over XLA host devices, on a four-chip TPU host one replica per chip
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --replicas 2 --mesh 2x2 --requests 8 --prompt-len 16 --gen 6
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --replicas 4 --mesh 4x1 --requests 8 --prompt-len 128 --gen 32
"""

from __future__ import annotations

import argparse


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="trace length (mixed prompt lengths)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--stagger", type=int, default=2,
                    help="requests arriving per engine tick (0 = all "
                         "upfront)")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="in-flight sequences (default: registry serve "
                         "artifact, else 2)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admissions micro-batched per tick")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--replicas", type=int, default=None,
                    help="data-parallel engine replicas behind the deploy "
                         "router (default: the resolved serve plan's "
                         "replicas knob, usually 1)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL mesh over the attached devices for "
                         "the replicas, e.g. 4x1 gives each of 4 replicas "
                         "its own chip (on the CPU: that many XLA host "
                         "devices)")
    ap.add_argument("--artifacts", default=None,
                    help="ArtifactRegistry directory (serve-schedule and "
                         "plan artifacts)")
    ap.add_argument("--variant", default="default",
                    choices=("default", "evolved"),
                    help="serve the default config or an evolved plan "
                         "artifact")
    ap.add_argument("--plan-shape", default="decode_32k",
                    help="shape key for resolving the plan artifact")
    ap.add_argument("--cache", default=None,
                    help="publish a latency record into this "
                         "FitnessCache (JSONL) under writer tag 'serve'")
    ap.add_argument("--oneshot", action="store_true",
                    help="pre-engine one-shot path: batch prefill + "
                         "lockstep decode of --requests equal prompts")
    ap.add_argument("--liveloop", default=None,
                    help="live-loop root directory (see `python -m "
                         "repro.core.liveloop`): serve with the loop's "
                         "promoted schedule, optionally advancing the "
                         "loop first")
    ap.add_argument("--liveloop-ticks", type=int, default=0,
                    help="control-loop ticks to run before serving")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from ..configs import get_config, smoke_config
    from ..core.deploy import (ArtifactRegistry, ServeEngine,
                               apply_plan_artifact, build_router,
                               oneshot_generate, serve_plan_from)
    from ..core.evaluator import FitnessCache
    from ..core.liveloop.traces import demo_requests

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")

    if args.oneshot:
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab,
                               (args.requests, args.prompt_len)
                               ).astype(np.int32)
        gen = oneshot_generate(cfg, None, prompts, args.gen,
                               temperature=args.temperature)
        print(f"arch={cfg.name} oneshot batch={args.requests} "
              f"prompt={args.prompt_len} generated={gen.shape[1]}")
        for b in range(min(args.requests, 2)):
            print(f"  seq{b}: {gen[b][:12].tolist()}...")
        return

    registry = ArtifactRegistry(args.artifacts) if args.artifacts else None
    serve_art = plan_art = None
    if registry is not None:
        serve_art = registry.resolve(cfg.name, "smoke" if args.smoke
                                     else "full", kind="serve")
        plan_art = registry.resolve(cfg.name, args.plan_shape, kind="plan")
    schedule = serve_plan_from(serve_art)
    if args.liveloop:
        # the loop's promoted schedule wins over the static registry: this
        # is the serving end of evolve->serve->measure->promote
        from ..core.liveloop import LiveLoopController
        ctl = LiveLoopController(args.liveloop)
        if args.liveloop_ticks:
            ctl.run(args.liveloop_ticks)
        live = ctl.registry.resolve(ctl.arch, "live", kind="serve")
        if live is not None:
            schedule.update({k: live.genome[k] for k in schedule
                             if k in live.genome})
            print(f"liveloop: serving promoted schedule {schedule} "
                  f"(fingerprint {live.meta['genome_fingerprint'][:12]})")
        else:
            print("liveloop: nothing promoted yet; serving the default "
                  "schedule")
    if args.max_slots is not None:
        schedule["max_slots"] = args.max_slots
    if args.prefill_chunk is not None:
        schedule["prefill_chunk"] = args.prefill_chunk
    if args.replicas is not None:
        schedule["replicas"] = args.replicas

    served = cfg
    if args.variant == "evolved":
        if plan_art is None:
            raise SystemExit(
                f"--variant evolved needs a plan artifact for "
                f"({cfg.name}, {args.plan_shape}); none registered under "
                f"{args.artifacts or '--artifacts (not given)'}")
        served = apply_plan_artifact(cfg, plan_art)

    if int(schedule.get("replicas", 1)) > 1:
        mesh = None
        if args.mesh:
            from .mesh import make_smoke_mesh
            d, m = (int(x) for x in args.mesh.lower().split("x"))
            mesh = make_smoke_mesh(d, m)
        engine = build_router(served, genome=schedule,
                              max_len=args.prompt_len + args.gen,
                              mesh=mesh, temperature=args.temperature)
    else:
        engine = ServeEngine(served, max_len=args.prompt_len + args.gen,
                             max_slots=schedule["max_slots"],
                             prefill_chunk=schedule["prefill_chunk"],
                             temperature=args.temperature)
    trace = demo_requests(cfg, n_requests=args.requests,
                          prompt_len=args.prompt_len, gen=args.gen)
    results = engine.run(trace, stagger=args.stagger or None)

    s = engine.stats()
    replica_note = (f" replicas={s['n_live']}/{s['n_replicas']}"
                    if "n_replicas" in s else "")
    print(f"arch={cfg.name} variant={args.variant} "
          f"requests={len(results)} schedule={schedule}{replica_note} "
          f"ticks={s['ticks']}")
    print(f"wall={s['wall_s']:.2f}s throughput={s['throughput_tok_s']:.1f} "
          f"tok/s ttft={s['mean_ttft_s'] * 1e3:.1f}ms "
          f"latency={s['mean_latency_s'] * 1e3:.1f}ms "
          f"(p95 {s['p95_latency_s'] * 1e3:.1f}ms) "
          f"s/token={s['s_per_token'] * 1e3:.1f}ms")
    for r in results[:2]:
        print(f"  {r.uid}: {r.tokens[:12]}...")

    if args.cache:
        cache = FitnessCache(args.cache, writer="serve")
        # the run tag keeps an evolved plan's record apart from the
        # default's under the same schedule
        keys = engine.publish_stats(
            cache, name=cfg.name,
            shape={"prompt_len": args.prompt_len, "gen": args.gen,
                   "smoke": args.smoke},
            run="" if args.variant == "default" else args.variant)
        cache.close()
        print(f"published {len(keys)} serve-tagged latency records to "
              f"{args.cache}")


if __name__ == "__main__":
    main()
