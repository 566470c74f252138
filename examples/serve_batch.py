"""Continuous-batching serving demo across architecture families.

For each decoder arch (reduced config), replays a staggered mixed-length
request trace through the :class:`~repro.core.deploy.ServeEngine` —
micro-batched prefill interleaved with vmapped per-lane decode over KV /
compressed-MLA / SSM caches — and prints the measured throughput and
latency, plus a correctness check of the continuous path against the
one-shot oracle.

    PYTHONPATH=src python examples/serve_batch.py
    PYTHONPATH=src python examples/serve_batch.py --arch qwen3-0.6b \
        --artifacts experiments/artifacts
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

DEFAULT_ARCHS = ("qwen3-0.6b", "deepseek-v3-671b", "falcon-mamba-7b",
                 "zamba2-1.2b")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch id (repeatable; default: one per family)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--stagger", type=int, default=2)
    ap.add_argument("--artifacts", default=None,
                    help="resolve the serving schedule from this "
                         "ArtifactRegistry instead of the default")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import smoke_config
    from repro.core.deploy import (ArtifactRegistry, ServeEngine,
                                   engine_schedule_from, oneshot_generate)
    from repro.core.liveloop.traces import demo_requests

    registry = ArtifactRegistry(args.artifacts) if args.artifacts else None
    for arch in (args.arch or DEFAULT_ARCHS):
        cfg = smoke_config(arch)
        art = (registry.resolve(cfg.name, "smoke", kind="serve")
               if registry else None)
        schedule = engine_schedule_from(art)
        print(f"=== {arch} ({cfg.family}, reduced config, "
              f"schedule={schedule}"
              f"{' from ' + args.artifacts if art else ''}) ===", flush=True)
        engine = ServeEngine(cfg, max_len=args.prompt_len + args.gen,
                             max_slots=schedule["max_slots"],
                             prefill_chunk=schedule["prefill_chunk"])
        trace = demo_requests(cfg, n_requests=args.requests,
                              prompt_len=args.prompt_len, gen=args.gen)
        results = engine.run(trace, stagger=args.stagger or None)
        s = engine.stats()
        rec = s["per_variant"]["default"]
        print(f"  {len(results)} requests in {s['wall_s']:.2f}s "
              f"({s['throughput_tok_s']:.1f} tok/s, "
              f"ttft {rec['mean_ttft_s'] * 1e3:.0f}ms, "
              f"latency {rec['mean_latency_s'] * 1e3:.0f}ms, "
              f"{s['decode_batches']} decode dispatches)")
        # continuous batching must reproduce the one-shot oracle exactly
        probe = trace[0]
        ref = oneshot_generate(cfg, engine.params, probe.tokens[None, :],
                               probe.max_new_tokens)[0].tolist()
        got = next(r.tokens for r in results if r.uid == probe.uid)
        assert got == ref, f"{arch}: engine diverged from one-shot oracle"
        print(f"  {probe.uid}: {got[:10]}... (matches one-shot oracle)")


if __name__ == "__main__":
    main()
