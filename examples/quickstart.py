"""Quickstart: GEVO-ML in miniature (~2 minutes on CPU).

Reproduces the paper's training experiment structure on 2fcNet/MNIST-syn:
NSGA-II evolves patches of the training-step IR — sampled from the pluggable
operator registry (delete / copy / swap / insert / const_perturb) — and the
Pareto front trades runtime against model error.  Run:

    PYTHONPATH=src python examples/quickstart.py

Edit-layer flags (see README "Operator registry"):

    --operators SPEC    sampling mix: "all" (default), "legacy"
                        (paper's copy/delete), or "copy=1,swap=2,..."
    --minimize          ddmin the best-by-time patch down to its key
                        mutations (nearly free: reuses the fitness cache)

Evaluation-engine flags (see README "Evaluation engine"):

    --parallel N        evaluate variants in N worker processes
    --cache PATH        persistent fitness cache (JSONL); rerun with the
                        same path and the search re-measures nothing
    --checkpoint DIR    write per-generation snapshots
    --resume            continue from the latest snapshot in --checkpoint
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import GevoML, OperatorWeights, minimize_patch
from repro.core.evaluator import make_evaluator
from repro.launch.compile_cache import enable_compile_cache
from repro.workloads.twofc import build_twofc_training_workload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--operators", default="all",
                    help='mutation mix: "all", "legacy", or '
                         '"name=w,name=w,..." over '
                         "{delete,copy,swap,insert,const_perturb}")
    ap.add_argument("--minimize", action="store_true",
                    help="minimize the best-by-time patch to its key "
                         "mutations (GEVO Sec. 6 style)")
    ap.add_argument("--parallel", type=int, default=0,
                    help="evaluation worker processes (0/1 = in-process)")
    ap.add_argument("--cache", default=None,
                    help="persistent fitness cache path (JSONL)")
    ap.add_argument("--checkpoint", default=None,
                    help="checkpoint directory (one snapshot per generation)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --checkpoint")
    ap.add_argument("--generations", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    if args.resume and not args.checkpoint:
        ap.error("--resume requires --checkpoint")
    weights = OperatorWeights.parse(args.operators)

    print("Building 2fcNet training workload (one SGD step as IR)...")
    w = build_twofc_training_workload(batch=32, hidden=64, steps=80,
                                      n_train=2048, n_test=1024, lr=0.01)
    print(f"  program: {len(w.program.ops)} HLO-lite ops, "
          f"{len(w.program.inputs)} inputs")
    t0, e0 = w.evaluate(w.program)
    print(f"  original fitness: time={t0:.3e}s  error={e0:.4f}\n")

    mode = (f"{args.parallel} workers" if args.parallel > 1 else "serial")
    print(f"Running GEVO-ML (NSGA-II, pop=12, {args.generations} "
          f"generations, operators={{{', '.join(weights.names())}}}, "
          f"{mode} evaluation)...")
    evaluator = make_evaluator(w, parallel=args.parallel,
                               cache_path=args.cache)
    search = GevoML(w, pop_size=12, n_elite=6, seed=0, verbose=True,
                    operators=weights, evaluator=evaluator,
                    checkpoint_dir=args.checkpoint)
    res = search.run(generations=args.generations, resume=args.resume)

    print("\nPareto front (argmin(time, error)):")
    for ind in res.pareto:
        t, e = ind.fitness
        marks = []
        if t < t0 * 0.999:
            marks.append(f"time -{(1-t/t0)*100:.1f}%")
        if e < e0 - 1e-4:
            marks.append(f"error -{(e0-e)*100:.2f}pp")
        print(f"  time={t:.3e}  err={e:.4f}  {' '.join(marks)}")
        print(f"    patch: {ind.patch.describe()}")
    be = res.best_by_error()
    print(f"\nbest error {be.fitness[1]:.4f} vs original {e0:.4f} "
          f"({search.n_evals} fitness evaluations, "
          f"{search.n_invalid} invalid variants resampled, "
          f"cache hit rate {search.cache.hit_rate:.0%})")
    print("per-operator proposed/applied/valid/elite:")
    for name, row in res.operator_stats().items():
        print(f"  {name:>14}: {row['proposed']:4d} / {row['applied']:4d} / "
              f"{row['valid']:4d} / {row['elite']:4d}")
    if args.minimize:
        bt = res.best_by_time()
        small, fit = minimize_patch(bt.patch, search.evaluator,
                                    expect_fitness=bt.fitness)
        print(f"\nminimized best-by-time patch: {len(bt.patch)} -> "
              f"{len(small)} edits at identical fitness {fit}")
        print(f"  key mutations: {small.describe()}")
    if args.cache:
        print(f"fitness cache: {len(search.cache)} entries at {args.cache}")
    evaluator.close()


if __name__ == "__main__":
    main()
