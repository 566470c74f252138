"""The paper's prediction experiment: GEVO-ML on MobileNet/CIFAR10-syn
(Figure 4a).  Pretrains MobileNet in JAX, bakes it into the IR with weights
as constants, then evolves registry-operator patches (``--operators``
selects the mix; default all five) minimizing
(inference time, prediction error).

    PYTHONPATH=src python examples/gevo_mobilenet.py [--full]

The paper's headline: 90.43% runtime improvement at a 2% test-accuracy
cost.  At example scale (reduced width/eval set/generations) expect smaller
but clearly visible Pareto spread in the same direction.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import GevoML, OperatorWeights
from repro.core.evaluator import make_evaluator
from repro.launch.compile_cache import enable_compile_cache
from repro.workloads.mobilenet import build_mobilenet_prediction_workload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger model / eval set / budget (slow)")
    ap.add_argument("--operators", default="all",
                    help='mutation mix: "all", "legacy", or '
                         '"name=w,name=w,..."')
    ap.add_argument("--parallel", type=int, default=0,
                    help="evaluation worker processes (0/1 = in-process); "
                         "the pretrained workload ships to workers whole")
    ap.add_argument("--cache", default=None,
                    help="persistent fitness cache path (JSONL)")
    args = ap.parse_args()
    enable_compile_cache()

    t0 = time.time()
    print("Pretraining MobileNet on synthetic CIFAR10...")
    w = build_mobilenet_prediction_workload(
        alpha=0.25 if args.full else 0.125,
        n_eval=2048 if args.full else 512,
        n_pretrain=6000 if args.full else 2000,
        pretrain_epochs=4 if args.full else 2, verbose=True)
    tt, ee = w.evaluate(w.program)
    print(f"  baked IR: {len(w.program.ops)} ops; original time={tt:.3e}s "
          f"err={ee:.4f}  [{time.time()-t0:.0f}s]")

    evaluator = make_evaluator(w, parallel=args.parallel,
                               cache_path=args.cache)
    s = GevoML(w, pop_size=12 if args.full else 8,
               n_elite=6 if args.full else 4, seed=0, verbose=True,
               operators=OperatorWeights.parse(args.operators),
               evaluator=evaluator)
    res = s.run(generations=6 if args.full else 3)
    evaluator.close()

    print("\nPareto front:")
    t0_, e0 = res.original_fitness
    for ind in res.pareto:
        t, e = ind.fitness
        print(f"  time={t:.3e} ({(1-t/t0_)*100:+5.1f}%)  err={e:.4f} "
              f"({(e-e0)*100:+.2f}pp)")
        print(f"    {ind.patch.describe()}")
    ok = [i for i in res.pareto if i.fitness[1] <= e0 + 0.02]
    if ok:
        fastest = min(ok, key=lambda i: i.fitness[0])
        print(f"\npaper-style headline: {(1-fastest.fitness[0]/t0_)*100:.1f}% "
              f"runtime improvement at <=2% accuracy cost")


if __name__ == "__main__":
    main()
