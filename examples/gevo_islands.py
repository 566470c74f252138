"""Island-model GEVO: N populations, migration, one shared fitness cache.

Runs :class:`repro.core.IslandOrchestrator` over any of the engine's
scenario families — the paper's IR-level workloads (2fcNet training,
MobileNet prediction) or the kernel-schedule spaces (rmsnorm,
flash_attention, mamba_scan).  Each island gets its own RNG stream,
operator mix, and mutation rate (heterogeneous palette by default); elites
migrate every K generations over a configurable topology; all islands share
one concurrency-safe fitness cache, so a migrant is never re-evaluated by
its destination.  Run:

    PYTHONPATH=src python examples/gevo_islands.py --workload twofc \
        --islands 2 --generations 2          # CI smoke budget
    PYTHONPATH=src python examples/gevo_islands.py --workload rmsnorm \
        --islands 4 --generations 6 --topology broadcast_best

Flags:

    --workload NAME     twofc | mobilenet | rmsnorm | flash_attention |
                        mamba_scan | joint (all three kernels, one genome)
    --engine E          python (spawned-process islands, default) | tensor
                        (device-mesh islands: the whole fleet steps as one
                        vmapped array program; kernel workloads only)
    --islands N         number of islands (default 4)
    --migrate-every K   generations between migrations (default 2)
    --migrants M        NSGA-II-best individuals each source sends (2)
    --topology T        ring | full | broadcast_best (default ring)
    --processes MODE    auto | on | off — island worker processes; "auto"
                        consults repro.core.islands.plan() (default off)
    --root DIR          state directory (manifest + island checkpoints +
                        shared cache); enables --resume.  Default: temp dir
    --resume            continue a killed run from --root (bit-exact)
    --surrogate         cache-trained cost model pre-ranks offspring on
                        every island (the shared cache trains all models)
    --surrogate-keep F  fraction of generated offspring that is executed
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import IslandOrchestrator, default_island_specs
from repro.core.islands import TOPOLOGIES, plan
from repro.launch.compile_cache import enable_compile_cache

WORKLOADS = ("twofc", "mobilenet", "rmsnorm", "flash_attention",
             "mamba_scan", "joint")
KERNELS = ("rmsnorm", "flash_attention", "mamba_scan", "joint")


def build_workload(name: str):
    """(workload, operators) for the orchestrator: IR workloads use the
    heterogeneous operator palette, schedule spaces pin attr_tweak."""
    if name == "twofc":
        from repro.workloads.twofc import build_twofc_training_workload
        return build_twofc_training_workload(
            batch=32, hidden=64, steps=60, n_train=2048, n_test=1024), None
    if name == "mobilenet":
        from repro.workloads.mobilenet import \
            build_mobilenet_prediction_workload
        print("Pretraining MobileNet on synthetic CIFAR10...")
        return build_mobilenet_prediction_workload(
            alpha=0.125, n_eval=512, n_pretrain=2000, pretrain_epochs=2,
            verbose=True), None
    if name == "joint":
        from repro.kernels.workloads import build_joint_kernel_workload
        return build_joint_kernel_workload(), {"attr_tweak": 1.0}
    from repro.kernels.workloads import build_kernel_workload
    return (build_kernel_workload(name, time_mode="static"),
            {"attr_tweak": 1.0})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="twofc", choices=WORKLOADS)
    ap.add_argument("--engine", default="python",
                    choices=("python", "tensor"),
                    help="tensor = device-mesh island fleet (kernel "
                         "workloads only; see DESIGN.md Tensorized search)")
    ap.add_argument("--islands", type=int, default=4)
    ap.add_argument("--generations", type=int, default=6)
    ap.add_argument("--pop", type=int, default=8,
                    help="population size per island")
    ap.add_argument("--migrate-every", type=int, default=2)
    ap.add_argument("--migrants", type=int, default=2)
    ap.add_argument("--topology", default="ring", choices=TOPOLOGIES)
    ap.add_argument("--processes", default="off",
                    choices=("auto", "on", "off"))
    ap.add_argument("--root", default=None,
                    help="state directory (default: fresh temp dir)")
    ap.add_argument("--export-front", default=None, metavar="PATH",
                    help="write the merged Pareto front as a deployable "
                         "front doc (ParetoFront.load / the deploy CLI)")
    ap.add_argument("--resume", action="store_true",
                    help="continue a killed run from --root")
    ap.add_argument("--surrogate", action="store_true",
                    help="surrogate pre-rank on every island: a cost model "
                         "trained from the shared fitness cache keeps only "
                         "the predicted-Pareto slice of each generation's "
                         "offspring")
    ap.add_argument("--surrogate-keep", type=float, default=0.5,
                    help="fraction of generated offspring the surrogate "
                         "lets through (default 0.5)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.resume and not args.root:
        ap.error("--resume requires --root")
    if args.surrogate and args.engine == "tensor":
        ap.error("--surrogate drives the python island engine; for the "
                 "tensor engine use TensorGevoML(surrogate=True) directly")
    if args.engine == "tensor" and args.workload not in KERNELS:
        ap.error("--engine tensor needs a kernel-schedule workload "
                 f"({', '.join(KERNELS)})")

    print(f"Building {args.workload} workload...")
    w, operators = build_workload(args.workload)
    t0, e0 = w.evaluate(w.program)
    print(f"  original fitness: time={t0:.3e}s  error={e0:.4f}")

    if args.engine == "tensor":
        processes, eval_workers = False, 0
        print("  engine: tensor (vmapped mesh fleet, no island processes)")
    elif args.processes == "auto":
        p = plan(args.islands)
        processes, eval_workers = p.processes, p.eval_workers
        print(f"  core plan: {p.describe()}")
    else:
        processes, eval_workers = args.processes == "on", 0
    if processes and getattr(w, "spec", None) is None:
        print("  (workload has no WorkloadSpec; falling back to "
              "in-process islands)")
        processes = False

    specs = default_island_specs(args.islands, operators=operators,
                                 base_seed=args.seed)
    root = args.root or tempfile.mkdtemp(prefix="gevo_islands_")
    print(f"\n{args.islands} islands (pop {args.pop} each), "
          f"{args.generations} generations, migrate every "
          f"{args.migrate_every} ({args.topology}, {args.migrants} "
          f"migrants), state in {root}")
    for s in specs:
        ops = s.operators if isinstance(s.operators, str) else \
            ",".join((s.operators or {"all": 1}).keys())
        print(f"  {s.name}: operators={ops} mut={s.mutation_rate} "
              f"seed={s.seed}")

    orch = IslandOrchestrator(
        w, root_dir=root, specs=specs, pop_size=args.pop,
        migrate_every=args.migrate_every, n_migrants=args.migrants,
        topology=args.topology, processes=processes,
        eval_workers=eval_workers, verbose=True,
        backend="mesh" if args.engine == "tensor" else "processes",
        surrogate=args.surrogate, surrogate_keep=args.surrogate_keep)
    res = orch.run(generations=args.generations, resume=args.resume)

    print("\nMerged Pareto front (argmin(time, error)):")
    for ind, src in zip(res.pareto, res.pareto_sources):
        t, e = ind.fitness
        mark = f"  time -{(1 - t / t0) * 100:.1f}%" if t < t0 * 0.999 else ""
        print(f"  time={t:.3e}  err={e:.4f}  [{src}]{mark}")
    moved = sum(len(v) for r in res.migration_log
                for v in r["migrants"].values())
    cs = res.cache_stats
    print(f"\n{len(res.migration_log)} migration rounds ({moved} migrants "
          f"moved); shared cache: {cs['entries']} unique genomes, "
          f"{res.cross_island_hits} cross-island hits")
    for name, isl in zip(res.names, res.islands):
        bt = min(i.fitness[0] for i in isl.pareto)
        be = min(i.fitness[1] for i in isl.pareto)
        ev = cs["per_island"].get(name, {})
        print(f"  {name}: best time={bt:.3e} best err={be:.4f} "
              f"evals={ev.get('n_evals', '?')} "
              f"cross_hits={ev.get('cross_hits', '?')}")
    if args.export_front:
        res.export_front(args.export_front, origin=root)
        print(f"\nexported merged front to {args.export_front} "
              f"(query it: python -m repro.core.deploy select "
              f"--front {args.export_front} --within 0.02)")
    print(f"\nresume any time with: --root {root} --resume")


if __name__ == "__main__":
    main()
