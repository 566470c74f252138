"""§Perf A/B measurements.

Select with ``--suite {cells,evaluator,operators,kernels,islands,serving,
tensor_evo,analysis,surrogate,liveloop,sharded_serving,all}``:

* ``cells`` (default) — for each hillclimbed model cell, measures (under the
  FINAL roofline analyzer, so numbers are comparable) the paper-faithful
  BASELINE configuration and each optimization step, writing
  experiments/perf/<cell>.json.  This is the machine-readable source for the
  EXPERIMENTS.md §Perf iteration log.

* ``evaluator`` — A/Bs the GEVO-ML evaluation engine on the 2fcNet search:
  SerialEvaluator vs ParallelEvaluator (``--workers N``) generation
  wall-clock, plus a warm-persistent-cache rerun; reports per-generation
  wall time, evaluation counts, and cache hit rates, writing
  experiments/perf/evaluator_ab.json.

* ``operators`` — A/Bs the edit-operator mix on the 2fcNet search: the
  legacy ``{copy, delete}`` pair vs. the full five-operator registry
  (``swap``/``insert``/``const_perturb`` added), same seed and budget;
  reports valid-candidate rate, evals/sec, final Pareto hypervolume, and the
  per-operator proposed/valid/elite counters, writing
  experiments/perf/operators_ab.json (results quoted in EXPERIMENTS.md).

* ``kernels`` — A/Bs kernel-schedule search on the Pallas kernels
  (rmsnorm, flash_attention, mamba_scan): a random-schedule baseline vs
  GEVO-evolved schedules under the same evaluation budget, same
  schedule-aware roofline fitness; reports best modeled time vs the shipped
  default schedule, writing experiments/perf/kernels_ab.json (results
  quoted in EXPERIMENTS.md).

* ``islands`` — A/Bs the island-model orchestrator on the 2fcNet search:
  1 island vs 4 heterogeneous islands (pop 8 each, fully-connected
  migration, one shared fitness cache) at an equal unique-genome budget;
  reports Pareto hypervolume, cross-island cache hits, and the migration
  log, writing experiments/perf/islands_ab.json (results quoted in
  EXPERIMENTS.md).

* ``serving`` — A/Bs the deployment layer end to end: evolves the
  continuous-batching engine's serving schedule under measured fitness,
  exports the winner through the ArtifactRegistry, resolves it back from
  disk, and re-measures the default schedule vs the evolved-artifact route
  on the same staggered request trace, writing
  experiments/perf/serving_ab.json (results quoted in EXPERIMENTS.md).

* ``tensor_evo`` — A/Bs the tensorized on-device engine against the Python
  engine on the joint three-kernel schedule space: population-evals/sec of
  ``TensorGevoML`` at pop 1024 vs ``GevoML(engine="python")``, then reruns
  the islands-vs-panmictic comparison at >= 100x the PR-4 genome budget
  (4 mesh islands x pop 1024 x 4 generations = 16384 genome-evals vs the
  original 140) against an equal-budget panmictic tensor run, writing
  experiments/perf/tensor_evo_ab.json (results quoted in EXPERIMENTS.md).

* ``analysis`` — A/Bs the static patch screen (``core.analysis``) on the
  2fcNet IR search and the joint three-kernel schedule search: the same
  seeded ``GevoML`` run with and without the pre-execution classifier, at an
  equal genome budget.  Asserts the exported Pareto fronts are
  byte-identical (screening must not change the search, only skip
  executions) and that >= 20% of cache-missing mutants resolve statically;
  reports the skip rate, screen-verdict histogram, and the per-operator
  invalid/noop/equivalent table, writing experiments/perf/analysis_ab.json
  (results quoted in EXPERIMENTS.md).

* ``surrogate`` — A/Bs the surrogate pre-rank (``core.surrogate``) on the
  joint three-kernel schedule search: the same seeded ``GevoML`` run
  unguided vs guided by the cache-trained cost model, at an equal genome
  budget.  The guided arm generates offspring at the normal rate but only
  the model's predicted-Pareto slice reaches the evaluator.  Asserts the
  guided front's hypervolume is >= 1.0x the unguided front's while the
  guided arm executes <= 70% of the unguided arm's evaluations; reports
  both fronts, the executed-evaluation counts, and the per-operator
  ranked/kept table, writing experiments/perf/surrogate_ab.json (results
  quoted in EXPERIMENTS.md).

* ``liveloop`` — closes the full evolve->serve->measure->promote loop on a
  synthesized bursty trace (``core.liveloop``): a background GevoML island
  evolves the serve schedule against replayed traffic, the canary state
  machine promotes the winner under measured guardrails, and the promoted
  artifact must re-measure at >= 1.0x the default schedule's throughput on
  the real engine; a second, fault-injected run must be rolled back and
  its fingerprint blocked.  Writes experiments/perf/liveloop_ab.json
  (results quoted in EXPERIMENTS.md).

* ``sharded_serving`` — A/Bs the full serving plan (engine schedule + KV
  memory plan + replica layout) on the multi-replica router: GevoML
  evolves the joint 432-point SERVE_SPACE under (modeled s/token, measured
  quantized-cache decode error), the deployment rule
  ``select("time", on="error", limit=KV_ERROR_GATE)`` picks the winner,
  and the artifact is rebuilt as a real Router and re-measured against the
  default plan (bar: >= 1.0x) plus the same plan pinned to one replica on
  a 2x2 smoke mesh (bar: router >= single).  Writes
  experiments/perf/sharded_serving_ab.json (results quoted in
  EXPERIMENTS.md).

  PYTHONPATH=src python -m benchmarks.perf_ab
  PYTHONPATH=src python -m benchmarks.perf_ab --suite evaluator --workers 2
  PYTHONPATH=src python -m benchmarks.perf_ab --suite operators
  PYTHONPATH=src python -m benchmarks.perf_ab --suite kernels
  PYTHONPATH=src python -m benchmarks.perf_ab --suite islands
  PYTHONPATH=src python -m benchmarks.perf_ab --suite serving
  PYTHONPATH=src python -m benchmarks.perf_ab --suite tensor_evo
  PYTHONPATH=src python -m benchmarks.perf_ab --suite liveloop
"""

from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from contextlib import contextmanager  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.dryrun import run_cell  # noqa: E402

OUT = "experiments/perf"


@contextmanager
def pinned_xla_host_devices(count: int = 512):
    """Pin ``XLA_FLAGS`` host-device-count for one suite, restoring the
    previous value afterwards.

    jax reads ``XLA_FLAGS`` exactly once, at first backend initialization,
    so a suite whose numerics depend on the device count (the surrogate
    A/B's roofline/VMEM feature probes see per-device shapes) must pin the
    flag *and verify the backend actually honors it* — if another suite
    already initialized jax at a different count, re-exporting the flag is
    silently ignored.  This guard makes that failure loud instead of a
    numbers drift, which is what makes suites order-independent (see
    EXPERIMENTS.md)."""
    prev = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={count}"
    try:
        import jax
        n = jax.device_count()
        if n != count:
            print(f"[xla] WARNING: backend already initialized with {n} "
                  f"host devices (wanted {count}); results may differ "
                  f"from an isolated run of this suite", flush=True)
        yield
    finally:
        if prev is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = prev


def run(tag: str, arch: str, shape: str, cfg, micro: int = 1) -> dict:
    path = os.path.join(OUT, f"{tag}.json")
    if os.path.exists(path):
        rec = json.load(open(path))
        if rec.get("status") == "ok":
            print(f"[cached] {tag}")
            return rec
    rec = run_cell(arch, shape, False, cfg_override=cfg, microbatches=micro)
    rec["tag"] = tag
    json.dump(rec, open(path, "w"), indent=1)
    rl = rec.get("roofline", {})
    print(f"[{rec['status']}] {tag}: step={rl.get('step_s', 0):.2f}s "
          f"dom={rl.get('dominant')} frac={rl.get('roofline_fraction', 0):.4f}")
    return rec


def _gen_walls(history: list[dict]) -> list[float]:
    walls, prev = [], 0.0
    for h in history:
        walls.append(h["wall_s"] - prev)
        prev = h["wall_s"]
    return [round(w, 4) for w in walls]


def evaluator_ab(workers: int = 2, generations: int = 4) -> dict:
    """Serial vs parallel vs warm-cache search wall-clock on one workload.

    All three runs use seed 0 in ``static`` fitness mode, so they evaluate
    the *same* variants and reach the same Pareto front — the A/B isolates
    the evaluation engine."""
    import tempfile

    from repro.core.evaluator import (FitnessCache, ParallelEvaluator,
                                      SerialEvaluator)
    from repro.core.search import GevoML
    from repro.workloads.twofc import build_twofc_training_workload

    w = build_twofc_training_workload(batch=32, hidden=64, steps=60,
                                      n_train=2048, n_test=1024)
    cache_path = os.path.join(tempfile.mkdtemp(prefix="gevoml_ab_"),
                              "fitness.jsonl")

    def measure(tag, make_ev):
        ev = make_ev()
        s = GevoML(w, pop_size=10, n_elite=5, seed=0, evaluator=ev)
        t0 = time.perf_counter()
        res = s.run(generations=generations)
        wall = time.perf_counter() - t0
        rec = {"wall_s": round(wall, 4),
               "gen_wall_s": _gen_walls(res.history),
               "n_evals": s.n_evals,
               "cache_hits": s.cache.hits,
               "cache_hit_rate": round(s.cache.hit_rate, 4),
               "pareto": [list(i.fitness) for i in res.pareto]}
        ev.close()
        print(f"[evaluator_ab] {tag}: wall={wall:.2f}s evals={s.n_evals} "
              f"hit_rate={s.cache.hit_rate:.0%}")
        return rec

    out = {
        "workers": workers,
        "generations": generations,
        "serial": measure(
            "serial", lambda: SerialEvaluator(w)),
        "parallel": measure(
            f"parallel x{workers}",
            lambda: ParallelEvaluator(w, n_workers=workers,
                                      cache=FitnessCache(cache_path))),
        # rerun against the persistent cache the parallel run just filled
        "parallel_warm_cache": measure(
            "parallel warm cache",
            lambda: ParallelEvaluator(w, n_workers=workers,
                                      cache=FitnessCache(cache_path))),
    }
    assert out["serial"]["pareto"] == out["parallel"]["pareto"], \
        "parallel evaluation diverged from serial (static mode must match)"
    out["speedup_parallel_vs_serial"] = round(
        out["serial"]["wall_s"] / max(out["parallel"]["wall_s"], 1e-9), 3)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "evaluator_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[evaluator_ab] wrote {path}; serial/parallel speedup="
          f"{out['speedup_parallel_vs_serial']}x, warm-cache evals="
          f"{out['parallel_warm_cache']['n_evals']}")
    return out


def operators_ab(generations: int = 6) -> dict:
    """Legacy {copy,delete} vs. full five-operator mix on the 2fcNet search.

    Same seed, same budget, ``static`` fitness: the A/B isolates the operator
    mix.  Pareto quality is compared by 2-D hypervolume against a reference
    point slightly worse than the original program's fitness."""
    from repro.core.edits import OperatorWeights
    from repro.core.evaluator import SerialEvaluator
    from repro.core.nsga2 import hypervolume_2d
    from repro.core.search import GevoML
    from repro.workloads.twofc import build_twofc_training_workload

    w = build_twofc_training_workload(batch=32, hidden=64, steps=60,
                                      n_train=2048, n_test=1024)
    to, eo = w.evaluate(w.program)
    ref = (to * 1.05, eo + 0.05)

    def measure(tag, weights):
        ev = SerialEvaluator(w)
        s = GevoML(w, pop_size=12, n_elite=6, seed=0, operators=weights,
                   evaluator=ev)
        t0 = time.perf_counter()
        res = s.run(generations=generations)
        wall = time.perf_counter() - t0
        outcomes = ev.n_evals  # executed variants (cache-missing candidates)
        # candidate validity = mutation proposals that applied cleanly
        # (apply failures are resampled parent-side and never reach the
        # evaluator, so evaluator-level invalids can't measure the mix)
        per_op = res.operator_stats()
        proposed = sum(r["proposed"] for r in per_op.values())
        applied = sum(r["applied"] for r in per_op.values())
        valid_rate = applied / max(proposed, 1)
        hv = hypervolume_2d([i.fitness for i in res.pareto], ref)
        rec = {"operators": list(weights.names()),
               "wall_s": round(wall, 4),
               "n_evals": outcomes,
               "evals_per_s": round(outcomes / max(wall, 1e-9), 2),
               "valid_candidate_rate": round(valid_rate, 4),
               "exec_invalid": ev.n_invalid,
               "pareto": [list(i.fitness) for i in res.pareto],
               "hypervolume": hv,
               "best_error": min(i.fitness[1] for i in res.pareto),
               "best_time": min(i.fitness[0] for i in res.pareto),
               "per_operator": per_op}
        ev.close()
        print(f"[operators_ab] {tag}: valid={valid_rate:.0%} "
              f"evals/s={rec['evals_per_s']} hv={hv:.3e} "
              f"best_err={rec['best_error']:.4f}")
        return rec

    out = {
        "generations": generations,
        "original_fitness": [to, eo],
        "hv_reference": list(ref),
        "legacy": measure("legacy {copy,delete}", OperatorWeights.legacy()),
        "full": measure("full five-operator mix",
                        OperatorWeights.all_registered()),
    }
    out["hv_ratio_full_vs_legacy"] = round(
        out["full"]["hypervolume"] / max(out["legacy"]["hypervolume"], 1e-30),
        3)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "operators_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[operators_ab] wrote {path}; hypervolume full/legacy="
          f"{out['hv_ratio_full_vs_legacy']}x")
    return out


def kernels_ab(generations: int = 6, seed: int = 0) -> dict:
    """Random-schedule baseline vs GEVO-evolved schedules per Pallas kernel.

    Both arms use the same ``static`` schedule-aware roofline fitness and the
    same evaluation budget (the random arm draws as many unique genomes as
    the evolved search executed), so the A/B isolates the search itself.
    ``best`` arms are the fastest schedule whose numerical error stays within
    the default schedule's error + 1e-3."""
    import numpy as np

    from repro.core.evaluator import SerialEvaluator
    from repro.kernels.workloads import (KERNELS, build_kernel_workload,
                                         evolve_kernel_schedule)

    out: dict = {"generations": generations, "kernels": {}}
    for kernel in KERNELS:
        w = build_kernel_workload(kernel, time_mode="static")

        # distinct patches can decode to the same genome, so the fair budget
        # for the random arm is unique *genomes* the evolved search executed
        genomes_seen: set = set()
        inner_runner = w.runner

        def counting_runner(g, _inner=inner_runner, _seen=genomes_seen):
            _seen.add(tuple(sorted(g.items())))
            return _inner(g)

        w.runner = counting_runner
        ev = SerialEvaluator(w)
        t0 = time.perf_counter()
        s, res, best, within_tol = evolve_kernel_schedule(
            w, generations=generations, seed=seed, evaluator=ev)
        wall = time.perf_counter() - t0
        t_def, e_def = res.original_fitness  # the engine's baseline eval
        tol = e_def + 1e-3
        if not within_tol:
            print(f"[kernels_ab] {kernel}: WARNING no evolved schedule "
                  f"within error tolerance; reporting fastest outright")
        evolved = {
            "wall_s": round(wall, 4),
            "n_evals": ev.n_evals,
            "n_genomes": len(genomes_seen),
            "within_tol": within_tol,
            "cache_hit_rate": round(s.cache.hit_rate, 4),
            "best_time": best.fitness[0],
            "best_error": best.fitness[1],
            "best_schedule": w.space.decode(best.patch.apply(w.program)),
        }

        rng = np.random.default_rng(seed)
        t0 = time.perf_counter()
        rand_best, seen = None, set()
        budget = min(len(genomes_seen), w.space.size())
        while len(seen) < budget:
            g = w.space.random(rng)
            key = tuple(sorted(g.items()))
            if key in seen:
                continue
            seen.add(key)
            try:
                t, e = w.runner(g)
            except Exception:
                continue
            if e <= tol and (rand_best is None or t < rand_best[0]):
                rand_best = (t, e, g)
        random_arm = {
            "wall_s": round(time.perf_counter() - t0, 4),
            "n_evals": len(seen),
            "best_time": rand_best[0] if rand_best else None,
            "best_error": rand_best[1] if rand_best else None,
            "best_schedule": rand_best[2] if rand_best else None,
        }
        ev.close()

        rec = {"default": {"time": t_def, "error": e_def,
                           "schedule": w.space.decode(w.program)},
               "evolved": evolved, "random": random_arm,
               "evolved_vs_default": round(t_def / evolved["best_time"], 3),
               "evolved_vs_random": (
                   round(random_arm["best_time"] / evolved["best_time"], 3)
                   if rand_best else None)}
        out["kernels"][kernel] = rec
        rand_txt = (f"{random_arm['best_time']:.3e}s"
                    if rand_best else "none-within-tol")
        print(f"[kernels_ab] {kernel}: default={t_def:.3e}s "
              f"evolved={evolved['best_time']:.3e}s "
              f"random={rand_txt} "
              f"speedup_vs_default={rec['evolved_vs_default']}x "
              f"vs_random={rec['evolved_vs_random']}x")

    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "kernels_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[kernels_ab] wrote {path}")
    return out


def islands_ab(generations: int = 6, seed: int = 0) -> dict:
    """1 island vs 4 heterogeneous islands at an equal unique-genome budget
    on the 2fcNet search.

    The islands arm (4 islands × pop 8, heterogeneous operator palette,
    fully-connected migration every 2 generations, one shared persistent
    cache) runs first and sets the budget: the number of unique genomes it
    executed (shared-cache entries — cross-island duplicates count once).
    The baseline is ONE island of the same configuration (pop 8, the
    default "all" mix, same engine) run generation by generation until its
    unique-genome count reaches at least that budget — the single
    population never sees *fewer* genomes than the fleet did.  Pareto
    quality is 2-D hypervolume against a reference slightly worse than the
    original program's fitness."""
    import tempfile

    from repro.core import IslandOrchestrator
    from repro.core.evaluator import SerialEvaluator
    from repro.core.nsga2 import hypervolume_2d
    from repro.core.search import GevoML
    from repro.workloads.twofc import build_twofc_training_workload

    w = build_twofc_training_workload(batch=32, hidden=64, steps=60,
                                      n_train=2048, n_test=1024)
    to, eo = w.evaluate(w.program)
    ref = (to * 1.05, eo + 0.05)
    n_islands, pop_island = 4, 8

    root = tempfile.mkdtemp(prefix="gevoml_islands_ab_")
    orch = IslandOrchestrator(w, root_dir=root, n_islands=n_islands,
                              pop_size=pop_island, migrate_every=2,
                              n_migrants=2, topology="full")
    t0 = time.perf_counter()
    res = orch.run(generations=generations)
    wall_islands = time.perf_counter() - t0
    budget = res.cache_stats["entries"]
    hv_islands = hypervolume_2d([i.fitness for i in res.pareto], ref)
    islands_rec = {
        "n_islands": n_islands, "pop_per_island": pop_island,
        "topology": "full", "migrate_every": 2, "n_migrants": 2,
        "generations": generations,
        "wall_s": round(wall_islands, 4),
        "unique_genomes": budget,
        "migration_rounds": len(res.migration_log),
        "cross_island_hits": res.cross_island_hits,
        "pareto": [list(i.fitness) for i in res.pareto],
        "pareto_sources": res.pareto_sources,
        "hypervolume": hv_islands,
        "per_island": res.cache_stats["per_island"],
    }
    print(f"[islands_ab] islands: {budget} unique genomes, "
          f"hv={hv_islands:.3e}, "
          f"{islands_rec['cross_island_hits']} cross-island hits")

    # -- one-island baseline: run until it has seen >= `budget` genomes ----
    ck = tempfile.mkdtemp(prefix="gevoml_islands_ab_single_")
    ev = SerialEvaluator(w)
    s = GevoML(w, pop_size=pop_island, n_elite=pop_island // 2, seed=seed,
               evaluator=ev, checkpoint_dir=ck)

    class _BudgetReached(Exception):
        pass

    def stop_when_budget(gen, row):
        if len(ev.cache) >= budget:
            raise _BudgetReached

    t0 = time.perf_counter()
    try:
        s.run(generations=generations * 16, on_generation=stop_when_budget)
    except _BudgetReached:
        pass
    wall_single = time.perf_counter() - t0
    last_gen = json.load(open(os.path.join(ck, "latest.json")))["gen"]
    r_single = s.run(generations=last_gen + 1, resume=True)  # no-op replay
    ev.close()
    hv_single = hypervolume_2d([i.fitness for i in r_single.pareto], ref)
    single_rec = {
        "pop_size": pop_island,
        "generations_run": last_gen + 1,
        "wall_s": round(wall_single, 4),
        "unique_genomes": len(ev.cache),
        "pareto": [list(i.fitness) for i in r_single.pareto],
        "hypervolume": hv_single,
    }
    print(f"[islands_ab] single island: {single_rec['unique_genomes']} "
          f"unique genomes over {last_gen + 1} generations, "
          f"hv={hv_single:.3e}")

    out = {
        "generations": generations,
        "original_fitness": [to, eo],
        "hv_reference": list(ref),
        "islands": islands_rec,
        "single": single_rec,
        "hv_ratio_islands_vs_single": round(
            hv_islands / max(hv_single, 1e-30), 3),
    }
    # the acceptance bar for the island orchestrator (see EXPERIMENTS.md):
    # equal-budget heterogeneous islands must not lose to one population,
    # and the shared cache must actually be shared
    assert islands_rec["cross_island_hits"] >= 1, \
        "shared cache reported no cross-island hits"
    assert hv_islands >= hv_single, \
        (f"islands hypervolume {hv_islands:.3e} fell below the "
         f"single-population baseline {hv_single:.3e}")
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "islands_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[islands_ab] wrote {path}; hypervolume islands/single="
          f"{out['hv_ratio_islands_vs_single']}x at >= equal budget")
    return out


def serving_ab(generations: int = 2, seed: int = 0,
               artifacts_dir: str = "experiments/artifacts") -> dict:
    """Default serving schedule vs an evolved serving artifact on the
    continuous-batching engine.

    The evolved arm is produced the way a deployment would produce it:
    ``GevoML`` (attr_tweak over the serve schedule space) searches engine
    schedules under *measured* ``(s/token, mean latency)`` fitness on a
    fixed staggered request trace, the fastest Pareto member is exported to
    the :class:`ArtifactRegistry`, and the A/B re-measures both routes from
    a fresh engine with the artifact **resolved back from disk** — the
    GEVO validate-winners-in-the-target-application loop.  Serving latency
    records are published into a shared FitnessCache under the ``serve``
    writer tag alongside the search's own records."""
    import statistics
    import tempfile

    from repro.configs import smoke_config
    from repro.core import GevoML
    from repro.core.deploy import (DEFAULT_ENGINE_SCHEDULE, Artifact,
                                   ArtifactRegistry, ServeEngine,
                                   engine_schedule_from, build_serve_workload)
    from repro.core.evaluator import FitnessCache, SerialEvaluator
    from repro.core.liveloop.traces import demo_requests

    arch = "qwen3-0.6b"
    trace_cfg = dict(n_requests=12, prompt_len=8, gen=8)
    stagger = 4
    w = build_serve_workload(arch, smoke=True, stagger=stagger, seed=seed,
                             **trace_cfg)
    cfg = smoke_config(arch)
    cache_path = os.path.join(tempfile.mkdtemp(prefix="gevoml_serving_ab_"),
                              "fitness.jsonl")

    # -- evolve the serving schedule under measured fitness -----------------
    ev = SerialEvaluator(w, cache=FitnessCache(cache_path, writer="search"))
    s = GevoML(w, pop_size=6, n_elite=3, seed=seed, init_mutations=2,
               mutation_rate=0.9, operators={"attr_tweak": 1.0},
               evaluator=ev)
    t0 = time.perf_counter()
    res = s.run(generations=generations)
    wall_search = time.perf_counter() - t0
    best = res.best_by_time()
    best_genome = w.space.decode(best.patch.apply(w.program))

    # -- ship it: export the winner, resolve it back ------------------------
    registry = ArtifactRegistry(artifacts_dir)
    art_path = registry.export(Artifact(
        kind="serve", name=cfg.name, shape="smoke",
        genome=best_genome, fitness=best.fitness,
        meta={"rule": "min s_per_token (measured)", "trace": trace_cfg,
              "stagger": stagger, "suite": "serving_ab"}))
    resolved = registry.resolve(cfg.name, "smoke", kind="serve")
    evolved_schedule = engine_schedule_from(resolved)

    # -- re-measure both routes from fresh engines --------------------------
    def measure(tag, schedule, publish=False):
        runs = []
        for rep in range(3):
            engine = ServeEngine(cfg, max_len=trace_cfg["prompt_len"]
                                 + trace_cfg["gen"],
                                 max_slots=schedule["max_slots"],
                                 prefill_chunk=schedule["prefill_chunk"])
            engine.run(demo_requests(cfg, seed=seed, **trace_cfg),
                       stagger=stagger)
            stats = engine.stats()
            if publish and rep == 0:
                cache = FitnessCache(cache_path, writer="serve")
                engine.publish_stats(cache, name=cfg.name,
                                     shape={"schedule": tag, **trace_cfg})
                cache.close()
            runs.append(stats)
        med = statistics.median(r["throughput_tok_s"] for r in runs)
        rec = {"schedule": schedule,
               "throughput_tok_s": med,
               "runs_tok_s": [r["throughput_tok_s"] for r in runs],
               **{k: runs[0][k] for k in ("mean_latency_s",
                                          "p95_latency_s", "mean_ttft_s",
                                          "s_per_token")},
               "decode_batches": runs[0]["decode_batches"]}
        print(f"[serving_ab] {tag}: {schedule} -> {med:.1f} tok/s "
              f"(runs {rec['runs_tok_s']})")
        return rec

    default_rec = measure("default", dict(DEFAULT_ENGINE_SCHEDULE),
                          publish=True)
    evolved_rec = measure("evolved", evolved_schedule, publish=True)
    ev.close()

    n_serve_records = sum(
        1 for line in open(cache_path)
        if json.loads(line).get("writer") == "serve")
    out = {
        "arch": cfg.name, "trace": trace_cfg, "stagger": stagger,
        "generations": generations,
        "search": {"wall_s": round(wall_search, 2), "n_evals": s.n_evals,
                   "space_size": w.space.size(),
                   "best_genome": best_genome,
                   "best_fitness": list(best.fitness),
                   "default_fitness": list(res.original_fitness)},
        "artifact": {"path": art_path,
                     "fingerprint": resolved.fingerprint()},
        "default": default_rec,
        "evolved": evolved_rec,
        "throughput_ratio_evolved_vs_default": round(
            evolved_rec["throughput_tok_s"]
            / max(default_rec["throughput_tok_s"], 1e-9), 3),
        "serve_cache_records": n_serve_records,
    }
    # the acceptance bar: the evolved-artifact route must not lose to the
    # default schedule on the trace it was evolved for, and serving must
    # have fed latency records back into the shared cache
    assert n_serve_records >= 2, "no serve-tagged records in the cache"
    assert out["throughput_ratio_evolved_vs_default"] >= 1.0, \
        (f"evolved serving artifact lost to the default schedule "
         f"({evolved_rec['throughput_tok_s']:.1f} vs "
         f"{default_rec['throughput_tok_s']:.1f} tok/s)")
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "serving_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[serving_ab] wrote {path}; evolved/default throughput="
          f"{out['throughput_ratio_evolved_vs_default']}x "
          f"({n_serve_records} serve-tagged cache records)")
    return out


def tensor_evo_ab(seed: int = 0, pop: int = 1024,
                  throughput_gens: int = 8) -> dict:
    """The tensorized on-device engine vs the Python engine on the joint
    three-kernel schedule space, plus the islands-vs-panmictic A/B rerun at
    >= 100x the PR-4 genome budget.

    Throughput arm: ``TensorGevoML`` (pop 1024) computes fitness for every
    population lane in one jitted array program per generation;
    ``GevoML(engine="python")`` evaluates per genome through the serial
    evaluator (memoized, so its metric counts *executed* evaluations —
    the favorable accounting for the Python arm).  Both numbers are
    fitness-assignments/sec on the same workload.

    Budget arm: 4 mesh islands x pop 1024 x 4 generations = 16384
    genome-evals (PR 4's islands_ab executed 140 unique genomes, so this is
    >= 100x that budget) vs one panmictic tensor population of 4096 at the
    same generation count.  Pareto quality is 2-D hypervolume against a
    reference slightly worse than the default schedule's fitness."""
    import tempfile

    from repro.core.nsga2 import hypervolume_2d
    from repro.core.search import GevoML
    from repro.core.tensor_evo import TensorGevoML, TensorIslandFleet
    from repro.kernels.workloads import build_joint_kernel_workload

    w = build_joint_kernel_workload()
    to, eo = w.evaluate(w.program)
    ref = (to * 1.05, eo + 0.05)

    # -- throughput: population-evals/sec, tensor vs python engine ---------
    t0 = time.perf_counter()
    eng = TensorGevoML(w, pop_size=pop, n_elite=32, seed=seed)
    res_t = eng.run(generations=throughput_gens, record_cache=False)
    wall_t = time.perf_counter() - t0
    evals_t = res_t.history[-1]["evals"]
    tensor_rec = {
        "pop_size": pop, "generations": throughput_gens,
        "wall_s": round(wall_t, 4), "population_evals": evals_t,
        "evals_per_s": round(evals_t / max(wall_t, 1e-9), 2),
        "pareto": sorted(list(i.fitness) for i in res_t.pareto),
        "hypervolume": hypervolume_2d(
            [i.fitness for i in res_t.pareto], ref),
    }
    print(f"[tensor_evo_ab] tensor engine: {evals_t} population-evals in "
          f"{wall_t:.2f}s = {tensor_rec['evals_per_s']}/s")

    py_pop, py_gens = 64, 2
    s = GevoML(w, engine="python", pop_size=py_pop, n_elite=16, seed=seed,
               operators={"attr_tweak": 1.0})
    t0 = time.perf_counter()
    res_p = s.run(generations=py_gens)
    wall_p = time.perf_counter() - t0
    python_rec = {
        "pop_size": py_pop, "generations": py_gens,
        "wall_s": round(wall_p, 4), "executed_evals": s.n_evals,
        "evals_per_s": round(s.n_evals / max(wall_p, 1e-9), 2),
        "hypervolume": hypervolume_2d(
            [i.fitness for i in res_p.pareto], ref),
    }
    print(f"[tensor_evo_ab] python engine: {s.n_evals} executed evals in "
          f"{wall_p:.2f}s = {python_rec['evals_per_s']}/s")
    speedup = round(tensor_rec["evals_per_s"]
                    / max(python_rec["evals_per_s"], 1e-9), 2)

    # -- 100x-budget islands vs panmictic at equal lane budget -------------
    n_isl, ipop, igens = 4, pop, 4
    genome_evals = n_isl * ipop * igens
    root = tempfile.mkdtemp(prefix="tensor_islands_ab_")
    t0 = time.perf_counter()
    with TensorIslandFleet(w, root_dir=root, n_islands=n_isl, pop_size=ipop,
                           n_elite=32, migrate_every=2, n_migrants=8,
                           topology="full", seed=seed) as fleet:
        res_i = fleet.run(igens)
    wall_i = time.perf_counter() - t0
    hv_islands = hypervolume_2d([i.fitness for i in res_i.pareto], ref)
    islands_rec = {
        "n_islands": n_isl, "pop_per_island": ipop, "generations": igens,
        "topology": "full", "migrate_every": 2, "n_migrants": 8,
        "wall_s": round(wall_i, 4),
        "genome_evals": genome_evals,
        "unique_genomes": res_i.cache_stats["entries"],
        "migration_rounds": len(res_i.migration_log),
        "cross_island_hits": res_i.cross_island_hits,
        "writer_tags": res_i.cache_stats["writer_tags"],
        "hypervolume": hv_islands,
    }
    print(f"[tensor_evo_ab] mesh islands: {genome_evals} genome-evals "
          f"({islands_rec['unique_genomes']} unique) in {wall_i:.2f}s, "
          f"hv={hv_islands:.3e}, "
          f"{islands_rec['cross_island_hits']} cross-island hits")

    t0 = time.perf_counter()
    pan = TensorGevoML(w, pop_size=n_isl * ipop, n_elite=32, seed=seed)
    res_pan = pan.run(generations=igens, record_cache=False)
    wall_pan = time.perf_counter() - t0
    hv_pan = hypervolume_2d([i.fitness for i in res_pan.pareto], ref)
    pan_rec = {
        "pop_size": n_isl * ipop, "generations": igens,
        "wall_s": round(wall_pan, 4),
        "genome_evals": res_pan.history[-1]["evals"],
        "hypervolume": hv_pan,
    }
    print(f"[tensor_evo_ab] panmictic: {pan_rec['genome_evals']} "
          f"genome-evals in {wall_pan:.2f}s, hv={hv_pan:.3e}")

    out = {
        "workload": w.name,
        "space_size": w.space.size(),
        "original_fitness": [to, eo],
        "hv_reference": list(ref),
        "tensor": tensor_rec,
        "python": python_rec,
        "speedup_tensor_vs_python": speedup,
        "pr4_genome_budget": 140,
        "budget_ratio_vs_pr4": round(genome_evals / 140, 1),
        "islands": islands_rec,
        "panmictic": pan_rec,
        "hv_ratio_islands_vs_panmictic": round(
            hv_islands / max(hv_pan, 1e-30), 3),
    }
    # the acceptance bars (see ISSUE/EXPERIMENTS.md): the tensorized engine
    # must clear 10x the Python engine's eval throughput, the budget must be
    # >= 100x PR 4's 140-genome islands_ab, and the mesh fleet's shared
    # cache must actually be shared
    assert speedup >= 10, \
        f"tensor engine speedup {speedup}x fell below the 10x bar"
    assert genome_evals >= 14000, \
        f"budget {genome_evals} below 100x the PR-4 run (14000)"
    assert islands_rec["cross_island_hits"] >= 1, \
        "mesh shared cache reported no cross-island hits"
    assert hv_islands >= 0.99 * hv_pan, \
        (f"mesh islands hypervolume {hv_islands:.3e} fell below the "
         f"panmictic baseline {hv_pan:.3e}")
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "tensor_evo_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[tensor_evo_ab] wrote {path}; tensor/python throughput="
          f"{speedup}x, islands/panmictic hv="
          f"{out['hv_ratio_islands_vs_panmictic']}x at "
          f"{out['budget_ratio_vs_pr4']}x the PR-4 budget")
    return out


def analysis_ab(generations: int = 12, seed: int = 0) -> dict:
    """Screened vs unscreened ``GevoML`` — same seed, same budget, byte-
    identical exported Pareto fronts; the A/B isolates the static screen.

    Two searches: the 2fcNet IR search (program screen: DCE + constant
    folding + canonical fingerprints) and the joint three-kernel schedule
    search (kernel screen: decode + launch gates + genome canon).  Both run
    in ``static`` fitness mode, where verdict inheritance is exact, so the
    screened arm must reproduce the unscreened arm's front byte for byte
    while skipping the executions the screen resolved."""
    import tempfile

    from repro.core.evaluator import SerialEvaluator
    from repro.core.search import GevoML
    from repro.kernels.workloads import build_joint_kernel_workload
    from repro.workloads.twofc import build_twofc_training_workload

    root = tempfile.mkdtemp(prefix="gevoml_analysis_ab_")

    def arm(tag, workload, *, screen, gens, **gevo_kw):
        ev = SerialEvaluator(workload)
        s = GevoML(workload, seed=seed, evaluator=ev, screen=screen,
                   **gevo_kw)
        t0 = time.perf_counter()
        res = s.run(generations=gens)
        wall = time.perf_counter() - t0
        front_path = os.path.join(root, f"{tag}.json")
        res.export_front(front_path)
        st = ev.stats()
        rec = {"wall_s": round(wall, 4),
               "n_evals": st["n_evals"],
               "n_screened": st["n_screened"],
               "screened_by": st["screened_by"],
               "pareto": sorted(list(i.fitness) for i in res.pareto),
               "population": [list(i.fitness) for i in res.population],
               "per_operator": res.operator_stats()}
        ev.close()
        return rec, front_path

    out: dict = {"generations": generations, "seed": seed, "searches": {}}
    searches = {
        "twofc": (build_twofc_training_workload(
                      batch=32, hidden=16, steps=5,
                      n_train=256, n_test=200),
                  dict(pop_size=10, n_elite=5)),
        "joint_kernels": (build_joint_kernel_workload(),
                          dict(pop_size=10, n_elite=5, init_mutations=2,
                               mutation_rate=0.9,
                               operators={"attr_tweak": 1.0})),
    }
    tot_screened = tot_missed = 0
    for name, (w, kw) in searches.items():
        base, base_front = arm(f"{name}_unscreened", w, screen=False,
                               gens=generations, **kw)
        scr, scr_front = arm(f"{name}_screened", w, screen=True,
                             gens=generations, **kw)
        front_equal = (open(base_front, "rb").read()
                       == open(scr_front, "rb").read())
        # the bit-exactness bar: identical exported front BYTES and
        # identical final population fitness, at the same genome budget
        assert front_equal, \
            f"{name}: screened front diverged from unscreened"
        assert base["population"] == scr["population"], \
            f"{name}: screened population fitness diverged"
        missed = scr["n_evals"] + scr["n_screened"]
        skip = scr["n_screened"] / max(missed, 1)
        tot_screened += scr["n_screened"]
        tot_missed += missed
        out["searches"][name] = {
            "unscreened": {k: base[k] for k in
                           ("wall_s", "n_evals", "pareto")},
            "screened": {k: scr[k] for k in
                         ("wall_s", "n_evals", "n_screened", "screened_by",
                          "pareto", "per_operator")},
            "front_bytes_equal": front_equal,
            "executions_skipped": base["n_evals"] - scr["n_evals"],
            "skip_rate": round(skip, 4),
        }
        print(f"[analysis_ab] {name}: fronts byte-equal; "
              f"{base['n_evals']} evals unscreened vs {scr['n_evals']} "
              f"screened ({scr['n_screened']} resolved statically, "
              f"skip rate {skip:.0%}, verdicts {scr['screened_by']})")
    out["skip_rate_overall"] = round(tot_screened / max(tot_missed, 1), 4)
    # the acceptance bar (see ISSUE/EXPERIMENTS.md): fronts byte-identical
    # (asserted above) and >= 20% of proposed cache-missing mutants
    # resolved without execution
    assert out["skip_rate_overall"] >= 0.20, \
        (f"static screen resolved only {out['skip_rate_overall']:.0%} of "
         f"cache-missing mutants (bar: 20%)")
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "analysis_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[analysis_ab] wrote {path}; fronts byte-identical, overall "
          f"skip rate {out['skip_rate_overall']:.0%}")
    return out


def surrogate_ab(generations: int = 10, seed: int = 5,
                 keep: float = 0.5) -> dict:
    """Surrogate-guided vs unguided ``GevoML`` on the joint three-kernel
    schedule search — same seed, same genome budget.  The guided arm
    generates offspring at the normal rate, featurizes each cache-missing
    candidate (schedule one-hots + roofline/VMEM counters), and lets the
    ridge cost model trained from the run's own FitnessCache pick the
    predicted-Pareto slice that actually reaches the evaluator.  The bar
    (see ISSUE/EXPERIMENTS.md): guided hypervolume >= 1.0x unguided while
    executing <= 70% of the unguided arm's evaluations.

    The feature probes' VMEM/roofline numbers depend on the XLA host
    device count, so the whole suite runs under
    :func:`pinned_xla_host_devices` — order-independent of whatever suite
    ran (and initialized jax) before it."""
    with pinned_xla_host_devices(512):
        return _surrogate_ab_body(generations, seed, keep)


def _surrogate_ab_body(generations: int, seed: int, keep: float) -> dict:
    from repro.core.evaluator import SerialEvaluator
    from repro.core.nsga2 import hypervolume_2d
    from repro.core.search import GevoML
    from repro.kernels.workloads import build_joint_kernel_workload

    w = build_joint_kernel_workload()
    to, eo = w.evaluate(w.program)
    ref = (to * 1.05, eo + 0.05)
    kw = dict(pop_size=10, n_elite=5, init_mutations=2, mutation_rate=0.9,
              operators={"attr_tweak": 1.0})

    def arm(tag, *, surrogate):
        ev = SerialEvaluator(w)
        s = GevoML(w, seed=seed, evaluator=ev, surrogate=surrogate,
                   surrogate_keep=keep, **kw)
        t0 = time.perf_counter()
        res = s.run(generations=generations)
        wall = time.perf_counter() - t0
        rec = {"wall_s": round(wall, 4),
               "executed_evals": ev.stats()["n_evals"],
               "hypervolume": hypervolume_2d(
                   [i.fitness for i in res.pareto], ref),
               "pareto": sorted(list(i.fitness) for i in res.pareto)}
        if surrogate:
            rec["surrogate"] = s.guide.stats()
            rec["per_operator"] = res.operator_stats()
        ev.close()
        print(f"[surrogate_ab] {tag}: {rec['executed_evals']} executed "
              f"evals, hypervolume {rec['hypervolume']:.3e}")
        return rec

    base = arm("unguided", surrogate=False)
    guided = arm("guided", surrogate=True)
    hv_ratio = guided["hypervolume"] / max(base["hypervolume"], 1e-30)
    exec_frac = guided["executed_evals"] / max(base["executed_evals"], 1)
    out = {"generations": generations, "seed": seed, "keep": keep,
           "ref_point": list(ref),
           "unguided": base, "guided": guided,
           "hv_ratio_guided_vs_unguided": round(hv_ratio, 4),
           "executed_frac_guided_vs_unguided": round(exec_frac, 4)}
    # the acceptance bar: no Pareto-quality regression at a real
    # execution saving
    assert hv_ratio >= 1.0, \
        (f"guided hypervolume fell to {hv_ratio:.3f}x unguided "
         f"(bar: >= 1.0x)")
    assert exec_frac <= 0.70, \
        (f"guided arm executed {exec_frac:.0%} of the unguided arm's "
         f"evaluations (bar: <= 70%)")
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "surrogate_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[surrogate_ab] wrote {path}; hypervolume guided/unguided="
          f"{hv_ratio:.2f}x at {exec_frac:.0%} of the executions")
    return out


def liveloop_ab(ticks: int = 3, seed: int = 0) -> dict:
    """The full live loop, both exits of the state machine.

    **Promote arm** (real engine): a :class:`LiveLoopController` in
    ``mode="real"`` evolves the serve schedule against a synthesized
    bursty trace replayed through actual :class:`ServeEngine` instances,
    canaries the winner by shadow-replaying a deterministic trace slice
    under both schedules, and promotes it through the journaled
    guardrails.  The promoted artifact is then
    re-measured from scratch (median of 3 full-trace replays) against the
    default schedule — the bar is throughput >= 1.0x default.

    **Rollback arm** (modeled, fault-injected): the same trace under the
    deterministic engine model, with a fault hook tripling every canary
    measurement's latency — the guardrails must roll the candidate back,
    block its fingerprint, and never re-propose it."""
    import statistics
    import tempfile

    from repro.configs import smoke_config
    from repro.core.deploy import DEFAULT_ENGINE_SCHEDULE, ServeEngine
    from repro.core.liveloop import (Guardrails, LiveLoopController, replay,
                                     synthesize)

    arch = "qwen3-0.6b"
    cfg = smoke_config(arch)
    trace = synthesize("bursty", vocab=cfg.vocab, n_requests=10,
                       max_prompt=8, gen=6, seed=seed)
    print(f"[liveloop_ab] trace: {trace.summary()}")

    # -- promote arm: real measured loop ------------------------------------
    root = tempfile.mkdtemp(prefix="liveloop_ab_")
    # pop 10 over the 12-point schedule space all but enumerates it, and
    # the canary gate tolerates 5% run-to-run measurement noise (both
    # sides shadow-replay the same slice, so there is no cross-slice
    # composition noise) -- the hard >= 1.0x bar is the from-scratch
    # re-measure below
    ctl = LiveLoopController(root, trace=trace, arch=arch, mode="real",
                             gens_per_tick=2, pop=10, seed=seed,
                             fraction=0.5,
                             guardrails=Guardrails(
                                 min_throughput_ratio=0.95, windows=2))
    t0 = time.perf_counter()
    summaries = ctl.run(ticks)
    wall_loop = time.perf_counter() - t0
    for s in summaries:
        print(f"[liveloop_ab] tick {s['tick']}: cand={s['candidate']} "
              f"outcome={s['outcome'] or 'pending'}")
    promoted = ctl.book.promoted
    assert promoted is not None, \
        f"no promotion after {ticks} ticks: {ctl.book.status()}"
    live = ctl.registry.resolve(arch, "live", kind="serve")
    assert live is not None and live.genome == promoted["genome"], \
        "registry live pointer does not match the journaled promotion"

    # -- re-measure the promoted schedule from scratch ----------------------
    params = ctl._model()[1]

    def measure(schedule):
        runs = []
        for i in range(4):
            engine = ServeEngine(cfg, params, max_len=trace.max_len(),
                                 max_slots=schedule["max_slots"],
                                 prefill_chunk=schedule["prefill_chunk"])
            replay(engine, trace)
            if i == 0:      # unmeasured warmup: XLA compiles stay out
                continue
            runs.append(engine.stats()["throughput_tok_s"])
        return statistics.median(runs), runs

    thr_default, runs_default = measure(dict(DEFAULT_ENGINE_SCHEDULE))
    thr_live, runs_live = measure(dict(live.genome))
    ratio = round(thr_live / max(thr_default, 1e-9), 3)
    print(f"[liveloop_ab] default {thr_default:.1f} tok/s vs promoted "
          f"{thr_live:.1f} tok/s -> {ratio}x")

    # -- rollback arm: fault-injected modeled loop --------------------------
    def fault(genome, metrics):
        m = dict(metrics)
        m["throughput_tok_s"] = round(m["throughput_tok_s"] / 3.0, 6)
        m["mean_ttft_s"] = round(m["mean_ttft_s"] * 3.0, 6)
        m["mean_latency_s"] = round(m["mean_latency_s"] * 3.0, 6)
        return m

    root_rb = tempfile.mkdtemp(prefix="liveloop_ab_rb_")
    ctl_rb = LiveLoopController(root_rb, trace=trace, arch=arch,
                                mode="modeled", gens_per_tick=1, pop=6,
                                seed=seed, fraction=0.5,
                                guardrails=Guardrails(windows=2),
                                fault_hook=fault)
    rb_summaries = ctl_rb.run(ticks + 1)
    rb_outcomes = [s["outcome"] for s in rb_summaries]
    blocked = ctl_rb.book.status()["blocked"]
    print(f"[liveloop_ab] rollback arm outcomes: {rb_outcomes}, "
          f"blocked={[(b[:12] + '…') for b in blocked]}")
    # the blocklist invariant: once a fingerprint rolls back, it is never
    # proposed again (fresh fingerprints may still be — each new genome
    # gets its one canary before the fault hook sinks it)
    rolled = set()
    re_proposed = False
    for ev in ctl_rb.book.doc["history"]:
        if ev["event"] == "rollback":
            rolled.add(ev["fingerprint"])
        elif ev["event"] == "propose" and ev["fingerprint"] in rolled:
            re_proposed = True

    out = {
        "arch": arch, "trace": trace.summary(), "ticks": ticks,
        "loop_wall_s": round(wall_loop, 2),
        "promote": {
            "summaries": summaries,
            "promoted_genome": promoted["genome"],
            "canary_ratios": promoted["ratios"],
            "default_tok_s": {"median": thr_default, "runs": runs_default},
            "promoted_tok_s": {"median": thr_live, "runs": runs_live},
            "throughput_ratio_promoted_vs_default": ratio,
        },
        "rollback": {
            "outcomes": rb_outcomes,
            "blocked": blocked,
            "re_proposed_after_rollback": re_proposed,
        },
        "serve_cache_records": sum(
            1 for line in open(os.path.join(root, "cache.jsonl"))
            if json.loads(line).get("writer") == "serve"),
    }
    # acceptance bars: the loop promotes a genome that measures no worse
    # than the default artifact; the fault-injected run is rolled back and
    # its fingerprint is never re-canaried
    assert ratio >= 1.0, \
        (f"promoted serve genome lost to the default schedule "
         f"({thr_live:.1f} vs {thr_default:.1f} tok/s)")
    assert "rolled_back" in rb_outcomes, \
        f"fault-injected run was not rolled back: {rb_outcomes}"
    assert len(blocked) >= 1, "rollback did not block the fingerprint"
    assert not out["rollback"]["re_proposed_after_rollback"], \
        "a rolled-back fingerprint was re-proposed"
    assert out["serve_cache_records"] >= 2, \
        "the loop published no serve-tagged records"
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "liveloop_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[liveloop_ab] wrote {path}; promoted/default throughput="
          f"{ratio}x, rollback arm blocked "
          f"{len(blocked)} fingerprint(s)")
    return out


def sharded_serving_ab(generations: int = 4, seed: int = 0,
                       artifacts_dir: str = "experiments/artifacts") -> dict:
    """Default serve plan vs an evolved FULL plan (slots x prefill chunk x
    KV page size x cache dtype x replica layout) on the multi-replica
    router.

    ``GevoML`` (attr_tweak over the joint :data:`SERVE_SPACE`) searches the
    432-point plan space under a deterministic two-objective fitness:
    modeled s/token from the live loop's discrete-event serving model
    (``liveloop.simulate``, replica- and byte-budget-aware) against the
    *measured* quantized-cache decode error (memoized per
    ``(kv_dtype, kv_page_size)`` — the model forward is plan-independent).
    The deployment rule is the KV-plan fitness gate as code:
    ``front.select("time", on="error", limit=KV_ERROR_GATE)``.  The winner
    ships through the ArtifactRegistry, resolves back from disk, and is
    re-measured as a real :class:`Router` (warmup + median of 3 full-trace
    replays) against the default plan.  A second bar replays the same plan
    at its replica fan-out vs pinned to one replica over the same smoke
    mesh — the router must not lose to a single replica."""
    import statistics
    import tempfile

    import jax
    import numpy as np

    from repro.configs import smoke_config
    from repro.core import GevoML
    from repro.core.deploy import (DEFAULT_SERVE_PLAN, KV_ERROR_GATE,
                                   Artifact, ArtifactRegistry, KVPlan,
                                   build_router, measure_cache_error,
                                   serve_plan_from, serve_schedule_space)
    from repro.core.evaluator import FitnessCache, SerialEvaluator
    from repro.core.fitness import KernelWorkload
    from repro.core.liveloop import replay, synthesize
    from repro.core.liveloop.controller import simulate
    from repro.core.serialize import patch_from_doc
    from repro.launch.mesh import make_smoke_mesh
    from repro.models.transformer import init_params

    arch = "qwen3-0.6b"
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # slot-starved regime: enough concurrent requests that the default
    # 2-slot plan queues heavily, so residency/replica knobs matter
    trace = synthesize("bursty", vocab=cfg.vocab, n_requests=24,
                       max_prompt=8, gen=8, seed=seed)
    max_len = trace.max_len()
    print(f"[sharded_serving_ab] trace: {trace.summary()}")

    # -- evolve the full serving plan under the error-gated fitness ---------
    space = serve_schedule_space(arch)
    probe = np.asarray(
        np.random.default_rng(seed).integers(1, cfg.vocab, size=(2, 8)))
    err_memo: dict[tuple, float] = {}

    def plan_error(genome: dict) -> float:
        plan = KVPlan.from_genome(genome)
        key = (plan.dtype, plan.page_size)
        if key not in err_memo:
            err_memo[key] = measure_cache_error(
                cfg, params, plan, probe)["measured"]
        return err_memo[key]

    def runner(genome: dict) -> tuple[float, float]:
        return (simulate(trace, genome)["s_per_token"], plan_error(genome))

    w = KernelWorkload(name=f"serve/{arch}",
                       program=space.encode(DEFAULT_SERVE_PLAN),
                       space=space, runner=runner, time_mode="static",
                       kind="serve")
    cache_path = os.path.join(
        tempfile.mkdtemp(prefix="gevoml_sharded_serving_ab_"),
        "fitness.jsonl")
    ev = SerialEvaluator(w, cache=FitnessCache(cache_path, writer="search"))
    s = GevoML(w, pop_size=8, n_elite=4, seed=seed, init_mutations=2,
               mutation_rate=0.9, operators={"attr_tweak": 1.0},
               evaluator=ev)
    t0 = time.perf_counter()
    res = s.run(generations=generations)
    wall_search = time.perf_counter() - t0
    ev.close()

    # the deployment rule: fastest modeled plan whose measured decode error
    # clears the KV fitness gate
    front = res.to_front(origin="sharded_serving_ab")
    member = front.select("time", on="error", limit=KV_ERROR_GATE)
    best_genome = w.space.decode(
        patch_from_doc(list(member.patch)).apply(w.program))
    sim_default = simulate(trace, dict(DEFAULT_SERVE_PLAN))
    sim_evolved = simulate(trace, best_genome)
    modeled_ratio = round(sim_evolved["throughput_tok_s"]
                          / max(sim_default["throughput_tok_s"], 1e-9), 3)
    print(f"[sharded_serving_ab] selected plan {best_genome} "
          f"(error {member.fitness[1]:.4g} <= gate {KV_ERROR_GATE}); "
          f"modeled evolved/default throughput={modeled_ratio}x")

    # -- ship it: export the winner, resolve it back ------------------------
    registry = ArtifactRegistry(artifacts_dir)
    art_path = registry.export(Artifact(
        kind="serve", name=cfg.name, shape="sharded_smoke",
        genome=best_genome, fitness=member.fitness,
        meta={"rule": f"min modeled s/token s.t. "
                      f"cache error <= {KV_ERROR_GATE}",
              "trace": trace.summary(), "suite": "sharded_serving_ab"}))
    resolved = registry.resolve(cfg.name, "sharded_smoke", kind="serve")
    evolved_plan = serve_plan_from(resolved)

    # -- re-measure real routers from scratch on one smoke mesh -------------
    # every arm runs on the SAME mesh: replicas split its data rows into
    # submeshes (params + caches sharded per row group), a 1-replica plan
    # owns the whole mesh — the honest apples-to-apples for a plan whose
    # replica knob means "parallel hardware"
    multi_plan = dict(evolved_plan)
    if int(multi_plan["replicas"]) < 2:
        multi_plan["replicas"] = 2
    single_plan = dict(multi_plan, replicas=1)
    mesh = make_smoke_mesh(int(multi_plan["replicas"]), 2)

    def measure(tag, plan_genome, *, mesh=None, publish=False):
        runs, stats = [], None
        for rep in range(4):
            router = build_router(cfg, params, genome=plan_genome,
                                  max_len=max_len, mesh=mesh, seed=seed)
            report = replay(router, trace)
            assert report.n_rejected == 0 and \
                len(report.results) == len(trace.items), \
                f"{tag}: replay dropped requests"
            stats = router.stats()
            if rep == 0:        # unmeasured warmup: XLA compiles stay out
                if publish:
                    cache = FitnessCache(cache_path, writer="serve")
                    router.publish_stats(cache, name=cfg.name,
                                         shape={"plan": tag,
                                                "trace": trace.summary()})
                    cache.close()
                continue
            runs.append(stats["throughput_tok_s"])
        med = statistics.median(runs)
        rec = {"plan": dict(plan_genome), "throughput_tok_s": med,
               "runs_tok_s": runs, "n_replicas": stats["n_replicas"],
               "effective_slots": router.replicas[0].engine.max_slots,
               "on_mesh": mesh is not None}
        print(f"[sharded_serving_ab] {tag}: replicas="
              f"{stats['n_replicas']} -> {med:.1f} tok/s (runs {runs})")
        return rec

    default_rec = measure("default", dict(DEFAULT_SERVE_PLAN), mesh=mesh,
                          publish=True)
    evolved_rec = measure("evolved", evolved_plan, mesh=mesh, publish=True)
    plan_ratio = round(evolved_rec["throughput_tok_s"]
                       / max(default_rec["throughput_tok_s"], 1e-9), 3)

    # -- router vs a single replica of the same plan ------------------------
    router_rec = measure("router", multi_plan, mesh=mesh)
    single_rec = measure("single", single_plan, mesh=mesh)
    router_ratio = round(router_rec["throughput_tok_s"]
                         / max(single_rec["throughput_tok_s"], 1e-9), 3)

    n_serve_records = sum(
        1 for line in open(cache_path)
        if json.loads(line).get("writer") == "serve")
    out = {
        "arch": cfg.name, "trace": trace.summary(),
        "generations": generations,
        "search": {"wall_s": round(wall_search, 2), "n_evals": s.n_evals,
                   "space_size": space.size(),
                   "selected_genome": best_genome,
                   "selected_fitness": list(member.fitness),
                   "error_gate": KV_ERROR_GATE,
                   "default_fitness": list(res.original_fitness),
                   "front_size": len(front.members),
                   "measured_cache_errors": {
                       f"{k[0]}/p{k[1]}": round(v, 6)
                       for k, v in sorted(err_memo.items())}},
        "modeled_ratio_evolved_vs_default": modeled_ratio,
        "artifact": {"path": art_path,
                     "fingerprint": resolved.fingerprint()},
        "default": default_rec,
        "evolved": evolved_rec,
        "throughput_ratio_evolved_vs_default": plan_ratio,
        "router_on_mesh": router_rec,
        "single_on_mesh": single_rec,
        "throughput_ratio_router_vs_single": router_ratio,
        "serve_cache_records": n_serve_records,
    }
    # acceptance bars: the gate-feasible evolved plan must not lose to the
    # default plan (modeled and real), the replica fan-out must not lose to
    # one replica of the same plan on the smoke mesh, and both router
    # measurements must have fed serve-tagged records back into the cache
    assert member.fitness[1] <= KV_ERROR_GATE, \
        "select() returned a plan outside the decode-error gate"
    assert modeled_ratio >= 1.0, \
        f"evolved plan lost to the default under the model ({modeled_ratio}x)"
    assert plan_ratio >= 1.0, \
        (f"evolved serve plan lost to the default plan "
         f"({evolved_rec['throughput_tok_s']:.1f} vs "
         f"{default_rec['throughput_tok_s']:.1f} tok/s)")
    assert router_ratio >= 1.0, \
        (f"router lost to a single replica of the same plan "
         f"({router_rec['throughput_tok_s']:.1f} vs "
         f"{single_rec['throughput_tok_s']:.1f} tok/s)")
    assert n_serve_records >= 2, "no serve-tagged records in the cache"
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "sharded_serving_ab.json")
    json.dump(out, open(path, "w"), indent=1)
    print(f"[sharded_serving_ab] wrote {path}; evolved/default="
          f"{plan_ratio}x, router/single={router_ratio}x "
          f"({n_serve_records} serve-tagged cache records)")
    return out


def run_cells():
    os.makedirs(OUT, exist_ok=True)

    # ---- cell A: zamba2-1.2b train_4k (worst roofline fraction) ----------
    z = get_config("zamba2-1.2b")
    run("zamba2_train_0_baseline", "zamba2-1.2b", "train_4k",
        z.scaled(ssm_impl="naive"))
    run("zamba2_train_1_ssd", "zamba2-1.2b", "train_4k", z)  # ssd default
    run("zamba2_train_2_ssd_blockattn_remat", "zamba2-1.2b", "train_4k",
        z.scaled(attn_impl="blockwise", attn_block=512, remat="full"))
    run("zamba2_train_3_plus_losschunk", "zamba2-1.2b", "train_4k",
        z.scaled(attn_impl="blockwise", attn_block=512, remat="full",
                 loss_chunk=512))

    # ---- cell B: deepseek-v3-671b train_4k (most collective-bound) -------
    d = get_config("deepseek-v3-671b")
    run("deepseek_train_0_baseline", "deepseek-v3-671b", "train_4k",
        d.scaled(gnorm_vdot=True))
    run("deepseek_train_1_sharded_gnorm", "deepseek-v3-671b", "train_4k", d)
    run("deepseek_train_2_blockattn", "deepseek-v3-671b", "train_4k",
        d.scaled(attn_impl="blockwise", attn_block=512))
    run("deepseek_train_3_plus_losschunk", "deepseek-v3-671b", "train_4k",
        d.scaled(attn_impl="blockwise", attn_block=512, loss_chunk=512))

    # ---- cell C: qwen2-vl-72b prefill_32k (attention-memory-bound) -------
    q = get_config("qwen2-vl-72b")
    run("qwen2vl_prefill_0_baseline", "qwen2-vl-72b", "prefill_32k", q)
    run("qwen2vl_prefill_1_blockattn", "qwen2-vl-72b", "prefill_32k",
        q.scaled(attn_impl="blockwise", attn_block=512))
    run("qwen2vl_prefill_2_blockattn1k", "qwen2-vl-72b", "prefill_32k",
        q.scaled(attn_impl="blockwise", attn_block=1024))
    run("qwen2vl_prefill_3_nofsdp", "qwen2-vl-72b", "prefill_32k",
        q.scaled(attn_impl="blockwise", attn_block=512, fsdp=False))

    # ---- bonus D: falcon-mamba-7b train_4k (worst memory after resweep) ---
    f = get_config("falcon-mamba-7b")
    run("falcon_train_0_baseline", "falcon-mamba-7b", "train_4k",
        f.scaled(ssm_impl="naive"))
    run("falcon_train_1_chunked", "falcon-mamba-7b", "train_4k", f)
    run("falcon_train_2_chunked_remat", "falcon-mamba-7b", "train_4k",
        f.scaled(remat="full"))

    # ---- bonus E: deepseek-v3-671b decode_32k (chip-share layer vs EP) ----
    run("deepseek_decode_0_local", "deepseek-v3-671b", "decode_32k",
        d.scaled(moe_mode="local"))
    run("deepseek_decode_1_ep_a2a", "deepseek-v3-671b", "decode_32k", d)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite",
                    choices=("cells", "evaluator", "operators", "kernels",
                             "islands", "serving", "tensor_evo", "analysis",
                             "surrogate", "liveloop", "sharded_serving",
                             "all"),
                    default="cells")
    ap.add_argument("--workers", type=int, default=2,
                    help="ParallelEvaluator workers for --suite evaluator")
    ap.add_argument("--generations", type=int, default=4)
    args = ap.parse_args()
    if args.suite in ("cells", "all"):
        run_cells()
    if args.suite in ("evaluator", "all"):
        evaluator_ab(workers=args.workers, generations=args.generations)
    if args.suite in ("operators", "all"):
        operators_ab(generations=max(args.generations, 6))
    if args.suite in ("kernels", "all"):
        kernels_ab(generations=max(args.generations, 6))
    if args.suite in ("islands", "all"):
        islands_ab(generations=max(args.generations, 6))
    if args.suite in ("serving", "all"):
        serving_ab(generations=min(args.generations, 3))
    if args.suite in ("tensor_evo", "all"):
        tensor_evo_ab()
    if args.suite in ("analysis", "all"):
        analysis_ab(generations=max(args.generations, 12))
    if args.suite in ("surrogate", "all"):
        surrogate_ab(generations=max(args.generations, 10))
    if args.suite in ("liveloop", "all"):
        liveloop_ab()
    if args.suite in ("sharded_serving", "all"):
        sharded_serving_ab(generations=max(args.generations, 4))


if __name__ == "__main__":
    main()
