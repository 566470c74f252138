"""TensorGevoML: the whole generation loop as one jitted array program.

The Python engine (:class:`~repro.core.search.GevoML`) interleaves RNG-driven
candidate generation with per-patch evaluation; its cost is Python-loop
bound.  This engine keeps the population as an ``(pop, n_knobs)`` index
matrix on-device and fuses fitness (batched roofline + gates + error-table
gathers), NSGA-II selection (:mod:`.nsga2`), tournament, uniform crossover,
and point mutation into a single ``jit``-compiled step — evaluation
throughput scales with vector width instead of interpreter speed.

Contract differences from the Python engine (documented in DESIGN.md):

* offspring are not resampled until valid — invalid lanes carry
  ``(inf, inf)`` objectives and die in selection instead;
* crossover is uniform over knobs (the natural fixed-shape operator), not
  messy edit-list splicing;
* the RNG is ``jax.random`` (counter-based), not NumPy's generator — runs
  are deterministic per seed but not RNG-compatible with ``GevoML``;
* ``surrogate=True`` swaps in an over-generating step (``ceil(1/keep)`` x
  the offspring lanes) whose children are cut back to ``P - E`` by the
  host-side cost model (:mod:`repro.core.surrogate`) before re-entering the
  device loop — the default step is untouched and stays bit-exact with the
  pre-surrogate engine.

Everything *reported* — final population fitness, Pareto front, cache
records — is recomputed through the bit-exact NumPy path
(:class:`~.evaluator.TensorEvaluator`), so results re-enter the Patch/doc
world (deployment, caches, EXPERIMENTS.md) with serial-identical values.

Checkpoints are one ``.npz`` (population matrix + RNG key) plus a JSON
sidecar per generation; ``run(resume=True)`` continues bit-exactly (the
step is a deterministic function of the restored arrays).
"""

from __future__ import annotations

import json
import os
import time as _time

import numpy as np

from ..evaluator import FitnessCache
from ..fitness import InvalidVariant
from ..search import Individual, SearchResult
from ..serialize import atomic_write_json
from . import nsga2 as tnsga
from .evaluator import TensorEvaluator


def _x64():
    import jax
    return jax.enable_x64(True)


class TensorGevoML:
    """Fixed-shape NSGA-II search over one tensorizable workload.

    ``step_fn`` (built once, jitted on first call) maps
    ``(idx, key, cx_rate, mut_rate) -> (idx', key', metrics)`` — rates are
    traced arguments so the island fleet can ``vmap`` one compiled step
    over heterogeneous per-island rates."""

    def __init__(self, workload, *, pop_size: int = 1024, n_elite: int = 16,
                 crossover_rate: float = 0.8, mutation_rate: float = 0.5,
                 seed: int = 0, verbose: bool = False,
                 cache: FitnessCache | None = None,
                 cache_path: str | None = None,
                 checkpoint_dir: str | None = None,
                 surrogate: bool = False, surrogate_keep: float = 0.5):
        if cache is not None and cache_path is not None:
            raise ValueError("pass cache OR cache_path, not both")
        if cache is None:
            cache = FitnessCache(cache_path)
        self.w = workload
        self.pop_size = pop_size
        self.n_elite = min(n_elite, pop_size)
        self.crossover_rate = crossover_rate
        self.mutation_rate = mutation_rate
        self.seed = seed
        self.verbose = verbose
        self.checkpoint_dir = checkpoint_dir
        # the numpy-exact side: encoding, batched fitness, cache, reporting
        self.evaluator = TensorEvaluator(workload, cache=cache)
        self.encoding = self.evaluator.encoding
        self.batched = self.evaluator.batched
        self._step = None
        self._over_step = None
        # surrogate pre-rank: the over-generating step produces
        # ceil(1/keep) x the offspring lanes; the cost model (trained each
        # generation on the current population's objectives) keeps the
        # predicted-Pareto slice, so the evaluated population stays
        # ``pop_size`` while candidate generation widens.  Off by default —
        # the default step is bit-exact with the pre-surrogate engine.
        self.guide = None
        if surrogate:
            import math
            from ..surrogate import SurrogateGuide
            self.guide = SurrogateGuide(workload, keep=surrogate_keep)
            self._overgen = math.ceil(1.0 / surrogate_keep)

    @property
    def cache(self) -> FitnessCache:
        return self.evaluator.cache

    def close(self) -> None:
        self.evaluator.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- the jitted generation step ------------------------------------------
    def _make_step(self, n_children: int, concat: bool):
        """Build one jitted generation step producing ``n_children``
        offspring lanes.  ``concat=True`` is the classic step (returns the
        next ``(P, knobs)`` population); ``concat=False`` returns
        ``(elites, children, objs, key, metrics)`` so a host-side stage can
        pick which children survive.  RNG draw shapes depend only on
        ``n_children``, so the ``n_children == P - E`` concat step is
        bit-exact with the pre-surrogate engine."""
        import jax
        import jax.numpy as jnp

        terms = self.batched.jnp_terms_fn()
        error_of = self.batched.jnp_error_fn()
        n_choices = jnp.asarray(self.encoding.n_choices(), jnp.int32)
        mutable = np.flatnonzero(self.encoding.n_choices() > 1)
        if len(mutable) == 0:
            raise InvalidVariant("space has no mutable knobs")
        mutable = jnp.asarray(mutable, jnp.int32)
        P, E = self.pop_size, self.n_elite
        n_off = n_children

        def objectives(idx):
            time, valid = terms(idx)
            err = error_of(idx)
            valid = valid & jnp.isfinite(time) & jnp.isfinite(err)
            inf = jnp.inf
            return (jnp.stack([jnp.where(valid, time, inf),
                               jnp.where(valid, err, inf)], axis=1), valid)

        def step(idx, key, cx_rate, mut_rate):
            objs, valid = objectives(idx)
            rank, crowd = tnsga.rank_crowd(objs, xp=jnp)
            order = tnsga.selection_order(rank, crowd, xp=jnp)
            elites = idx[order[:E]]
            key, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
            # binary crowded tournament, two parents per offspring lane:
            # second candidate wins only if strictly crowded-better.
            cand = jax.random.randint(k1, (2, 2, n_off), 0, P)

            def better(i, j):
                return (rank[i] < rank[j]) | ((rank[i] == rank[j])
                                              & (crowd[i] > crowd[j]))

            pa = jnp.where(better(cand[0, 1], cand[0, 0]),
                           cand[0, 1], cand[0, 0])
            pb = jnp.where(better(cand[1, 1], cand[1, 0]),
                           cand[1, 1], cand[1, 0])
            do_cx = jax.random.uniform(k2, (n_off,)) < cx_rate
            mix = jax.random.bernoulli(k3, 0.5, (n_off, idx.shape[1]))
            child = jnp.where(do_cx[:, None] & mix, idx[pb], idx[pa])
            # point mutation: pick a mutable knob, draw a *different* index
            do_mut = jax.random.uniform(k4, (n_off,)) < mut_rate
            kpos = mutable[jax.random.randint(k5, (n_off,), 0, len(mutable))]
            lanes = jnp.arange(n_off)
            cur = child[lanes, kpos]
            nc = n_choices[kpos]
            r = jax.random.randint(k6, (n_off,), 0,
                                   jnp.maximum(nc - 1, 1))
            new = r + (r >= cur)
            child = child.at[lanes, kpos].set(
                jnp.where(do_mut, new, cur).astype(idx.dtype))
            metrics = {
                "best_time": jnp.min(objs[:, 0]),
                "best_error": jnp.min(objs[:, 1]),
                "pareto_size": jnp.sum(rank == 0),
                "n_valid": jnp.sum(valid),
            }
            if concat:
                return jnp.concatenate([elites, child], axis=0), key, metrics
            return elites, child, objs, key, metrics

        return jax.jit(step)

    def step_fn(self):
        """Build (once) the jitted step.  Call under ``enable_x64`` — the
        roofline arithmetic is float64."""
        if self._step is None:
            self._step = self._make_step(self.pop_size - self.n_elite,
                                         concat=True)
        return self._step

    def over_step_fn(self):
        """The surrogate path's over-generating step: ``ceil(1/keep)`` x the
        offspring lanes, returned unconcatenated for host-side pre-rank."""
        if self._over_step is None:
            n_off = self.pop_size - self.n_elite
            self._over_step = self._make_step(self._overgen * n_off,
                                              concat=False)
        return self._over_step

    # -- surrogate pre-rank (host side; numpy featurizer + ridge model) ------
    def _row_features(self, row) -> list[float]:
        return self.guide.featurizer.of_genome(self.encoding.genome_of(row))

    def _guided_refit(self, idx_np, objs_np) -> bool:
        """Train on the generation's own (rows, objectives) — finite lanes
        only; the tensor path needs no cache round-trip for training data."""
        mask = np.isfinite(objs_np).all(axis=1)
        if int(mask.sum()) < self.guide.min_fit:
            return False
        X = [self._row_features(r) for r in idx_np[mask]]
        self.guide.model.fit(X, objs_np[mask])
        self.guide.n_refits += 1
        return True

    def _guided_select(self, child_np):
        """The predicted-Pareto ``P - E`` slice of the over-generated
        children (pass-through before the first fit)."""
        n_off = self.pop_size - self.n_elite
        if not self.guide.model.trained:
            return child_np[:n_off]
        feats = [self._row_features(r) for r in child_np]
        kept = sorted(self.guide.select(feats, n_off))
        return child_np[kept]

    def _init_pop(self, key):
        """Lane 0 = baseline schedule, the rest uniform over the space."""
        import jax
        import jax.numpy as jnp

        nc = jnp.asarray(self.encoding.n_choices(), jnp.float64)
        u = jax.random.uniform(key, (self.pop_size, self.encoding.n_knobs))
        rows = jnp.minimum((u * nc).astype(jnp.int32),
                           (nc - 1).astype(jnp.int32))
        return rows.at[0].set(
            jnp.asarray(self.encoding.baseline_row(), jnp.int32))

    # -- checkpoint/resume ----------------------------------------------------
    def _save_checkpoint(self, gen, idx, key, original, history) -> None:
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        npz = os.path.join(self.checkpoint_dir, "state_latest.npz")
        tmp = npz + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, idx=np.asarray(idx), key=np.asarray(key))
        os.replace(tmp, npz)
        doc = {
            "engine": "tensor", "gen": gen, "seed": self.seed,
            "program_fingerprint": self.evaluator.fingerprint,
            "original_fitness": list(original), "history": history,
        }
        if self.guide is not None:
            doc["surrogate"] = self.guide.stats()
        atomic_write_json(os.path.join(self.checkpoint_dir, "latest.json"),
                          doc)

    def _load_checkpoint(self):
        path = os.path.join(self.checkpoint_dir, "latest.json")
        if not os.path.exists(path):
            return None
        doc = json.load(open(path))
        if doc["program_fingerprint"] != self.evaluator.fingerprint:
            raise ValueError(
                "checkpoint was written for a different program "
                f"(fingerprint {doc['program_fingerprint'][:12]}… != "
                f"{self.evaluator.fingerprint[:12]}…)")
        state = np.load(os.path.join(self.checkpoint_dir, "state_latest.npz"))
        return doc, state["idx"], state["key"]

    # -- main loop ------------------------------------------------------------
    def run(self, generations: int = 10, *, resume: bool = False,
            record_cache: bool = True) -> SearchResult:
        import jax

        with _x64():
            state = (self._load_checkpoint()
                     if resume and self.checkpoint_dir else None)
            if state is not None:
                doc, idx_np, key_np = state
                original = tuple(doc["original_fitness"])
                history = list(doc["history"])
                start_gen = doc["gen"] + 1
                import jax.numpy as jnp
                idx = jnp.asarray(idx_np)
                key = jnp.asarray(key_np)
                if self.guide is not None:
                    self.guide.restore(doc.get("surrogate"))
                t0 = _time.perf_counter() - (history[-1]["wall_s"]
                                             if history else 0.0)
            else:
                t0 = _time.perf_counter()
                base = self.encoding.baseline_row()[None, :]
                first = self.evaluator.evaluate_rows(base)[0]
                if not first.ok:
                    raise InvalidVariant(
                        f"original program failed evaluation: {first.error}")
                original = first.fitness
                key = jax.random.PRNGKey(self.seed)
                key, init_key = jax.random.split(key)
                idx = self._init_pop(init_key)
                history = []
                start_gen = 0

            import jax.numpy as jnp
            step = (self.step_fn() if self.guide is None
                    else self.over_step_fn())
            for gen in range(start_gen, generations):
                if self.guide is None:
                    idx, key, metrics = step(idx, key, self.crossover_rate,
                                             self.mutation_rate)
                else:
                    elites, children, objs, key, metrics = step(
                        idx, key, self.crossover_rate, self.mutation_rate)
                    self._guided_refit(np.asarray(idx), np.asarray(objs))
                    child_sel = self._guided_select(np.asarray(children))
                    idx = jnp.concatenate(
                        [elites, jnp.asarray(child_sel, elites.dtype)],
                        axis=0)
                history.append({
                    "gen": gen,
                    "best_time": float(metrics["best_time"]),
                    "best_error": float(metrics["best_error"]),
                    "pareto_size": int(metrics["pareto_size"]),
                    "n_valid": int(metrics["n_valid"]),
                    "evals": self.pop_size * (gen + 1),
                    "wall_s": _time.perf_counter() - t0,
                })
                if self.guide is not None:
                    history[-1]["surrogate"] = self.guide.stats()
                if self.verbose:
                    h = history[-1]
                    print(f"[gen {gen:3d}] time={h['best_time']:.3e} "
                          f"err={h['best_error']:.4f} "
                          f"pareto={h['pareto_size']} "
                          f"valid={h['n_valid']}/{self.pop_size}")
                if self.checkpoint_dir:
                    self._save_checkpoint(gen, idx, key, original, history)
            idx_np = np.asarray(idx)
        return self._finalize(idx_np, original, history,
                              record_cache=record_cache)

    def _finalize(self, idx_np, original, history, *,
                  record_cache: bool) -> SearchResult:
        """Re-score the final population through the bit-exact NumPy path
        and hand back a standard :class:`SearchResult` (canonical patches,
        serial-identical fitness), recording outcomes into the cache."""
        if record_cache:
            patches = [self.encoding.to_patch(row) for row in idx_np]
            outs = self.evaluator.evaluate_batch(patches)
        else:
            patches = [self.encoding.to_patch(row) for row in idx_np]
            outs = self.evaluator.evaluate_rows(idx_np)
        pop = [Individual(p, o.fitness)
               for p, o in zip(patches, outs) if o.ok]
        if not pop:
            raise InvalidVariant("tensor search ended with no valid lane")
        objs = np.array([i.fitness for i in pop])
        pf = [pop[i] for i in tnsga.pareto_front(objs)]
        seen, pareto = set(), []
        for ind in sorted(pf, key=lambda i: i.fitness):
            if ind.fitness not in seen:
                seen.add(ind.fitness)
                pareto.append(ind)
        return SearchResult(original_fitness=original, population=pop,
                            pareto=pareto, history=history)
