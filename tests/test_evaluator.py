"""The evaluation engine: cache accounting, canonical-hash stability,
serial/parallel equivalence, persistent warm starts, checkpoint/resume."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.evaluator import (EvalOutcome, FitnessCache,
                                  ParallelEvaluator, SerialEvaluator,
                                  WorkloadSpec, make_evaluator)
from repro.core.edits import Edit, OperatorWeights, sample_edit
from repro.core.search import GevoML
from repro.core.serialize import patch_key, program_fingerprint
from repro.workloads.twofc import build_twofc_step, build_twofc_training_workload

_REPO = os.path.join(os.path.dirname(__file__), "..")
_TINY = dict(batch=32, hidden=16, steps=5, n_train=256, n_test=256)


@pytest.fixture(scope="module")
def tiny_workload():
    return build_twofc_training_workload(**_TINY)


@pytest.fixture(scope="module")
def some_patches(tiny_workload):
    rng = np.random.default_rng(0)
    out = [()]
    for _ in range(4):
        out.append((sample_edit(tiny_workload.program, rng,
                                OperatorWeights.legacy()),))
    return out


# -- cache accounting -------------------------------------------------------

def test_cache_hit_miss_accounting(tiny_workload, some_patches):
    ev = SerialEvaluator(tiny_workload)
    batch = some_patches + some_patches[:2]  # in-batch duplicates
    outs = ev.evaluate_batch(batch)
    assert len(outs) == len(batch)
    uniq = len(set(ev.key(p) for p in batch))
    assert ev.cache.misses == uniq
    assert ev.n_evals == uniq
    assert len(ev.cache) == uniq
    # duplicates within the batch were served from the single evaluation
    assert outs[0].fitness == outs[len(some_patches)].fitness
    # second pass: all hits, zero new executions
    outs2 = ev.evaluate_batch(some_patches)
    assert ev.n_evals == uniq
    assert ev.cache.hits >= len(some_patches)
    assert all(o.cached for o in outs2)
    assert [o.fitness for o in outs2] == [o.fitness
                                          for o in outs[:len(some_patches)]]


def test_invalid_outcomes_are_cached(tiny_workload):
    ev = SerialEvaluator(tiny_workload)
    bad = (Edit("delete", target_uid=10_000),)  # uid does not exist
    out = ev.evaluate_one(bad)
    assert not out.ok and out.error
    n = ev.n_evals
    out2 = ev.evaluate_one(bad)
    assert not out2.ok and out2.cached
    assert ev.n_evals == n  # known-bad variants are never re-executed


def test_fingerprint_covers_workload_protocol(tiny_workload):
    # same program, different evaluation protocol (steps) -> different keys,
    # so a shared persistent cache can never serve cross-config fitness
    other = build_twofc_training_workload(**{**_TINY, "steps": 7})
    assert program_fingerprint(other.program) == \
        program_fingerprint(tiny_workload.program)
    assert SerialEvaluator(other).fingerprint != \
        SerialEvaluator(tiny_workload).fingerprint


def test_original_program_through_evaluator(tiny_workload):
    ev = SerialEvaluator(tiny_workload)
    out = ev.evaluate_one(())
    assert out.ok
    assert out.fitness == tiny_workload.evaluate(tiny_workload.program)


# -- persistence ------------------------------------------------------------

def test_persistent_cache_roundtrip(tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    c = FitnessCache(path)
    c.put("k1", EvalOutcome(fitness=(1.0, 0.5)))
    c.put("k2", EvalOutcome(fitness=None, error="boom"))
    c.close()
    with open(path, "a") as f:
        f.write('{"key": "torn"')  # crash mid-write
    c2 = FitnessCache(path)
    assert len(c2) == 2
    assert c2.get("k1").fitness == (1.0, 0.5)
    assert c2.get("k2").error == "boom"
    assert c2.get("torn") is None
    c2.close()


def test_patch_key_stable_across_processes():
    prog = build_twofc_step(batch=8, in_dim=16, hidden=8)
    edits = (Edit("delete", target_uid=3, seed=7),
             Edit("copy", target_uid=1, dest_uid=4, seed=9))
    here = patch_key(program_fingerprint(prog), edits)
    script = (
        "from repro.workloads.twofc import build_twofc_step\n"
        "from repro.core.edits import Edit\n"
        "from repro.core.serialize import patch_key, program_fingerprint\n"
        "prog = build_twofc_step(batch=8, in_dim=16, hidden=8)\n"
        "edits = (Edit('delete', target_uid=3, seed=7),\n"
        "         Edit('copy', target_uid=1, dest_uid=4, seed=9))\n"
        "print(patch_key(program_fingerprint(prog), edits))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    there = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, check=True)
    assert there.stdout.strip() == here


# -- serial vs parallel -----------------------------------------------------

def test_parallel_identical_to_serial(tiny_workload):
    s1 = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                init_mutations=2)
    r1 = s1.run(generations=2)
    with ParallelEvaluator(tiny_workload, n_workers=2) as ev:
        s2 = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                    init_mutations=2, evaluator=ev)
        r2 = s2.run(generations=2)
    assert [(i.edits, i.fitness) for i in r1.population] == \
           [(i.edits, i.fitness) for i in r2.population]
    assert [(i.edits, i.fitness) for i in r1.pareto] == \
           [(i.edits, i.fitness) for i in r2.pareto]
    assert s1.n_evals == s2.n_evals


def test_parallel_inline_static_short_circuit(tiny_workload, some_patches):
    # static time mode + inline_static: no worker pool is ever spawned
    ev = ParallelEvaluator(tiny_workload, n_workers=2, inline_static=True)
    serial = SerialEvaluator(tiny_workload)
    outs = ev.evaluate_batch(some_patches)
    assert ev._pool is None
    assert [o.fitness for o in outs] == \
           [o.fitness for o in serial.evaluate_batch(some_patches)]
    ev.close()


def test_parallel_refuses_workers_on_tpu(tiny_workload, some_patches,
                                        monkeypatch):
    """On a TPU the parent already holds the chip: spawning workers that
    each open JAX must fail loudly, before any worker starts."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ev = ParallelEvaluator(tiny_workload, n_workers=2)
    with pytest.raises(RuntimeError, match="process that holds the chip"):
        ev.evaluate_batch(some_patches)
    assert ev._pool is None
    ev.close()


def test_unpicklable_workload_needs_spec(tiny_workload):
    # TrainingWorkload.eval_fn is a closure: transport must fall back to the
    # WorkloadSpec recipe the builder attached
    assert isinstance(tiny_workload.spec, WorkloadSpec)
    ev = ParallelEvaluator(tiny_workload, n_workers=2)
    assert ev._payload()["pickled"] is None
    ev.close()
    rebuilt = tiny_workload.spec.build()
    assert program_fingerprint(rebuilt.program) == \
        program_fingerprint(tiny_workload.program)


# -- warm persistent cache --------------------------------------------------

def test_warm_cache_zero_new_evaluations(tiny_workload, tmp_path):
    path = str(tmp_path / "fitness.jsonl")
    s1 = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                init_mutations=2, cache_path=path)
    r1 = s1.run(generations=2)
    lookups = s1.cache.hits + s1.cache.misses
    s1.close()  # GevoML owns this evaluator: releases the cache handle
    assert s1.n_evals > 0

    s2 = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                init_mutations=2, cache_path=path)
    r2 = s2.run(generations=2)
    assert s2.n_evals == 0                 # nothing re-measured
    assert s2.cache.misses == 0
    assert s2.cache.hits == lookups        # every evaluation was a cache hit
    assert [i.fitness for i in r2.pareto] == [i.fitness for i in r1.pareto]
    s2.close()


# -- checkpoint / resume ----------------------------------------------------

def test_checkpoint_resume_same_pareto(tiny_workload, tmp_path):
    full = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                  init_mutations=2, checkpoint_dir=str(tmp_path / "full"))
    r_full = full.run(generations=4)

    ck = str(tmp_path / "split")
    first = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                   init_mutations=2, checkpoint_dir=ck)
    first.run(generations=2)
    second = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                    init_mutations=2, checkpoint_dir=ck)
    r_resumed = second.run(generations=4, resume=True)

    assert [(i.edits, i.fitness) for i in r_resumed.pareto] == \
           [(i.edits, i.fitness) for i in r_full.pareto]
    assert [(i.edits, i.fitness) for i in r_resumed.population] == \
           [(i.edits, i.fitness) for i in r_full.population]
    assert len(r_resumed.history) == 4
    snap = json.load(open(os.path.join(ck, "latest.json")))
    assert snap["gen"] == 3
    assert "rng_state" in snap and "counters" in snap


def test_checkpoint_rejects_other_program(tiny_workload, tmp_path):
    ck = str(tmp_path / "ck")
    s = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=0,
               init_mutations=1, checkpoint_dir=ck)
    s.run(generations=1)
    other = build_twofc_training_workload(batch=32, hidden=24, steps=5,
                                          n_train=256, n_test=256)
    s2 = GevoML(other, pop_size=4, n_elite=2, seed=0, init_mutations=1,
                checkpoint_dir=ck)
    with pytest.raises(ValueError, match="different program"):
        s2.run(generations=2, resume=True)


def test_make_evaluator_dispatch(tiny_workload, tmp_path):
    assert isinstance(make_evaluator(tiny_workload), SerialEvaluator)
    ev = make_evaluator(tiny_workload, parallel=2,
                        cache_path=str(tmp_path / "c.jsonl"))
    assert isinstance(ev, ParallelEvaluator)
    assert ev.cache.path is not None
    ev.close()
    ev = make_evaluator(tiny_workload, features=True)
    assert ev.featurizer is not None
    ev.close()


# -- bugfix regressions: stats split, transient containment -----------------

def test_stats_split_executed_vs_screened(tiny_workload):
    """Regression: stats() must split cache misses into executed ones and
    statically screened ones — `misses` alone conflates them."""
    ev = make_evaluator(tiny_workload, screen=True)
    ev.evaluate_one(())                              # executes
    ev.evaluate_one((Edit("delete", target_uid=10_000),))  # screens: invalid
    s = ev.stats()
    assert s["executed_misses"] == ev.n_evals == 1
    assert s["screened"] == ev.n_screened == 1
    assert s["executed_misses"] + s["screened"] == s["misses"]
    ev.close()


def test_transient_outcomes_never_persisted(tmp_path):
    """Regression (cache poisoning): a transient failure is remembered for
    the current run only — it never reaches the JSONL, so the next run
    re-evaluates instead of trusting a crashed worker's verdict."""
    path = str(tmp_path / "c.jsonl")
    c = FitnessCache(path)
    c.put("boom", EvalOutcome(fitness=None, error="crash", transient=True))
    c.put("good", EvalOutcome(fitness=(1.0, 2.0)))
    assert c.get("boom") is not None     # this run does not retry it
    c.close()
    c2 = FitnessCache(path)
    assert "boom" not in c2              # ... but no future run inherits it
    assert c2.get("good").fitness == (1.0, 2.0)
    c2.close()


def test_worker_eval_contains_arbitrary_exceptions(tiny_workload,
                                                   monkeypatch):
    """Regression: a non-invalid exception in a worker (backend error, OOM)
    must come back as a contained ("error", traceback) result instead of
    propagating through pool.map and killing the whole search."""
    from repro.core import evaluator as ev_mod
    from repro.core.edits import Patch
    from repro.core.fitness import InvalidVariant

    class Boom:
        program = tiny_workload.program

        def evaluate(self, program):
            raise RuntimeError("backend exploded")

    monkeypatch.setattr(ev_mod, "_WORKER_WORKLOAD", Boom())
    tag, payload = ev_mod._worker_eval(Patch.coerce(()))
    assert tag == "error"
    assert "backend exploded" in payload and "Traceback" in payload

    class Invalid(Boom):
        def evaluate(self, program):
            raise InvalidVariant("broken contract")

    monkeypatch.setattr(ev_mod, "_WORKER_WORKLOAD", Invalid())
    tag, payload = ev_mod._worker_eval(Patch.coerce(()))
    assert tag == "invalid" and payload == "broken contract"


def test_worker_crash_marked_transient_and_not_persisted(tiny_workload,
                                                         tmp_path,
                                                         monkeypatch):
    """A crashed dispatch yields a transient outcome: invalid for this run,
    absent from the persistent cache, re-evaluated by the next run."""
    path = str(tmp_path / "c.jsonl")
    ev = ParallelEvaluator(tiny_workload, n_workers=2,
                           cache=FitnessCache(path))

    class CrashPool:
        def map(self, fn, patches, chunksize=None):
            return [("error", "Traceback ... boom")] * len(patches)

    monkeypatch.setattr(ev, "_ensure_pool", lambda: CrashPool())
    out = ev.evaluate_one(())
    assert not out.ok and out.transient
    key = ev.key(())
    assert key in ev.cache               # contained for this run
    ev.cache.close()

    ev2 = SerialEvaluator(tiny_workload, cache=FitnessCache(path))
    assert key not in ev2.cache          # the crash never reached disk
    assert ev2.evaluate_one(()).ok       # a healthy run re-measures it
    ev2.close()


def test_search_survives_transient_batch(tiny_workload, tmp_path):
    """One flaky dispatch mid-run must not kill the search or leak its
    failure into the persistent cache."""
    path = str(tmp_path / "c.jsonl")

    class Flaky(SerialEvaluator):
        calls = 0

        def _evaluate_misses(self, patches):
            Flaky.calls += 1
            if Flaky.calls == 2:    # one bad dispatch after the original
                return [EvalOutcome(fitness=None, error="boom",
                                    transient=True) for _ in patches]
            return super()._evaluate_misses(patches)

    ev = Flaky(tiny_workload, cache=FitnessCache(path))
    res = GevoML(tiny_workload, pop_size=6, n_elite=3, seed=0,
                 init_mutations=2, evaluator=ev).run(generations=2)
    assert Flaky.calls > 2
    assert len(res.pareto) >= 1
    ev.close()
    assert "boom" not in open(path).read()
