"""The island-model orchestrator: topologies, migrant selection, the
GevoML injection hook, end-to-end multi-island search, shared-cache
accounting, and fault-tolerant bit-exact resume."""

import json
import os

import numpy as np
import pytest

from repro.core import GevoML, IslandOrchestrator
from repro.core.islands import (IslandSpec, default_island_specs,
                                migration_edges, plan)
from repro.core.islands.migration import compute_migration, select_migrants
from repro.workloads.twofc import build_twofc_training_workload

_TINY = dict(batch=16, hidden=8, steps=3, n_train=128, n_test=128)


@pytest.fixture(scope="module")
def tiny_workload():
    return build_twofc_training_workload(**_TINY)


def _key_pop(res):
    return [(i.edits, i.fitness) for i in res.population]


def _key_pareto(res):
    return [(i.edits, i.fitness) for i in res.pareto]


# -- topology ---------------------------------------------------------------

def test_topologies():
    assert migration_edges("ring", 4) == {0: (3,), 1: (0,), 2: (1,), 3: (2,)}
    full = migration_edges("full", 3)
    assert full == {0: (1, 2), 1: (0, 2), 2: (0, 1)}
    bb = migration_edges("broadcast_best", 3)
    assert all(srcs == ("pool",) for srcs in bb.values())
    assert migration_edges("ring", 1) == {0: ()}
    with pytest.raises(ValueError, match="unknown topology"):
        migration_edges("hypercube", 4)


def test_plan_core_mapping():
    p = plan(4, cores=17)
    assert p.processes and p.eval_workers == 3     # 4 cores/island: 1 loop+3
    assert p.n_islands * (1 + p.eval_workers) <= 17 - 1   # never oversubscribed
    p = plan(4, cores=8)
    assert p.processes and p.eval_workers == 0          # 1 core per island
    p = plan(4, cores=3)
    assert not p.processes                              # machine too small
    p = plan(1, cores=64)
    assert not p.processes                              # one island: inline
    assert "islands" in plan(2, cores=8).describe()
    with pytest.raises(ValueError):
        plan(0)


# -- specs ------------------------------------------------------------------

def test_default_specs_heterogeneous_and_roundtrip():
    specs = default_island_specs(4)
    assert len({s.seed for s in specs}) == 4
    assert len({s.operators for s in specs}) == 4
    for s in specs:
        assert IslandSpec.from_doc(s.to_doc()).to_doc() == s.to_doc()
    # explicit mix: all islands share it, rates/seeds differ
    sched = default_island_specs(3, operators={"attr_tweak": 1.0})
    assert all(s.to_doc()["operators"] == {"attr_tweak": 1.0} for s in sched)
    assert len({(s.mutation_rate, s.init_mutations) for s in sched}) == 3


# -- migrant selection ------------------------------------------------------

def test_select_migrants_nsga2_best():
    pop = [{"edits": [i], "fitness": [float(i), float(i)]}
           for i in range(5)]          # strictly dominated chain
    picks = select_migrants(pop, 2)
    assert [p["edits"] for p in picks] == [[0], [1]]
    assert select_migrants([], 2) == []
    assert select_migrants(pop, 0) == []


def test_compute_migration_shapes_and_sources():
    pops = [[{"edits": [j, i], "fitness": [float(i), float(i)]}
             for i in range(4)] for j in range(3)]
    ring = compute_migration("ring", pops, 2)
    assert set(ring) == {"0", "1", "2"}
    assert [m["src"] for m in ring["1"]] == [0, 0]
    assert all(len(v) == 2 for v in ring.values())
    full = compute_migration("full", pops, 1)
    assert sorted(m["src"] for m in full["0"]) == [1, 2]
    bb = compute_migration("broadcast_best", pops, 2)
    # pooled global best: every island receives the same two migrants
    assert bb["0"] == bb["1"] == bb["2"] and len(bb["0"]) == 2
    # one island: nothing moves
    assert compute_migration("ring", pops[:1], 2) == {"0": []}


# -- GevoML injection hook --------------------------------------------------

def test_migrant_injection_replaces_worst(tiny_workload):
    s = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=0,
               init_mutations=1)
    res = s.run(generations=1)
    donor = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=99,
                   init_mutations=1)
    dres = donor.run(generations=1)
    migrants = [i.patch for i in dres.pareto[:2]]
    res2 = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=0,
                  init_mutations=1).run(generations=1, migrants=migrants)
    assert len(res2.population) == len(res.population)   # size preserved
    pop_patches = {i.patch for i in res2.population}
    fresh = [m for m in migrants if m not in {i.patch for i in res.population}]
    assert all(m in pop_patches for m in fresh[:3])      # migrants landed


def test_migrant_injection_is_rng_neutral(tiny_workload):
    """The injection step itself must consume no search RNG (the resume
    machinery depends on it): with zero generations, a run with migrants
    leaves the RNG in exactly the state of a run without them.  (Later
    generations legitimately diverge — the injected individuals change
    which programs mutation samples against.)"""
    a = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=0,
               init_mutations=1)
    a.run(generations=0)
    state_a = a.rng.bit_generator.state
    donor = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=7,
                   init_mutations=1).run(generations=1)
    b = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=0,
               init_mutations=1)
    b.run(generations=0, migrants=[i.patch for i in donor.pareto])
    assert b.rng.bit_generator.state == state_a


# -- orchestrator end-to-end ------------------------------------------------

@pytest.fixture(scope="module")
def island_run(tiny_workload, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("islands"))
    orch = IslandOrchestrator(tiny_workload, root_dir=root, n_islands=3,
                              pop_size=6, migrate_every=2, n_migrants=2,
                              topology="ring")
    return root, orch, orch.run(generations=4)


def test_island_search_basics(island_run):
    root, orch, res = island_run
    assert len(res.islands) == 3 and len(res.pareto) >= 1
    objs = np.array([i.fitness for i in res.pareto])
    for i in range(len(objs)):          # mutual non-domination
        for j in range(len(objs)):
            if i != j:
                assert not (np.all(objs[i] <= objs[j])
                            and np.any(objs[i] < objs[j]))
    assert set(res.pareto_sources) <= set(res.names)
    # every island ran all 4 generations
    assert all(len(r.history) == 4 for r in res.islands)


def test_island_state_on_disk(island_run):
    root, orch, res = island_run
    manifest = json.load(open(os.path.join(root, "manifest.json")))
    assert manifest["workload_fingerprint"] == orch.fingerprint
    assert [r["round"] for r in manifest["rounds"]] == [1]
    assert manifest["rounds"][0]["start_gen"] == 2
    migrants = manifest["rounds"][0]["migrants"]
    assert set(migrants) == {"0", "1", "2"}
    assert all(len(v) == 2 for v in migrants.values())   # ring, 2 migrants
    assert os.path.exists(os.path.join(root, "cache.jsonl"))
    for name in res.names:
        assert os.path.exists(os.path.join(root, name, "latest.json"))


def test_shared_cache_cross_island_hits(island_run):
    _, _, res = island_run
    # at minimum the original program's fitness is measured once and
    # consumed by every other island; migrants add more
    assert res.cross_island_hits >= 1
    assert res.cache_stats["entries"] > 0


def test_single_island_equals_plain_gevoml(tiny_workload, tmp_path):
    spec = IslandSpec(name="solo", seed=3, operators="all",
                      mutation_rate=0.5, init_mutations=2)
    orch = IslandOrchestrator(tiny_workload, root_dir=str(tmp_path),
                              specs=[spec], pop_size=4, n_elite=2)
    res = orch.run(generations=2)
    plain = GevoML(tiny_workload, pop_size=4, n_elite=2, seed=3,
                   init_mutations=2, operators="all").run(generations=2)
    assert _key_pareto(res.islands[0]) == _key_pareto(plain)
    assert _key_pareto(res) == _key_pareto(plain) or \
        {k for k in _key_pareto(res)} == {k for k in _key_pareto(plain)}
    assert res.migration_log == []


# -- fault-tolerant resume --------------------------------------------------

def test_resume_at_round_boundary_bit_exact(tiny_workload, tmp_path):
    """Kill at a migration boundary; the resume also *extends* the target
    generation count — both must replay to the uninterrupted trajectory."""
    kw = dict(n_islands=2, pop_size=4, migrate_every=2, n_migrants=1,
              topology="full")
    full = IslandOrchestrator(tiny_workload,
                              root_dir=str(tmp_path / "full"), **kw)
    r_full = full.run(generations=4)
    split_root = str(tmp_path / "split")
    IslandOrchestrator(tiny_workload, root_dir=split_root,
                       **kw).run(generations=2)
    r_resumed = IslandOrchestrator(tiny_workload, root_dir=split_root,
                                   **kw).run(generations=4, resume=True)
    assert _key_pareto(r_resumed) == _key_pareto(r_full)
    assert r_resumed.migration_log == r_full.migration_log
    for a, b in zip(r_full.islands, r_resumed.islands):
        assert _key_pop(a) == _key_pop(b)


def test_resume_mid_epoch_bit_exact(tiny_workload, tmp_path):
    """Kill after one island checkpointed a mid-epoch generation (the other
    still behind): resume must replay injection for the laggard only and
    reach the uninterrupted result."""
    kw = dict(n_islands=2, pop_size=4, migrate_every=2, n_migrants=1,
              topology="ring")
    r_full = IslandOrchestrator(tiny_workload,
                                root_dir=str(tmp_path / "full"),
                                **kw).run(generations=5)

    class Kill(Exception):
        pass

    def bomb(name, gen, row):
        if name == "island-0" and gen == 2:   # first gen of epoch 1
            raise Kill

    kill_root = str(tmp_path / "kill")
    with pytest.raises(Kill):
        IslandOrchestrator(tiny_workload, root_dir=kill_root,
                           **kw).run(generations=5, on_generation=bomb)
    r_resumed = IslandOrchestrator(tiny_workload, root_dir=kill_root,
                                   **kw).run(generations=5, resume=True)
    assert _key_pareto(r_resumed) == _key_pareto(r_full)
    assert r_resumed.migration_log == r_full.migration_log


def test_resume_rejects_config_drift(tiny_workload, tmp_path):
    kw = dict(n_islands=2, pop_size=4, migrate_every=2, n_migrants=1)
    IslandOrchestrator(tiny_workload, root_dir=str(tmp_path),
                       **kw).run(generations=2)
    other = IslandOrchestrator(tiny_workload, root_dir=str(tmp_path),
                               n_islands=2, pop_size=4, migrate_every=3,
                               n_migrants=1)
    with pytest.raises(ValueError, match="migrate_every"):
        other.run(generations=4, resume=True)


def test_resume_rejects_other_workload(tmp_path, tiny_workload):
    IslandOrchestrator(tiny_workload, root_dir=str(tmp_path), n_islands=2,
                       pop_size=4).run(generations=2)
    other_w = build_twofc_training_workload(**{**_TINY, "steps": 7})
    orch = IslandOrchestrator(other_w, root_dir=str(tmp_path), n_islands=2,
                              pop_size=4)
    with pytest.raises(ValueError, match="different workload"):
        orch.run(generations=4, resume=True)


def test_process_mode_refuses_on_tpu(tiny_workload, tmp_path, monkeypatch):
    """Process-mode islands spawn workers that each need the chip; on a TPU
    they are refused up front instead of hanging or running elsewhere."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    orch = IslandOrchestrator(tiny_workload, root_dir=str(tmp_path),
                              n_islands=2, pop_size=6, migrate_every=2,
                              processes=True)
    with pytest.raises(RuntimeError, match="process that holds the chip"):
        orch.run(generations=2)


# -- process mode (spawn is slow: slow tier) --------------------------------

@pytest.mark.slow
def test_process_mode_identical_to_inprocess(tiny_workload, tmp_path):
    kw = dict(n_islands=2, pop_size=6, migrate_every=2, n_migrants=1,
              topology="full")
    r_in = IslandOrchestrator(tiny_workload,
                              root_dir=str(tmp_path / "inproc"),
                              **kw).run(generations=4)
    r_pr = IslandOrchestrator(tiny_workload,
                              root_dir=str(tmp_path / "proc"),
                              processes=True, **kw).run(generations=4)
    assert _key_pareto(r_in) == _key_pareto(r_pr)
    assert r_in.migration_log == r_pr.migration_log
    for a, b in zip(r_in.islands, r_pr.islands):
        assert _key_pop(a) == _key_pop(b)
