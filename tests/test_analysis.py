"""The static-analysis layer: dataflow passes, the patch-effect classifier,
the schedule linter, evaluator screening (must be bit-exact with unscreened
search), and the `python -m repro.core.analysis` CLI."""

import json
import os

import numpy as np
import pytest

from repro.core.analysis import (Diagnostic, block_divisibility,
                                 canonical_fingerprint, dead_ops,
                                 def_use_chains, eliminate_dead,
                                 fold_constants, live_values, make_screen,
                                 normalize, vmem_capacity)
from repro.core.analysis.__main__ import main as analysis_cli
from repro.core.analysis.lint import (lint_any_genome, lint_artifact,
                                      lint_genome, lint_path,
                                      split_joint_genome)
from repro.core.builder import Builder
from repro.core.edits import Edit, EditError, Patch
from repro.core.evaluator import SerialEvaluator
from repro.core.edits.stats import OperatorStats
from repro.core.fitness import InvalidVariant
from repro.core.interp import evaluate
from repro.core.search import GevoML
from repro.kernels.costs import gate_message, schedule_gates, schedule_time
from repro.kernels.workloads import (BASELINES, SHAPES,
                                     build_joint_kernel_workload,
                                     build_kernel_workload, kernel_artifact)
from repro.workloads.twofc import build_twofc_training_workload

_TINY = dict(batch=32, hidden=16, steps=5, n_train=256, n_test=256)


@pytest.fixture(scope="module")
def tiny_workload():
    return build_twofc_training_workload(**_TINY)


def _mlp():
    b = Builder("mlp")
    x = b.input("x", (4, 8))
    w1 = b.const(np.random.RandomState(0).randn(8, 16).astype(np.float32))
    h = b.relu(b.dot(x, w1))
    w2 = b.const(np.random.RandomState(1).randn(16, 6).astype(np.float32))
    b.output(b.softmax(b.dot(h, w2)))
    return b.done()


# -- dataflow ----------------------------------------------------------------

def test_def_use_and_liveness():
    p = _mlp()
    chains = def_use_chains(p)
    live = live_values(p)
    # every op that feeds the output transitively is live
    assert all(op.result in live for op in p.ops
               if op.result in {u for us in chains.values() for u, _ in us}
               or op.result in {o for o in p.outputs})
    assert not dead_ops(p)  # the MLP uses everything it computes


def test_dce_removes_dead_and_preserves_outputs():
    p = _mlp()
    # graft a dead subgraph: a const nothing consumes
    b = Builder("dead")
    x = b.input("x", (4, 8))
    d = b.const(np.ones((3, 3), np.float32))
    dd = b.relu(d)   # dead chain of two
    b.output(b.relu(x))
    q = b.done()
    n_dead = len(dead_ops(q))
    assert n_dead >= 2   # the const and its relu chain (relu may expand)
    slim = eliminate_dead(q)
    assert not dead_ops(slim)
    assert len(slim.ops) == len(q.ops) - n_dead
    inp = {"x": np.random.RandomState(2).randn(4, 8).astype(np.float32)}
    outs_full = [np.asarray(o) for o in evaluate(q, inp)]
    outs_slim = [np.asarray(o) for o in evaluate(slim, inp)]
    for a, b2 in zip(outs_full, outs_slim):
        assert np.array_equal(a, b2)   # bit-identical, not just close


def test_fold_constants_is_bit_exact():
    b = Builder("fold")
    x = b.input("x", (2, 3))
    c1 = b.const(np.full((2, 3), 2.0, np.float32))
    c2 = b.const(np.full((2, 3), 3.0, np.float32))
    s = b.add(c1, c2)            # foldable: const + const
    b.output(b.add(x, s))
    p = b.done()
    folded = fold_constants(p)
    folded.verify()
    inp = {"x": np.random.RandomState(3).randn(2, 3).astype(np.float32)}
    a = [np.asarray(o) for o in evaluate(p, inp)]
    c = [np.asarray(o) for o in evaluate(folded, inp)]
    for u, v in zip(a, c):
        assert np.array_equal(u, v)
    # the add-of-consts became a constant: one fewer add survives normalize
    assert sum(op.opcode == "add" for op in normalize(p).ops) < \
        sum(op.opcode == "add" for op in p.ops)


def test_fold_keeps_scalar_type():
    """A reshape of a (1, 1) constant to a scalar folds to a 0-d constant,
    matching the op's recorded scalar type."""
    b = Builder("fold_scalar")
    x = b.input("x", (2, 3))
    s = b.reshape(b.const(np.full((1, 1), 1.5, np.float32)), ())
    b.output(s, b.add(x, b.broadcast(s, (2, 3), ())))
    p = b.done()
    folded = fold_constants(p)
    folded.verify()
    (c,) = [op for op in folded.ops if op.result == s]
    assert c.opcode == "constant" and c.attrs["value"].shape == ()
    inp = {"x": np.random.RandomState(4).randn(2, 3).astype(np.float32)}
    want = [np.asarray(o) for o in evaluate(p, inp)]
    got = [np.asarray(o) for o in evaluate(folded, inp)]
    for u, v in zip(want, got):
        assert u.dtype == v.dtype and u.shape == v.shape
        assert np.array_equal(u, v)


def test_canonical_fingerprint_ignores_dead_code_and_uids():
    p = _mlp()
    f0 = canonical_fingerprint(normalize(p))
    # dead edit: a const no output consumes
    q = p.clone()
    b = Builder("padded")
    x = b.input("x", (4, 8))
    b.const(np.zeros((2, 2), np.float32))
    b.output(b.relu(x))
    # same semantic program with different uids: renumber by round-trip
    r = eliminate_dead(p.clone())
    assert canonical_fingerprint(normalize(r)) == f0
    assert canonical_fingerprint(normalize(_mlp())) == f0


# -- diagnostics: one source of gate truth -----------------------------------

def test_diagnostic_messages_match_gate_messages():
    # a genome that fails the divisibility gate: 48 does not divide 512
    bad = dict(BASELINES["rmsnorm"], block_rows=48)
    gates = schedule_gates("rmsnorm", bad, **SHAPES["rmsnorm"])
    lane = [not ok for _, ok, *_ in gates]
    legacy = gate_message(gates, lane)
    d = block_divisibility("rmsnorm", 512, 48)
    assert d.message == legacy == "rmsnorm: block 48 does not divide dim 512"
    assert d.is_error and d.code == "block-divisibility"
    v = vmem_capacity("flash_attention", 48 * 2**20, 16 * 2**20)
    assert "VMEM working set 48.0 MB exceeds 16 MB" in v.message


def test_diagnostic_doc_roundtrip_and_severity():
    d = block_divisibility("rmsnorm", 512, 48, knob="block_rows",
                           hint="try 128")
    assert Diagnostic.from_doc(d.to_doc()) == d
    assert "hint: try 128" in d.format()
    with pytest.raises(ValueError):
        Diagnostic(code="x", severity="fatal", subject="s", message="m")


# -- the schedule linter -----------------------------------------------------

def test_lint_genome_flags_bad_block_with_fix_hint():
    # the single-kernel space is launchable-by-construction, so widen the
    # declared choices to include a non-dividing block (the joint space has
    # these) and exercise the gate diagnostics
    w = build_kernel_workload("rmsnorm", time_mode="static")
    choices = {k: tuple(w.space.choices(k)) for k in w.space.names()}
    choices["block_rows"] = choices["block_rows"] + (48,)
    diags = lint_genome("rmsnorm", dict(BASELINES["rmsnorm"], block_rows=48),
                        choices=choices)
    errs = [d for d in diags if d.is_error]
    assert len(errs) == 1
    assert errs[0].message == "rmsnorm: block 48 does not divide dim 512"
    assert errs[0].knob and "block_rows" in errs[0].knob
    assert errs[0].hint and "launchable block_rows choices" in errs[0].hint


def test_lint_genome_ref_impl_marks_inert_knobs():
    diags = lint_genome("rmsnorm", dict(BASELINES["rmsnorm"], impl="ref"))
    inert = [d for d in diags if d.code == "knob-inert"]
    assert {d.knob for d in inert} == {"block_rows", "epilogue"}
    assert not any(d.is_error for d in diags)


def test_lint_genome_unknown_kernel_and_bad_choice():
    assert any(d.is_error for d in lint_genome("nope", {}))
    diags = lint_genome("rmsnorm", dict(BASELINES["rmsnorm"], block_rows=7))
    errs = [d for d in diags if d.is_error]
    assert errs and "declared choices" in (errs[0].hint or "")


def test_lint_joint_genome_split_and_order():
    w = build_joint_kernel_workload()
    genome = w.space.decode(w.program)
    sub = split_joint_genome(genome)
    assert set(sub) == {"rmsnorm", "flash_attention", "mamba_scan"}
    assert not any(d.is_error for d in lint_any_genome(genome))
    bad = dict(genome)
    bad["rmsnorm.block_rows"] = 48
    assert any(d.is_error for d in lint_any_genome(bad))


def test_lint_artifact_and_path(tmp_path):
    from repro.core.deploy import ArtifactRegistry
    art = kernel_artifact("rmsnorm", BASELINES["rmsnorm"],
                          fitness=(1e-6, 0.0))
    assert not any(d.is_error for d in lint_artifact(art))
    reg = ArtifactRegistry(str(tmp_path))
    reg.export(art)
    results = lint_path(str(tmp_path))
    assert len(results) == 1 and not any(
        d.is_error for _, diags in results for d in diags)


# -- the patch-effect classifier ---------------------------------------------

def test_program_screen_invalid_matches_execution(tiny_workload):
    w = tiny_workload
    screen = make_screen(w)
    # deleting ops until an output weight vanishes reproduces the runtime
    # "variant lost weight outputs" / shape-drift errors; find one by search
    rng = np.random.default_rng(0)
    from repro.core.edits import sample_edit
    hits = 0
    for _ in range(300):
        try:
            edits = tuple(sample_edit(w.program, rng)
                          for _ in range(int(rng.integers(1, 5))))
            patch = Patch(edits)
            res = screen.classify(patch)
        except Exception:
            continue
        if res.label != "invalid":
            continue
        hits += 1
        # the evaluator folds EditError (apply failure) and InvalidVariant
        # (contract violation) into invalid outcomes the same way
        with pytest.raises((EditError, InvalidVariant)) as ei:
            w.evaluate(patch.apply(w.program))
        assert str(ei.value) == res.outcome.error  # byte-identical message
        if hits >= 3:
            break
    assert hits, "screen never produced an invalid verdict to check"


def _joint_patches(w, n=400, seed=0):
    """Random attr_tweak patches over the joint schedule program."""
    from repro.core.edits import OperatorWeights, sample_edit
    rng = np.random.default_rng(seed)
    weights = OperatorWeights.of(attr_tweak=1.0)
    for _ in range(n):
        try:
            yield Patch(tuple(sample_edit(w.program, rng, weights)
                              for _ in range(int(rng.integers(1, 4)))))
        except EditError:
            continue


def test_kernel_screen_invalid_matches_gate_message():
    # the joint space declares non-dividing blocks, so random tweaks hit the
    # launch gates; every invalid verdict's message must match execution's
    w = build_joint_kernel_workload()
    screen = make_screen(w)
    hits = 0
    for patch in _joint_patches(w):
        res = screen.classify(patch)
        if res.label != "invalid":
            continue
        hits += 1
        with pytest.raises((EditError, InvalidVariant)) as ei:
            w.evaluate(patch.apply(w.program))
        assert str(ei.value) == res.outcome.error
        if hits >= 3:
            break
    assert hits, "no invalid verdict found in the joint space"


def test_kernel_screen_equivalent_inherits_exact_fitness():
    w = build_joint_kernel_workload()
    screen = make_screen(w)
    ev = SerialEvaluator(w)
    for patch in _joint_patches(w, seed=1):
        res = screen.classify(patch)
        if res.label != "novel" or res.resolved:
            continue
        executed = ev.evaluate_one(patch)
        if not executed.ok:
            continue
        screen.observe(res, executed)
        # re-classifying the same patch now hits the seen canonical class
        again = screen.classify(patch)
        assert again.label == "equivalent" and again.resolved
        assert again.outcome.fitness == executed.fitness
        assert again.outcome.error is None
        break
    else:
        pytest.fail("no executable novel patch found")
    ev.close()


def test_screen_unseen_equivalent_downgrades_to_novel(tiny_workload):
    screen = make_screen(tiny_workload)
    res = screen.classify(Patch(()))   # empty patch: the baseline itself
    # baseline's class is known a priori -> "noop", but unseen: unresolved
    assert res.label == "noop" and not res.resolved


# -- evaluator screening: bit-exact with unscreened search -------------------

def _run(workload, screen, **kw):
    ev = SerialEvaluator(workload)
    s = GevoML(workload, seed=5, evaluator=ev, screen=screen, **kw)
    res = s.run(generations=3)
    stats = ev.stats()
    ev.close()
    return res, stats


def test_screened_search_is_bit_exact(tiny_workload):
    base, bs = _run(tiny_workload, False, pop_size=8, n_elite=4)
    scr, ss = _run(tiny_workload, True, pop_size=8, n_elite=4)
    assert [i.fitness for i in base.population] == \
        [i.fitness for i in scr.population]
    assert sorted(i.fitness for i in base.pareto) == \
        sorted(i.fitness for i in scr.pareto)
    assert ss["n_evals"] + ss["n_screened"] == bs["n_evals"]
    assert bs["n_screened"] == 0


def test_screened_kernel_search_is_bit_exact():
    kw = dict(pop_size=8, n_elite=4, init_mutations=2, mutation_rate=0.9,
              operators={"attr_tweak": 1.0})
    w = build_joint_kernel_workload()
    base, bs = _run(w, False, **kw)
    scr, ss = _run(w, True, **kw)
    assert [i.fitness for i in base.population] == \
        [i.fitness for i in scr.population]
    assert ss["n_screened"] > 0       # the joint space has non-launchable
    assert "invalid" in ss["screened_by"]  # blocks, so invalids must screen


def test_screen_counters_checkpoint_and_resume(tiny_workload, tmp_path):
    ev = SerialEvaluator(tiny_workload)
    s = GevoML(tiny_workload, seed=5, pop_size=8, n_elite=4, evaluator=ev,
               screen=True, checkpoint_dir=str(tmp_path))
    s.run(generations=3)
    n_screened, by = ev.n_screened, dict(ev.screened_by)
    ck = json.load(open(tmp_path / "latest.json"))
    assert ck["counters"]["evaluator"]["n_screened"] == n_screened
    ev2 = SerialEvaluator(tiny_workload)
    s2 = GevoML(tiny_workload, seed=5, pop_size=8, n_elite=4, evaluator=ev2,
                screen=True, checkpoint_dir=str(tmp_path))
    s2.run(generations=3, resume=True)   # replay: restores counters
    assert ev2.n_screened == n_screened and dict(ev2.screened_by) == by


def test_screened_verdicts_cached_with_analysis_writer(tmp_path):
    from repro.core.evaluator import FitnessCache
    w = build_joint_kernel_workload()
    screen = make_screen(w)
    bad = next(p for p in _joint_patches(w)
               if screen.classify(p).label == "invalid")
    path = str(tmp_path / "cache.jsonl")
    ev = SerialEvaluator(w, cache=FitnessCache(path, writer="me"))
    ev.screen = make_screen(w)
    out = ev.evaluate_one(bad)
    assert out.verdict == "invalid" and ev.n_screened == 1
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["writer"] == "analysis:me"
    assert recs[0]["verdict"] == "invalid"
    # re-reading one's own screened record is NOT a cross-writer hit
    ev2 = SerialEvaluator(w, cache=FitnessCache(path, writer="me"))
    assert ev2.evaluate_one(bad).cached
    assert ev2.cache.cross_hits == 0
    ev.close(), ev2.close()


def test_tensor_evaluator_screened_matches_python(tmp_path):
    from repro.core.tensor_evo import make_tensor_evaluator
    kw = dict(pop_size=8, n_elite=4, init_mutations=2, mutation_rate=0.9,
              operators={"attr_tweak": 1.0})
    w = build_joint_kernel_workload()
    base, _ = _run(w, False, **kw)
    ev = make_tensor_evaluator(w, screen=True)
    assert ev.screen is not None
    s = GevoML(w, seed=5, evaluator=ev, **kw)
    res = s.run(generations=3)
    assert [i.fitness for i in base.population] == \
        [i.fitness for i in res.population]
    assert ev.n_screened > 0
    ev.close()


def test_operator_stats_screen_fields_roundtrip():
    st = OperatorStats(names=("copy",))
    st.count_screened(("copy", "copy"), "noop")     # per-edit attribution
    st.count_screened(("copy",), "novel")           # novel: not counted
    row = st.snapshot()["copy"]
    assert row["noop"] == 2 and row["invalid"] == 0
    assert OperatorStats.from_doc(st.to_doc()).snapshot() == st.snapshot()
    legacy = OperatorStats.from_doc({"copy": {"proposed": 3}})
    assert legacy.snapshot()["copy"]["equivalent"] == 0  # tolerant reader


# -- the CLI -----------------------------------------------------------------

def test_cli_lint_artifact_registry(tmp_path, capsys):
    from repro.core.deploy import ArtifactRegistry
    reg = ArtifactRegistry(str(tmp_path))
    reg.export(kernel_artifact("rmsnorm", BASELINES["rmsnorm"]))
    assert analysis_cli(["lint", str(tmp_path), "--strict"]) == 0
    assert "ok" in capsys.readouterr().out
    reg.export(kernel_artifact(
        "flash_attention",
        dict(BASELINES["flash_attention"], block_q=48)))
    assert analysis_cli(["lint", str(tmp_path), "--strict"]) == 1
    out = capsys.readouterr().out
    assert "FAIL" in out and "not among the declared choices" in out


def test_cli_explain_and_diff_on_search_outputs(tiny_workload, tmp_path,
                                                capsys):
    ev = SerialEvaluator(tiny_workload)
    s = GevoML(tiny_workload, seed=5, pop_size=8, n_elite=4, evaluator=ev,
               checkpoint_dir=str(tmp_path / "ck"))
    res = s.run(generations=2)
    front = str(tmp_path / "front.json")
    res.export_front(front)
    ev.close()
    assert analysis_cli(["explain", front, "--member", "0"]) == 0
    assert "pass --workload" in capsys.readouterr().out
    # --workload twofc builds the DEFAULT config: fingerprint must warn
    ck = str(tmp_path / "ck" / "latest.json")
    assert analysis_cli(["explain", ck, "--member", "0",
                         "--workload", "twofc"]) == 0
    out = capsys.readouterr().out
    assert "fingerprint mismatch" in out and "verdict:" in out
    assert analysis_cli(["diff", front, ck, "--member-a", "0",
                         "--member-b", "0", "--workload", "twofc"]) == 0
    out = capsys.readouterr().out
    assert "EQUIVALENT" in out or "DIFFERENT" in out


def test_cli_explain_genome_against_baseline(tmp_path, capsys):
    from repro.core.deploy import ArtifactRegistry
    reg = ArtifactRegistry(str(tmp_path))
    reg.export(kernel_artifact("rmsnorm",
                               dict(BASELINES["rmsnorm"], block_rows=256)))
    assert analysis_cli(["explain", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "(baseline: 128)" in out and "impl = 'pallas'  (baseline)" in out
