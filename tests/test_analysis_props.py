"""Hypothesis differential properties for the static-analysis passes: on
random mutants, DCE / constant folding / normalization never change what the
interpreter computes (bit-identical outputs), and every verdict the patch
screen hands out is confirmed by actually executing the variant."""

import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property tests need hypothesis (pip install "
                           ".[test])")
from hypothesis import example, given, settings, strategies as st

from repro.core.analysis import (canonical_fingerprint, eliminate_dead,
                                 fold_constants, make_screen, normalize)
from repro.core.builder import Builder
from repro.core.edits import EditError, Patch, sample_edit
from repro.core.evaluator import SerialEvaluator
from repro.core.fitness import InvalidVariant
from repro.core.interp import evaluate
from repro.workloads.twofc import build_twofc_training_workload

_TINY = dict(batch=32, hidden=16, steps=5, n_train=256, n_test=256)
_W = build_twofc_training_workload(**_TINY)


def _base_program():
    b = Builder("mlp")
    x = b.input("x", (4, 8))
    w1 = b.const(np.random.RandomState(0).randn(8, 16).astype(np.float32))
    h = b.relu(b.dot(x, w1))
    w2 = b.const(np.random.RandomState(1).randn(16, 6).astype(np.float32))
    b.output(b.softmax(b.dot(h, w2)))
    return b.done()


def _random_mutant(program, seed, max_edits=4):
    rng = np.random.default_rng(seed)
    p = program
    for _ in range(int(rng.integers(0, max_edits + 1))):
        try:
            e = sample_edit(p, rng)
            p = Patch((e,)).apply(p)
        except EditError:
            continue
    return p


def _outs(program, inputs):
    return [np.asarray(o) for o in evaluate(program, inputs)]


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000))
@example(seed=595)   # these three fold a reshape to a scalar
@example(seed=679)
@example(seed=826)
def test_passes_preserve_interp_bit_exactly(seed):
    """eliminate_dead / fold_constants / normalize on a random mutant leave
    the interpreted outputs bit-identical (not merely allclose)."""
    p = _random_mutant(_base_program(), seed)
    inputs = {"x": np.random.default_rng(seed).standard_normal(
        (4, 8)).astype(np.float32)}
    want = _outs(p, inputs)
    for pass_fn in (eliminate_dead, fold_constants, normalize):
        q = pass_fn(p)
        q.verify()
        got = _outs(q, inputs)
        assert len(got) == len(want)
        for a, b in zip(want, got):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b, equal_nan=True), pass_fn.__name__


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_canonical_collision_implies_equal_outputs(seed):
    """Two mutants with the same canonical fingerprint compute the same
    function — checked on a concrete input, bit for bit."""
    base = _base_program()
    p = _random_mutant(base, seed)
    q = _random_mutant(base, seed + 17)
    fp, fq = (canonical_fingerprint(normalize(r)) for r in (p, q))
    inputs = {"x": np.random.default_rng(seed).standard_normal(
        (4, 8)).astype(np.float32)}
    if fp == fq:
        for a, b in zip(_outs(p, inputs), _outs(q, inputs)):
            assert np.array_equal(a, b, equal_nan=True)
    # and every mutant always collides with itself post-normalization
    assert canonical_fingerprint(normalize(p.clone())) == fp


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_screen_verdicts_confirmed_by_execution(seed):
    """Whatever the screen says, execution agrees:

    * ``invalid``  — evaluating the variant raises the *same* message;
    * ``noop``     — the variant's canonical class is the baseline's, and
      executing it reproduces the baseline fitness exactly;
    * ``equivalent`` (after observing a representative) — the inherited
      fitness equals the real executed fitness, bit for bit.
    """
    rng = np.random.default_rng(seed)
    screen = make_screen(_W)
    try:
        patch = Patch(tuple(sample_edit(_W.program, rng)
                            for _ in range(int(rng.integers(1, 4)))))
        res = screen.classify(patch)
    except EditError:
        return
    if res.label == "invalid":
        with pytest.raises((EditError, InvalidVariant)) as ei:
            _W.evaluate(patch.apply(_W.program))
        assert str(ei.value) == res.outcome.error
        return
    # executable variant: run it for real
    ev = SerialEvaluator(_W)
    executed = ev.evaluate_one(patch)
    if not executed.ok:
        # dynamically invalid (e.g. non-finite weights) — the screen is
        # allowed to miss these; it must only never claim them resolved
        assert not res.resolved
        ev.close()
        return
    if res.label == "noop":
        # noop: same canonical class as the baseline program, so training is
        # semantically unchanged — identical error objective.  (The *time*
        # objective may differ: dead ops still occupy the static roofline.)
        baseline = ev.evaluate_one(Patch(()))
        assert executed.fitness[1] == baseline.fitness[1]
    # observe, then a re-classify must inherit exactly what execution found
    if not res.resolved and res.canon is not None:
        screen.observe(res, executed)
        again = screen.classify(patch)
        assert again.resolved and again.label in ("noop", "equivalent")
        assert again.outcome.fitness == executed.fitness
    ev.close()
