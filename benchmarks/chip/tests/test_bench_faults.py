"""A run with its timed path broken underneath must come out not correct.

Each test skips the look for a chip and drives the rest of a run on the CPU,
on a cell cut to tiny widths (``tiny.py``), with one fault planted in the
program after set-up, and sees ``correct`` come out false; the same run
without the fault comes out true."""

import jax
import numpy as np
import pytest

import tiny

SEED = 2**33 + 5


def _serve(tamper=None):
    cell = tiny.serving_cell()
    rec, _ = cell.entry.run(cell, SEED, 2.0, False, jax.devices()[:1],
                            tamper=tamper)
    return rec


def _engines(server):
    return [r.engine for r in getattr(server, "replicas", [])] or [server]


def alter_tokens(server):
    """Every sampled token is replaced by its neighbour in the vocabulary,
    where the engine produces it."""
    for eng in _engines(server):
        orig = eng._sample

        def sample(logits, orig=orig, vocab=eng.cfgs["default"].vocab):
            return (orig(logits) + 1) % vocab
        eng._sample = sample


def freeze_decode_state(server):
    """The decode step hands back the cache it was given: the new token's
    keys and values are never written."""
    for eng in _engines(server):
        orig = eng._decode_dispatch

        def dispatch(eng=eng, orig=orig):
            kept = {v: jax.tree.map(jax.numpy.copy, b.caches)
                    for v, b in eng.batches.items()}
            pending = orig()
            for v, b in eng.batches.items():
                b.caches = kept[v]
            return pending
        eng._decode_dispatch = dispatch


def test_sound_serving_run_is_correct():
    rec = _serve()
    assert rec.correct, rec.checks


@pytest.mark.parametrize("fault", [alter_tokens, freeze_decode_state])
def test_serving_fault_is_not_correct(fault):
    rec = _serve(fault)
    assert not rec.correct, rec.checks


def _search(tamper=None):
    cell = tiny.search_cell()
    rec, _ = cell.entry.run(cell, SEED, 3.0, False, jax.devices()[:1],
                            tamper=tamper)
    return rec


def alter_scores(s):
    """Each candidate's error is reported 0.05 off where it is scored."""
    orig = s._evaluate

    def evaluate(program):
        t, err = orig(program)
        return t, min(1.0, err + 0.05)
    s._evaluate = evaluate


def _wrap_step(monkeypatch, make):
    import repro.core.fitness as fitness
    real = fitness.jit_program
    monkeypatch.setattr(fitness, "jit_program",
                        lambda program: make(real(program)))


def test_sound_search_run_is_correct():
    assert _search().correct


def test_search_fault_altered_score_is_not_correct():
    assert not _search(alter_scores).correct


def test_search_fault_step_returns_state_unchanged(monkeypatch):
    def make(step):
        def frozen(inputs):
            step(inputs)
            return [inputs[k] for k in ("w1", "b1", "w2", "b2")]
        return frozen
    rec = _search(lambda s: _wrap_step(monkeypatch, make))
    assert not rec.correct


def test_search_fault_half_the_batch_left_out(monkeypatch):
    """The step sees the first half of each batch twice: its mean gradient
    is over that half alone."""
    def make(step):
        def half(inputs):
            inputs = dict(inputs)
            for k in ("x", "y_onehot"):
                a = np.asarray(inputs[k])
                inputs[k] = np.concatenate([a[:len(a) // 2]] * 2)
            return step(inputs)
        return half
    rec = _search(lambda s: _wrap_step(monkeypatch, make))
    assert not rec.correct
