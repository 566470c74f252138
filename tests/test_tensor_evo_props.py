"""Differential property tests: ``core.tensor_evo.nsga2`` (TensorNSGA2)
must reproduce ``core/nsga2.py`` exactly — front ranks, crowding distances
(including inf/nan propagation), and the environmental-selection order — on
random objective matrices with duplicates, ties, non-finite values, and
masked padding lanes.

Two layers so the differential contract is exercised everywhere:

* a seeded exhaustive sweep (no external deps) over 200+ random
  populations, always on;
* hypothesis-generated populations (200 more examples across the two
  properties) when ``hypothesis`` is installed (CI installs ``.[test]``).
"""

import numpy as np
import pytest

# nan objectives make both paths warn identically; the tests assert the
# *results* agree, warnings included is just noise here
pytestmark = pytest.mark.filterwarnings("ignore::RuntimeWarning")

from repro.core import nsga2 as pynsga  # noqa: E402
from repro.core.tensor_evo import TensorNSGA2
from repro.core.tensor_evo.nsga2 import (rank_crowd, rank_select,
                                         selection_order)

# a palette that forces duplicates, exact ties, and non-finite lanes
_PALETTE = np.array([0.0, 1.0, 2.0, 0.5, -1.25, 3.0,
                     np.inf, -np.inf, np.nan])


def _random_objs(rng: np.random.Generator) -> np.ndarray:
    n = int(rng.integers(1, 20))
    m = int(rng.integers(1, 4))
    if rng.random() < 0.5:
        objs = rng.choice(_PALETTE, size=(n, m))
    else:
        # coarse grid: duplicates remain likely, arithmetic stays exact
        objs = rng.integers(-4, 5, size=(n, m)) / 4.0
    if n > 1 and rng.random() < 0.5:   # force duplicated rows
        objs[int(rng.integers(n))] = objs[int(rng.integers(n))]
    return np.asarray(objs, dtype=np.float64)


def _eq_nan(a, b) -> bool:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return bool(np.all((a == b) | (np.isnan(a) & np.isnan(b))))


def _py_order(objs: np.ndarray) -> np.ndarray:
    rank, crowd = pynsga.rank_population(objs)
    with np.errstate(invalid="ignore"):
        return np.lexsort((np.arange(len(objs)), -crowd, rank))


def check_unmasked(objs: np.ndarray, n_elite: int) -> None:
    """Tensor rank/crowd/selection == python rank/crowd/selection, exactly."""
    with np.errstate(invalid="ignore"):
        rank_p, crowd_p = pynsga.rank_population(objs)
        rank_t, crowd_t, elites_t = rank_select(objs, n_elite)
        _, _, elites_p = pynsga.rank_select(objs, n_elite)
        order_t = selection_order(rank_t, crowd_t)
    assert np.array_equal(rank_t, rank_p)
    assert _eq_nan(crowd_t, crowd_p)
    assert elites_t == elites_p
    assert np.array_equal(order_t, _py_order(objs))


def check_masked(objs: np.ndarray, valid: np.ndarray) -> None:
    """Padding lanes: rank n / crowd 0 / sorted last; valid lanes match the
    python path run on the compressed (valid-only) population."""
    n = len(objs)
    vidx = np.flatnonzero(valid)
    with np.errstate(invalid="ignore"):
        rank_t, crowd_t = rank_crowd(objs, valid)
        order_t = selection_order(rank_t, crowd_t)
        rank_p, crowd_p = pynsga.rank_population(objs[valid])
        order_p = np.lexsort((np.arange(len(vidx)), -crowd_p, rank_p))
    assert np.array_equal(rank_t[vidx], rank_p)
    assert _eq_nan(crowd_t[vidx], crowd_p)
    assert np.all(rank_t[~valid] == n)
    assert np.all(crowd_t[~valid] == 0.0)
    # the compressed python order maps back through vidx (monotone, so the
    # index tie-break is preserved); dead lanes trail in index order
    expect = list(vidx[order_p]) + list(np.flatnonzero(~valid))
    assert list(order_t) == expect


def test_seeded_sweep_200_populations():
    rng = np.random.default_rng(0)
    for _ in range(200):
        objs = _random_objs(rng)
        check_unmasked(objs, n_elite=int(rng.integers(0, len(objs) + 2)))
        valid = rng.random(len(objs)) < 0.7
        check_masked(objs, valid)


def test_all_lanes_masked_is_well_defined():
    objs = np.array([[1.0, 2.0], [3.0, 0.5]])
    rank, crowd = rank_crowd(objs, np.array([False, False]))
    assert list(rank) == [2, 2] and list(crowd) == [0.0, 0.0]
    assert list(selection_order(rank, crowd)) == [0, 1]


def test_singleton_and_identical_population():
    check_unmasked(np.array([[1.0, 2.0]]), 1)
    check_unmasked(np.full((6, 2), 3.5), 4)       # all duplicates: one front


def test_pareto_front_matches_python():
    rng = np.random.default_rng(7)
    for _ in range(50):
        objs = _random_objs(rng)
        with np.errstate(invalid="ignore"):
            assert (TensorNSGA2.pareto_front(objs)
                    == sorted(pynsga.pareto_front(objs)))


def test_jnp_backend_agrees_with_python():
    """The device path: ranks are pure comparisons (exact on any input);
    crowding/selection use only exactly-rounded ops (sub/div), so the jitted
    path agrees with the scalar engine on these populations too."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    with jax.enable_x64(True):
        fn = jax.jit(lambda o, v: rank_crowd(o, v, xp=jnp))
        for _ in range(25):
            objs = _random_objs(rng)
            valid = rng.random(len(objs)) < 0.8
            with np.errstate(invalid="ignore"):
                rank_j, crowd_j = fn(jnp.asarray(objs), jnp.asarray(valid))
                rank_n, crowd_n = rank_crowd(objs, valid)
                order_j = selection_order(jnp.asarray(rank_j),
                                          jnp.asarray(crowd_j), xp=jnp)
                order_n = selection_order(rank_n, crowd_n)
            assert np.array_equal(np.asarray(rank_j), rank_n)
            assert _eq_nan(np.asarray(crowd_j), crowd_n)
            assert np.array_equal(np.asarray(order_j), order_n)


# ---- hypothesis layer -------------------------------------------------------
# NOT importorskip at module scope: that would skip the always-on seeded
# sweep above too.  The seeded layer runs everywhere; this layer adds 200
# generated examples when hypothesis is installed (CI installs .[test]).

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    st = None


def test_hypothesis_layer_present_or_skipped():
    if st is None:
        pytest.skip("hypothesis not installed (pip install .[test]); "
                    "the seeded 200-population sweep above still ran")


if st is not None:
    _vals = st.sampled_from([float(v) for v in _PALETTE[:-1]]
                            + [float("nan")])

    @st.composite
    def _objs_strategy(draw):
        n = draw(st.integers(1, 16))
        m = draw(st.integers(1, 3))
        rows = draw(st.lists(st.lists(_vals, min_size=m, max_size=m),
                             min_size=n, max_size=n))
        return np.asarray(rows, dtype=np.float64)

    @settings(max_examples=100, deadline=None)
    @given(objs=_objs_strategy(), n_elite=st.integers(0, 20))
    def test_hypothesis_unmasked_parity(objs, n_elite):
        check_unmasked(objs, n_elite)

    @settings(max_examples=100, deadline=None)
    @given(objs=_objs_strategy(), data=st.data())
    def test_hypothesis_masked_parity(objs, data):
        valid = np.asarray(data.draw(
            st.lists(st.booleans(), min_size=len(objs),
                     max_size=len(objs))))
        check_masked(objs, valid)
