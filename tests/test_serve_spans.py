"""The span and counter recorder (``repro.core.spans``) and what
``ServeEngine`` records with it: off by default and then silent, every span,
interval and counter of a tick when on, and the same served tokens either
way, through one engine and through a two-replica router.  Also the names
the jitted serving programs carry into a profiler trace."""

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.deploy import ServeEngine, ServeRequest, build_router
from repro.core.deploy.engine import _jitted
from repro.core.spans import OFF, Spans
from repro.models.transformer import init_cache, init_params

NAMES = ("tick", "admit", "prefill", "splice", "dispatch", "fetch",
         "decode", "queue")


@pytest.fixture(scope="module")
def qwen():
    cfg = smoke_config("qwen3-0.6b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _reqs(cfg, lens=(8, 8, 4, 6, 4), gen=5, seed=3):
    rng = np.random.default_rng(seed)
    return [ServeRequest(uid=f"r{i}", max_new_tokens=gen,
                         tokens=rng.integers(0, cfg.vocab, n).astype(
                             np.int32))
            for i, n in enumerate(lens)]


def _serve(qwen, spans=None):
    cfg, params = qwen
    eng = ServeEngine(cfg, params, max_len=16, max_slots=2,
                      prefill_chunk=2, spans=spans)
    out = eng.run(_reqs(cfg), stagger=2)
    return eng, {r.uid: r for r in out}


@pytest.fixture(scope="module")
def served(qwen):
    spans = Spans()
    eng, res = _serve(qwen, spans)
    return spans, eng, res


def _of(spans, name):
    return [(a, b) for n, a, b in spans.intervals if n == name]


class TestRecorder:
    def test_span_record_count_and_window(self):
        sp = Spans()
        with sp.span("a") as t0:
            pass
        sp.record("b", 5.0, 7.5)
        sp.count("n")
        sp.count("n", 4)
        (name, a, b), rec = sp.intervals
        assert name == "a" and a == t0 <= b
        assert rec == ("b", 5.0, 7.5)
        assert sp.counters == {"n": 5}
        assert sp.durations("b") == [2.5]
        assert sp.durations("b", 5.0, 6.0) == [2.5]
        assert sp.durations("b", 5.5) == []

    def test_span_closes_when_its_body_raises(self):
        sp = Spans()
        with pytest.raises(RuntimeError):
            with sp.span("x"):
                raise RuntimeError("boom")
        assert [n for n, _, _ in sp.intervals] == ["x"]

    def test_annotate_names_each_span_in_the_trace(self, monkeypatch):
        names = []

        class Ann:
            def __init__(self, name):
                names.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        sp = Spans(annotate=True)
        with sp.span("fetch"):
            pass
        sp.record("decode", 0.0, 1.0)
        assert names == ["serve.fetch"]


class TestEngineSpans:
    def test_off_by_default_and_silent(self, qwen, monkeypatch):
        def no_annotation(name):
            raise AssertionError(f"annotated {name} with the recorder off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
        eng, res = _serve(qwen)
        assert eng.spans is OFF and len(res) == 5
        assert vars(OFF) == {}
        assert OFF.now() is None

    def test_every_span_interval_and_counter_is_recorded(self, served):
        spans, eng, _ = served
        assert {n for n, _, _ in spans.intervals} == set(NAMES)
        assert set(spans.counters) == {"prefill_tokens"}
        assert len(_of(spans, "tick")) == eng.n_ticks
        assert len(_of(spans, "prefill")) == eng.n_prefill_batches
        # the first two prompts share a length and prefill as one batch
        assert eng.n_prefill_batches < len(_reqs(eng.cfg))
        assert len(_of(spans, "decode")) == eng.n_decode_batches
        for name in NAMES:
            assert all(a <= b for a, b in _of(spans, name)), name

    def test_prefill_and_splice_lie_inside_admit(self, served):
        spans, _, _ = served
        admits = _of(spans, "admit")
        for name in ("prefill", "splice"):
            for a, b in _of(spans, name):
                assert any(s <= a and b <= e for s, e in admits), name

    def test_fetch_lies_in_prefill_or_tick(self, served):
        spans, _, _ = served
        ticks = _of(spans, "tick")
        fetches = _of(spans, "fetch")
        # one wait per prefill batch and one per decode batch
        assert len(fetches) == len(_of(spans, "prefill")) \
            + len(_of(spans, "decode"))
        for a, b in fetches:
            assert any(s <= a and b <= e for s, e in ticks)

    def test_queue_is_the_results_own_stamps(self, served):
        spans, _, res = served
        assert sorted(_of(spans, "queue")) == \
            sorted((r.t_submit, r.t_admit) for r in res.values())
        assert sorted(spans.durations("queue")) == \
            sorted(r.t_admit - r.t_submit for r in res.values())

    def test_prefill_tokens_count_the_admitted_prompts(self, qwen, served):
        spans, _, _ = served
        assert spans.counters["prefill_tokens"] == \
            sum(len(r.tokens) for r in _reqs(qwen[0]))

    def test_same_tokens_with_the_recorder_on_and_off(self, qwen, served):
        _, _, on = served
        _, off = _serve(qwen)
        assert {u: r.tokens for u, r in on.items()} == \
            {u: r.tokens for u, r in off.items()}

    def test_two_replica_router_passes_the_recorder_on(self, qwen):
        cfg, params = qwen
        genome = {"replicas": 2, "max_slots": 2, "prefill_chunk": 1}

        def serve(spans):
            router = build_router(cfg, params, genome=genome, max_len=16,
                                  spans=spans)
            out = router.run(_reqs(cfg), stagger=2)
            return router, {r.uid: r.tokens for r in out}

        spans = Spans()
        router, on = serve(spans)
        _, off = serve(None)
        assert on == off
        engines = [r.engine for r in router.replicas]
        assert all(e.spans is spans for e in engines)
        assert len(_of(spans, "tick")) == sum(e.n_ticks for e in engines)
        assert len(_of(spans, "queue")) == len(on)

    def test_router_queue_starts_when_the_router_accepts(self, qwen):
        """A request's wait in the router's own queue counts: its
        ``t_submit``, and so its ``queue`` interval, starts at the router's
        ``submit``, before any replica sees it."""
        cfg, params = qwen
        spans = Spans()
        router = build_router(cfg, params, genome={"replicas": 2,
                                                   "max_slots": 2},
                              max_len=16, spans=spans)
        reqs = _reqs(cfg, lens=(8, 4), gen=2)
        stamps = []
        for req in reqs:
            t0 = spans.now()
            router.submit(req)
            stamps.append((t0, spans.now()))
        router.run()
        res = {r.uid: r for r in router.completed}
        for req, (t0, t1) in zip(reqs, stamps):
            r = res[req.uid]
            assert t0 <= r.t_submit <= t1 < r.t_admit
            assert (r.t_submit, r.t_admit) in _of(spans, "queue")


def test_jitted_programs_carry_their_names(qwen):
    """A profiler trace names a program by its module: the prefill and the
    lane-batch decode lower to ``jit_serve_prefill`` and
    ``jit_serve_decode``."""
    cfg, params = qwen
    pre, dec = _jitted(cfg)
    plen, n_lanes, max_len = 4, 2, 8
    batch = {"tokens": np.zeros((1, plen), np.int32),
             "positions": np.arange(plen, dtype=np.int32)[None]}
    assert "jit_serve_prefill" in pre.lower(params, batch).as_text()
    caches = init_cache(cfg, n_lanes, max_len)
    tb = {"tokens": np.zeros((n_lanes, 1), np.int32),
          "positions": np.zeros((n_lanes, 1), np.int32)}
    idx = np.zeros((n_lanes,), np.int32)
    assert "jit_serve_decode" in dec.lower(params, tb, caches,
                                           idx).as_text()
