"""Multi-replica router coverage: the differential harness (router over N
replicas bit-exact against the unbatched one-shot oracle on dense, MoE and
SSM smoke configs), the fault-injection paths (replica death mid-replay,
crashing steps, heartbeat lapses, total outage), the liveloop canary
rolling back a plan whose replicas die, and the CLI smoke contract."""

import json

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.deploy import (Router, ServeEngine, build_router,
                               oneshot_generate)
from repro.core.deploy.engine import DEFAULT_SERVE_PLAN, ServeRequest
from repro.core.deploy.router import main as router_main
from repro.core.evaluator import FitnessCache
from repro.core.liveloop import (ROLLED_BACK, Guardrails,
                                 LiveLoopController, genome_fingerprint,
                                 synthesize)
from repro.core.liveloop.traces import replay
from repro.models.transformer import init_params


@pytest.fixture(scope="module")
def qwen():
    cfg = smoke_config("qwen3-0.6b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _reqs(prompts, gen):
    return [ServeRequest(uid=f"r{i}", tokens=p, max_new_tokens=gen)
            for i, p in enumerate(prompts)]


def _two_replica_router(cfg, params, *, max_len, max_slots=2,
                        prefill_chunk=1):
    engines = [ServeEngine(cfg, params, max_len=max_len,
                           max_slots=max_slots,
                           prefill_chunk=prefill_chunk, seed=i)
               for i in range(2)]
    return Router(engines)


class TestDifferentialOracle:
    """The tentpole property: every request through the router over N
    replicas is bit-identical to running it alone through the unbatched
    (B=1 one-shot) path — an oracle that shares no routing code."""

    @pytest.mark.parametrize("arch", ("qwen3-0.6b",        # dense
                                      "granite-moe-3b-a800m",   # MoE
                                      "falcon-mamba-7b"))  # SSM
    def test_router_matches_oneshot(self, arch):
        cfg = smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        prompts = _prompts(cfg, (8, 4, 8, 4, 6), seed=3)
        gen = 4
        refs = [oneshot_generate(cfg, params, p[None, :], gen)[0].tolist()
                for p in prompts]
        router = _two_replica_router(cfg, params, max_len=12)
        res = {r.uid: r for r in router.run(_reqs(prompts, gen),
                                            stagger=2)}
        assert len(res) == len(prompts)
        for i, ref in enumerate(refs):
            assert res[f"r{i}"].tokens == ref, \
                f"{arch} request {i} diverged from the one-shot oracle"
        # traffic really fanned out: both replicas completed work
        per = router.stats()["per_replica"]
        assert all(row["n_completed"] > 0 for row in per)

    def test_build_router_resolves_plan(self, qwen):
        """build_router turns a serve-plan genome into replicas with the
        plan's clamped slot count — and stays bit-exact."""
        cfg, params = qwen
        genome = dict(DEFAULT_SERVE_PLAN, replicas=2, max_slots=4,
                      kv_dtype="int8")
        router = build_router(cfg, params, genome=genome, max_len=12)
        assert router.n_live == 2
        assert router.plan.dtype == "int8"
        assert all(r.engine.max_slots ==
                   router.plan.effective_slots(4, 12)
                   for r in router.replicas)
        prompts = _prompts(cfg, (8, 8, 4), seed=5)
        refs = [oneshot_generate(cfg, params, p[None, :], 3)[0].tolist()
                for p in prompts]
        res = {r.uid: r for r in router.run(_reqs(prompts, 3), stagger=1)}
        for i, ref in enumerate(refs):
            assert res[f"r{i}"].tokens == ref

    def test_replay_drives_router_like_an_engine(self, qwen):
        """The router duck-types the engine protocol, so traces.replay —
        the liveloop's measurement loop — drives it unchanged."""
        cfg, params = qwen
        trace = synthesize("bursty", vocab=cfg.vocab, n_requests=6,
                           max_prompt=8, gen=3, seed=1)
        router = _two_replica_router(cfg, params, max_len=trace.max_len())
        report = replay(router, trace)
        assert len(report.results) == len(trace)
        assert report.n_rejected == 0
        assert report.stats["n_replicas"] == 2


class TestFaultInjection:
    def test_kill_replica_mid_replay_stays_exact(self, qwen):
        """Kill a replica mid-flight: its queued + in-flight requests drain
        to the survivor and every result still matches the oracle (greedy
        decode restarts from the prompt bit-exactly)."""
        cfg, params = qwen
        prompts = _prompts(cfg, (8, 4, 8, 4, 6, 8), seed=7)
        gen = 4
        refs = [oneshot_generate(cfg, params, p[None, :], gen)[0].tolist()
                for p in prompts]
        router = _two_replica_router(cfg, params, max_len=12)
        router.submit_many(_reqs(prompts, gen))
        router.step()
        router.step()                  # replica 0 now has work in flight
        router.kill_replica(0)
        assert router.n_requeued > 0
        router.drain()
        res = {r.uid: r for r in router.completed}
        assert len(res) == len(prompts)
        for i, ref in enumerate(refs):
            assert res[f"r{i}"].tokens == ref, \
                f"request {i} diverged across the failover"
        s = router.stats()
        assert s["n_live"] == 1 and s["n_rejected"] == 0
        dead = s["per_replica"][0]
        assert not dead["alive"] and dead["fail_reason"] == "killed"
        assert s["n_requeued"] == router.n_requeued

    def test_crashing_step_fails_replica_not_router(self, qwen):
        """A replica whose begin_step raises is failed and drained; the
        router finishes the backlog on the survivor."""
        cfg, params = qwen
        prompts = _prompts(cfg, (6, 6, 6, 6), seed=2)
        router = _two_replica_router(cfg, params, max_len=10)

        boom_count = [0]
        victim = router.replicas[1].engine
        orig = victim.begin_step

        def crashing():
            if victim.n_ticks >= 1:
                boom_count[0] += 1
                raise RuntimeError("device lost")
            return orig()
        victim.begin_step = crashing

        out = router.run(_reqs(prompts, 3), stagger=1)
        assert boom_count[0] == 1       # failed once, never stepped again
        assert len(out) == len(prompts)
        s = router.stats()
        assert s["n_live"] == 1
        assert "begin_step: RuntimeError: device lost" == \
            s["per_replica"][1]["fail_reason"]

    def test_heartbeat_lapse_fails_silent_replica(self, qwen):
        """The HeartbeatMonitor sweep: a replica that stops heartbeating
        (its beats dropped, as if the host went silent without crashing)
        is failed with its work re-routed, without its step ever
        raising."""
        cfg, params = qwen
        prompts = _prompts(cfg, (6, 6, 6), seed=4)
        router = Router([ServeEngine(cfg, params, max_len=10, max_slots=2,
                                     prefill_chunk=1, seed=i)
                         for i in range(2)], heartbeat_timeout=2.0)
        orig_hb = router.monitor.heartbeat

        def dropping(host, now, step_latency=None):
            if host != 1:               # replica 1's beats never arrive
                orig_hb(host, now, step_latency=step_latency)
        router.monitor.heartbeat = dropping
        router.submit_many(_reqs(prompts, 3))
        for _ in range(3):              # silence outlasts the timeout
            router.step()
        assert not router.replicas[1].alive
        assert router.replicas[1].fail_reason == "heartbeat timeout"
        router.drain()
        assert len(router.completed) == len(prompts)

    def test_total_outage_rejects_backlog_and_never_hangs(self, qwen):
        cfg, params = qwen
        prompts = _prompts(cfg, (6, 6, 6, 6), seed=6)
        router = _two_replica_router(cfg, params, max_len=10)
        router.submit_many(_reqs(prompts, 3))
        router.step()
        router.kill_replica(0, reason="power")
        router.kill_replica(1, reason="power")
        router.drain()                  # must return, not spin
        assert not router.busy
        s = router.stats()
        assert s["n_live"] == 0
        assert s["n_completed"] + s["n_rejected"] == len(prompts)
        assert s["n_rejected"] > 0
        assert set(router.rejected_uids) <= {f"r{i}"
                                             for i in range(len(prompts))}
        # stats stay well-defined after the outage
        assert s["wall_s"] >= 0.0 and s["throughput_tok_s"] >= 0.0

    def test_constructor_validation(self, qwen):
        cfg, params = qwen
        with pytest.raises(ValueError, match="at least one replica"):
            Router([])
        with pytest.raises(ValueError, match="share max_len"):
            Router([ServeEngine(cfg, params, max_len=10),
                    ServeEngine(cfg, params, max_len=12)])

    def test_router_validates_submissions(self, qwen):
        cfg, params = qwen
        router = _two_replica_router(cfg, params, max_len=8)
        assert not router.try_submit(ServeRequest(
            uid="big", tokens=np.zeros(8, np.int32), max_new_tokens=4))
        assert router.n_rejected == 1
        assert router.rejected_uids == ["big"]


class TestRouterFeedback:
    def test_publish_keys_on_full_plan(self, qwen, tmp_path):
        """Router records key on the full serving plan (replicas
        included), so they never collide with a single-engine measurement
        of the same arch."""
        cfg, params = qwen
        genome = dict(DEFAULT_SERVE_PLAN, replicas=2)
        router = build_router(cfg, params, genome=genome, max_len=12)
        router.run(_reqs(_prompts(cfg, (6, 6, 6), seed=8), 3), stagger=1)
        single = ServeEngine(cfg, params, max_len=12,
                             max_slots=DEFAULT_SERVE_PLAN["max_slots"],
                             prefill_chunk=DEFAULT_SERVE_PLAN[
                                 "prefill_chunk"])
        single.run(_reqs(_prompts(cfg, (6, 6, 6), seed=8), 3), stagger=1)
        cache = FitnessCache(str(tmp_path / "c.jsonl"), writer="serve")
        k_router = router.publish_stats(cache, name=cfg.name, shape="s",
                                        run="unit")
        k_single = single.publish_stats(cache, name=cfg.name, shape="s",
                                        run="unit")
        k_again = router.publish_stats(cache, name=cfg.name, shape="s",
                                       run="unit")
        cache.close()
        assert k_router and k_single
        assert not (set(k_router) & set(k_single))
        # this plan's key as it was when the engine also had an evolved
        # route: stores written then keep de-duplicating
        assert k_router == ["serve:8ecb7e789e2ba053a1a67ae88bb366c5e947aa03"
                            "cd1e52fbd26ead22d7244cca"]
        assert k_again == []            # first write wins, dedupe holds

    def test_fresh_router_stats_are_zeros(self, qwen):
        cfg, params = qwen
        router = _two_replica_router(cfg, params, max_len=12)
        s = router.stats()
        assert s["n_completed"] == 0 and s["wall_s"] == 0.0
        assert s["throughput_tok_s"] == 0.0
        assert s["gen_tokens"] == 0 and s["mean_latency_s"] == 0.0
        assert s["mean_ttft_s"] == 0.0 and s["s_per_token"] == 0.0
        assert len(s["per_replica"]) == 2

    def test_router_summary_matches_engine(self, qwen):
        """A one-replica router summarises the same finished requests as
        its engine, so their latency keys agree exactly."""
        cfg, params = qwen
        router = build_router(cfg, params, genome={"replicas": 1},
                              max_len=12)
        router.run(_reqs(_prompts(cfg, (6, 4, 6, 5), seed=9), 3), stagger=1)
        keys = ("n_completed", "gen_tokens", "mean_latency_s",
                "p95_latency_s", "mean_ttft_s", "s_per_token")
        want = {k: router.replicas[0].engine.stats()[k] for k in keys}
        assert want["n_completed"] == 4 and want["mean_latency_s"] > 0
        assert {k: router.stats()[k] for k in keys} == want


class TestLiveLoopPlanCanary:
    def test_plan_whose_replicas_die_rolls_back_cleanly(self, tmp_path,
                                                        monkeypatch):
        """The liveloop fault drill at plan scale: a canaried replicas=2
        plan whose replicas all die mid-measurement trips the reject-rate
        guardrail deterministically — rolled back, fingerprint blocked, no
        hang, and no torn FitnessCache rows."""
        tr = synthesize("bursty", vocab=64, n_requests=6, max_prompt=8,
                        gen=3, seed=0)
        ctl = LiveLoopController(
            str(tmp_path / "loop"), trace=tr, mode="real", pop=4,
            repeats=1, surrogate=False,
            guardrails=Guardrails(windows=1, min_throughput_ratio=0.0,
                                  max_ttft_ratio=1e9))
        genome = dict(DEFAULT_SERVE_PLAN, replicas=2)
        fp = genome_fingerprint(genome)
        assert ctl.book.propose(fp, genome, tick=0)

        orig_step = Router.step

        def dying_step(self):
            if self.n_ticks >= 1:
                for r in self.replicas:
                    if r.alive:
                        self.kill_replica(r.index, reason="injected crash")
            orig_step(self)
        monkeypatch.setattr(Router, "step", dying_step)

        # the canary measurement window, exactly as tick() runs it
        base_g = dict(DEFAULT_SERVE_PLAN)
        base_m, can_m = ctl.measure(base_g, genome, 0)
        assert base_m["reject_rate"] == 0.0      # single engine, no Router
        assert can_m["reject_rate"] > 0.0        # the dead canary rejected
        ctl._publish_window(base_g, base_m, role="baseline", tick=0)
        ctl._publish_window(genome, can_m, role="canary", tick=0)
        ctl.book.observe(tick=0, baseline=base_m, canary=can_m)
        assert ctl.book.decide(tick=0) == ROLLED_BACK
        ctl._sync_promoted()

        assert ctl.book.active is None and ctl.book.promoted is None
        assert fp in ctl.book.status()["blocked"]
        # blocked means the plan is never proposed again
        assert not ctl.book.propose(fp, genome, tick=1)
        # every cache row written through the fault is intact JSON
        cache_path = str(tmp_path / "loop" / "cache.jsonl")
        for line in open(cache_path):
            rec = json.loads(line)
            assert "fitness" in rec and "writer" in rec


class TestRouterCLI:
    def test_smoke_contract(self, capsys):
        """The CI smoke: replay a synthesized trace over 2 replicas, exit 0
        only when every accepted request completed."""
        rc = router_main(["--arch", "qwen3-0.6b", "--smoke",
                          "--replicas", "2", "--requests", "5",
                          "--max-prompt", "8", "--gen", "3",
                          "--max-slots", "2", "--prefill-chunk", "1"])
        assert rc == 0
        stats = json.loads(capsys.readouterr().out)
        assert stats["n_completed"] == 5
        assert stats["n_replicas"] == 2 and stats["n_live"] == 2
        assert stats["plan"]["replicas"] == 2

    def test_kill_at_demonstrates_failover(self, capsys, tmp_path):
        cache = str(tmp_path / "c.jsonl")
        rc = router_main(["--arch", "qwen3-0.6b", "--smoke",
                          "--replicas", "2", "--requests", "5",
                          "--max-prompt", "8", "--gen", "3",
                          "--max-slots", "2", "--prefill-chunk", "1",
                          "--kill-at", "2", "--cache", cache])
        assert rc == 0
        stats = json.loads(capsys.readouterr().out)
        assert stats["n_completed"] == 5 and stats["n_live"] == 1
        assert stats["per_replica"][0]["alive"] is False
        recs = [json.loads(line) for line in open(cache)]
        assert recs and all(r["writer"] == "serve" for r in recs)


_SHARDED = """
import json, jax
from repro.configs import smoke_config
from repro.core.deploy import build_router, oneshot_generate
from repro.core.deploy.engine import ServeRequest
from repro.launch.mesh import make_smoke_mesh
from repro.models.transformer import init_params
import numpy as np
cfg = smoke_config("qwen3-0.6b")
params = init_params(cfg, jax.random.PRNGKey(0))
router = build_router(cfg, params, genome={"replicas": 2, "max_slots": 2},
                      max_len=16, mesh=make_smoke_mesh(2, 2))
specs = [[str(ax) for ax in r.engine.batch.caches[n].sharding.spec]
         for r in router.replicas for n in ("k", "v")]
rng = np.random.default_rng(3)
prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (8, 5, 8)]
out = {r.uid: r.tokens for r in router.run(
    [ServeRequest(uid=str(i), tokens=p, max_new_tokens=4)
     for i, p in enumerate(prompts)], stagger=1)}
lanes = router.replicas[0].engine.batch.caches
print(json.dumps({
    "specs": specs, "shape": list(lanes["k"].shape),
    "served": [out[str(i)] for i in range(len(prompts))],
    "oneshot": [oneshot_generate(cfg, params, p[None], 4)[0].tolist()
                for p in prompts]}))
"""


class TestShardedReplicas:
    def test_model_axis_lands_on_kv_heads(self):
        """Two replicas on a 2x2 mesh: each replica's lane cache
        ``(L, lanes, max_len, kv_heads, hd)`` is split over ``model`` on
        its KV-head axis, not the sequence axis, and serves the one-shot
        oracle's tokens."""
        import os
        import subprocess
        import sys
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=src + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        run = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        got = json.loads(run.stdout.strip().splitlines()[-1])
        cfg = smoke_config("qwen3-0.6b")
        shape = got["shape"]
        assert shape == [cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.hd]
        assert len(got["specs"]) == 4
        for spec in got["specs"]:
            assert spec.index("model") == 3, spec   # the KV-head axis
        assert got["served"] == got["oneshot"]


@pytest.mark.flaky_quarantine
class TestWallClockThroughput:
    """Real wall-clock throughput comparisons.  Genuinely timing-sensitive
    (shared-CPU scheduling decides the margin), so this class lives in the
    flaky quarantine: the weekly workflow runs it 20x and reports the pass
    rate; tier-1 never selects it."""

    def test_two_replicas_not_slower_than_one(self, qwen):
        import statistics

        cfg, params = qwen
        reqs = _reqs(_prompts(cfg, [6, 4, 6, 4, 6, 4, 6, 4], seed=3), 6)

        def run(replicas):
            runs = []
            for rep in range(4):
                router = build_router(
                    cfg, params,
                    genome=dict(DEFAULT_SERVE_PLAN, replicas=replicas,
                                max_slots=2),
                    max_len=12, seed=0)
                router.run([ServeRequest(uid=r.uid, tokens=r.tokens,
                                         max_new_tokens=r.max_new_tokens)
                            for r in reqs])
                if rep == 0:
                    continue        # unmeasured warmup
                runs.append(router.stats()["throughput_tok_s"])
            return statistics.median(runs)

        single, double = run(1), run(2)
        assert double >= 0.9 * single, \
            (f"2-replica router fell below one replica's wall-clock "
             f"throughput: {double:.1f} vs {single:.1f} tok/s")
