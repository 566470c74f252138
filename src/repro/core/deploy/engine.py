"""The continuous-batching serving loop: evolved genomes under live traffic.

The previous ``launch/serve.py`` was a one-shot demo — fix a batch of B
prompts, prefill them together, decode them in lockstep, exit.  Production
serving is a *queue*: requests arrive over time with different prompt and
generation lengths, and throughput comes from keeping the decode batch full
while new arrivals prefill.  :class:`ServeEngine` is that loop, sized for
this repo's smoke configs but shaped like the real thing:

* a **request queue** with slot admission — up to ``max_slots`` sequences
  in flight, ``prefill_chunk`` new admissions micro-batched per tick;
* **prefill/decode interleaving** — each tick admits + prefills new
  requests (grouped by prompt length, so prefill batches are pad-free) and
  advances every in-flight sequence one token (grouped by cache position,
  so grouped decode is numerically identical to lockstep decode);
* **per-variant routing** — requests route to the ``default`` model
  configuration or to an ``evolved`` one (a distribution-plan artifact's
  serve-relevant knobs applied via ``cfg.scaled``), with an A/B fraction,
  so an evolved winner can take traffic gradually;
* **measured latency feedback** — per-request TTFT / latency / tokens, and
  :meth:`publish_stats` writes per-variant (s/token, mean latency) records
  into the shared :class:`~repro.core.evaluator.FitnessCache` under a
  ``serve`` writer tag — the serving fleet reports fitness into the same
  store the search reads.

The engine's *own* schedule (``max_slots``, ``prefill_chunk``) — joined
with the KV memory plan from :mod:`~repro.core.deploy.kvplan` (page size,
cache dtype, replica layout) — is a searchable genome:
:func:`serve_schedule_space` declares the merged plan as a
:class:`~repro.core.schedule.ScheduleSpace` and :func:`build_serve_workload`
wraps a replayed request trace as a measured-fitness
:class:`~repro.core.fitness.KernelWorkload`, so ``GevoML`` evolves the
serving plan with the same engine that evolves kernels — and the winner
ships through the :class:`~repro.core.deploy.registry.ArtifactRegistry`.
The multi-replica fan-out lives in :mod:`~repro.core.deploy.router`.

Model functions are imported lazily from ``repro.models`` (this module is
the bridge between the core search stack and the launch stack, like
``core/autotune.py``).
"""

from __future__ import annotations

import hashlib
import json
import time as _time
import warnings
from collections import deque
from dataclasses import dataclass, field
from functools import lru_cache

import numpy as np

from ..evaluator import EvalOutcome, FitnessCache
from ..schedule import ScheduleSpace
from ..spans import OFF, Spans
from .kvplan import DEFAULT_KV_PLAN, KV_SPACE, KVPlan
from .registry import Artifact, shape_tag

# Model-config knobs a serving path may safely take from a distribution-plan
# artifact (training-only knobs like remat/loss_chunk are ignored).
SERVE_PLAN_KEYS = ("attn_impl", "attn_block")

# The engine's own searchable schedule + the shipped default (the old
# one-shot launcher behaved like a conservative 2-slot engine).
ENGINE_SPACE: dict[str, tuple] = {"max_slots": (1, 2, 4, 8),
                                  "prefill_chunk": (1, 2, 4)}
DEFAULT_ENGINE_SCHEDULE: dict = {"max_slots": 2, "prefill_chunk": 1}

# The full serving plan: the engine schedule joined with the KV memory /
# parallelism plan (``kvplan.KV_SPACE``) — slots × prefill chunk × page
# size × cache dtype × replica layout as ONE genome space, so the search
# trades memory residency against decode error against replica throughput
# in a single Pareto front.
SERVE_SPACE: dict[str, tuple] = {**ENGINE_SPACE, **KV_SPACE}
DEFAULT_SERVE_PLAN: dict = {**DEFAULT_ENGINE_SCHEDULE, **DEFAULT_KV_PLAN}


def serve_schedule_space(arch: str) -> ScheduleSpace:
    """The full serving plan (engine schedule + KV memory plan) as a
    searchable genome space."""
    return ScheduleSpace.of(f"serve/{arch}", SERVE_SPACE)


def apply_plan_artifact(cfg, artifact: Artifact | None):
    """The evolved model configuration for serving: the artifact's
    serve-relevant knobs applied over ``cfg`` (weights stay compatible —
    these knobs change the computation schedule, not the parameters)."""
    if artifact is None:
        return cfg
    fields = {k: artifact.genome[k] for k in SERVE_PLAN_KEYS
              if k in artifact.genome}
    return cfg.scaled(**fields) if fields else cfg


def engine_schedule_from(artifact: Artifact | None) -> dict:
    """The engine schedule an artifact prescribes (defaults filled in;
    KV-plan knobs are resolved separately — :func:`serve_plan_from`)."""
    g = dict(DEFAULT_ENGINE_SCHEDULE)
    if artifact is not None:
        g.update({k: artifact.genome[k] for k in ENGINE_SPACE
                  if k in artifact.genome})
    return g


def serve_plan_from(artifact: Artifact | None) -> dict:
    """The FULL serving plan an artifact prescribes: engine schedule plus
    KV-plan knobs, every missing knob at its shipped default — the genome
    the router and the live loop hand to :class:`~repro.core.deploy.kvplan.
    KVPlan.from_genome`."""
    g = dict(DEFAULT_SERVE_PLAN)
    if artifact is not None:
        g.update({k: artifact.genome[k] for k in SERVE_SPACE
                  if k in artifact.genome})
    return g


# --------------------------------------------------------------------------
# Requests and results
# --------------------------------------------------------------------------


@dataclass
class ServeRequest:
    """One generation request: a prompt (1-D int token array) and a token
    budget.  ``variant`` pins the route (``"default"``/``"evolved"``);
    ``None`` lets the engine's A/B fraction decide."""

    uid: str
    tokens: np.ndarray
    max_new_tokens: int = 16
    eos_id: int | None = None
    variant: str | None = None


@dataclass
class ServeResult:
    """A completed request: generated tokens, the route it took, and its
    measured timeline (submit -> admit -> first token -> done)."""

    uid: str
    variant: str
    tokens: list[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit

    @property
    def ttft(self) -> float:
        return self.t_first - self.t_submit


@dataclass
class _Lane:
    """One resident sequence in a variant's lane batch."""
    req: ServeRequest
    index: int                      # current cache length (next write pos)
    tokens: list[int]
    last: int
    res: ServeResult


class _LaneBatch:
    """A variant's fixed-width continuous batch: ``n_lanes`` resident
    sequences sharing ONE cache whose batch axis is the lane
    (``init_cache(cfg, n_lanes, max_len)``), advanced by a single decode
    dispatch per tick with a per-lane cache index.  Lane shapes never
    change, so decode compiles exactly once per variant; a finished lane's
    cache is simply overwritten at the next admission."""

    def __init__(self, n_lanes: int):
        self.n_lanes = n_lanes
        self.lanes: list[_Lane | None] = [None] * n_lanes
        self.caches = None           # allocated lazily at first admission

    def free_lanes(self) -> list[int]:
        return [i for i, l in enumerate(self.lanes) if l is None]

    def active(self) -> list[tuple[int, _Lane]]:
        return [(i, l) for i, l in enumerate(self.lanes) if l is not None]

    def n_active(self) -> int:
        return sum(1 for l in self.lanes if l is not None)


# --------------------------------------------------------------------------
# Jit function cache (shared across engine instances: an engine per genome
# during serving-schedule search must not recompile the model)
# --------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _jitted(cfg):
    """(prefill, decode) jitted for ``cfg``.  Decode takes **a per-lane
    cache index**: all in-flight sequences advance in ONE fixed-shape
    dispatch regardless of their (different) positions — the core
    continuous-batching capability the lockstep path lacks.  The lane cache
    is donated and each lane's new K/V rows are written into it in place.
    Decode returns ``(logits, caches, routed)``: ``routed`` counts, per MoE
    layer and lane, the pairs routed to experts held here ((MoE layers,
    lanes) int32; None without MoE)."""
    import jax

    from ...models.transformer import Dist, _forward, _head, prefill

    # named functions: a profiler trace shows the programs as
    # ``jit_serve_prefill`` and ``jit_serve_decode``
    def serve_prefill(p, b):
        return prefill(p, b, cfg)

    def serve_decode(p, tb, c, i):
        h, caches, routed = _forward(p, cfg, tb, Dist(), decoding=True,
                                     caches=c, index=i)
        return _head(p, h[:, -1]), caches, routed

    return jax.jit(serve_prefill), jax.jit(serve_decode, donate_argnums=(2,))


def _write_lane(lanes: dict, lane: int, one: dict):
    """Install one sequence's (B=1) cache into lane ``lane`` of the lane
    batch's cache (a device-side single-lane copy; the only per-admission
    cache traffic — decode itself writes one token per lane)."""
    import jax
    return jax.tree.map(lambda full, x: full.at[:, lane].set(x[:, 0]),
                        lanes, one)


def _batch_axis_slice(caches: dict, i: int):
    import jax
    return jax.tree.map(lambda x: x[:, i:i + 1], caches)


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching serving over one model's parameters.

    ``cfg`` is the default-route :class:`~repro.models.common.ModelConfig`;
    ``evolved_cfg`` (optional, same parameter shapes) is the evolved route,
    taking ``ab_fraction`` of unpinned requests.  ``params=None`` initializes
    random weights (the smoke/demo path).  ``max_len`` bounds
    ``prompt + generation`` per request; every slot cache is allocated at
    ``max_len`` so any group of slots can decode together.

    ``admit_max_wait`` bounds admission reordering: the prompt-length
    grouping below prefers same-length prefill batches, but any request
    queued longer than this many ticks forces strict oldest-first
    admission, so an odd-length prompt can never be starved behind a
    steady stream of grouping-friendly ones.

    ``spans`` (a :class:`~repro.core.spans.Spans`) records the tick from
    inside: ``tick``, ``admit`` with its ``prefill`` and ``splice``,
    ``dispatch``, ``fetch`` (every wait for sampled ids), the ``decode`` and
    ``queue`` intervals and the ``prefill_tokens`` count; for an MoE model,
    per decode execution, ``moe_pairs`` (busy lanes x top_k x MoE layers),
    ``moe_pairs_here`` (those routed to experts held here, fetched with the
    sampled ids), ``moe_expert_slots`` (held experts x MoE layers) and, when
    the decode computes the held experts densely (``moe.computes_densely``),
    ``moe_rows_dense`` (lanes x held experts x MoE layers, idle lanes
    included: the rows those products compute).  Off by default: the routed
    counts are then never fetched."""

    def __init__(self, cfg, params=None, *, max_len: int = 128,
                 max_slots: int = 4, prefill_chunk: int = 2,
                 evolved_cfg=None, ab_fraction: float = 0.0,
                 temperature: float = 0.0, seed: int = 0,
                 admit_max_wait: int = 32, spans: Spans | None = None):
        import jax
        if cfg.family == "encoder":
            raise ValueError("encoder-only arch has no decode step")
        if max_slots < 1 or prefill_chunk < 1:
            raise ValueError("max_slots and prefill_chunk must be >= 1")
        if admit_max_wait < 1:
            raise ValueError("admit_max_wait must be >= 1")
        self.admit_max_wait = admit_max_wait
        self.cfgs = {"default": cfg}
        if evolved_cfg is not None:
            self.cfgs["evolved"] = evolved_cfg
        self.ab_fraction = ab_fraction
        self.max_len = max_len
        self.max_slots = max_slots
        self.prefill_chunk = prefill_chunk
        self.temperature = temperature
        self._route_rng = np.random.default_rng(seed)
        self._sample_key = jax.random.PRNGKey(seed + 1)
        if params is None:
            from ...models.transformer import init_params
            params = init_params(cfg, jax.random.PRNGKey(0))
        self.params = params
        self.queue: deque[ServeRequest] = deque()
        self.batches = {v: _LaneBatch(max_slots) for v in self.cfgs}
        self.completed: list[ServeResult] = []
        self.n_rejected = 0
        self._t0: float | None = None
        self._t_last: float = 0.0
        self.n_ticks = 0
        self.n_prefill_batches = 0
        self.n_decode_batches = 0
        self.spans = OFF if spans is None else spans
        self._t_tick = self._t_dispatch = None

    # -- submission ----------------------------------------------------------
    def submit(self, req: ServeRequest, *,
               t_submit: float | None = None) -> None:
        """Queue ``req``; ``t_submit`` is when it was accepted, if earlier
        than now (a router's own queue)."""
        tokens = np.asarray(req.tokens, np.int32).reshape(-1)
        if len(tokens) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(tokens)} + "
                f"{req.max_new_tokens} new tokens exceeds max_len "
                f"{self.max_len}")
        if req.variant is not None and req.variant not in self.cfgs:
            raise ValueError(f"request {req.uid}: unknown variant "
                             f"{req.variant!r} (have {list(self.cfgs)})")
        req.tokens = tokens
        req._t_submit = _time.perf_counter() if t_submit is None \
            else t_submit
        req._enq_tick = self.n_ticks
        self.queue.append(req)

    def try_submit(self, req: ServeRequest) -> bool:
        """Admission-or-reject: like :meth:`submit` but malformed requests
        (over-budget prompt, unknown variant) are *counted*, not raised — a
        live replay loop must survive bad traffic.  Returns whether the
        request was accepted."""
        try:
            self.submit(req)
        except ValueError:
            self.n_rejected += 1
            return False
        return True

    def submit_many(self, reqs) -> None:
        for r in reqs:
            self.submit(r)

    # -- routing -------------------------------------------------------------
    def _route(self, req: ServeRequest) -> str:
        if req.variant is not None:
            return req.variant
        if "evolved" in self.cfgs and \
                self._route_rng.random() < self.ab_fraction:
            return "evolved"
        return "default"

    # -- prefill (admission) -------------------------------------------------
    def _token_batch(self, cfg, tokens_2d, positions_2d):
        # host arrays: jit transfers them straight to the device(s) holding
        # this engine's parameters, never by way of the default device
        b = {"tokens": np.asarray(tokens_2d, np.int32),
             "positions": np.asarray(positions_2d, np.int32)}
        if cfg.mrope:
            b["positions3"] = np.broadcast_to(
                b["positions"][:, :, None], positions_2d.shape + (3,))
        return b

    def _n_in_flight(self) -> int:
        return sum(b.n_active() for b in self.batches.values())

    def _select_admissions(self, n_take: int) -> list[ServeRequest]:
        """Pick ``n_take`` queued requests for this tick's prefill.

        Preference: the queue's most common prompt length (ties broken
        toward the earliest arrival), so a full chunk usually prefills as
        ONE pad-free batch; remaining seats fill oldest-first.  Bound: if
        the oldest queued request has waited ``admit_max_wait`` ticks, the
        whole pick is strict FIFO — grouping must never starve an
        odd-length prompt behind a steady stream of same-length ones."""
        q = self.queue
        if self.n_ticks - getattr(q[0], "_enq_tick", self.n_ticks) \
                >= self.admit_max_wait:
            return [q.popleft() for _ in range(n_take)]
        counts: dict[int, int] = {}
        first_at: dict[int, int] = {}
        for i, r in enumerate(q):
            plen = len(r.tokens)
            counts[plen] = counts.get(plen, 0) + 1
            first_at.setdefault(plen, i)
        best = max(counts, key=lambda p: (counts[p], -first_at[p]))
        take: list[ServeRequest] = []
        rest: list[ServeRequest] = []
        for r in q:
            if len(r.tokens) == best and len(take) < n_take:
                take.append(r)
            else:
                rest.append(r)
        while len(take) < n_take:
            take.append(rest.pop(0))
        self.queue = deque(rest)
        return take

    def _admit(self) -> None:
        n_free = self.max_slots - self._n_in_flight()
        n_take = min(n_free, self.prefill_chunk, len(self.queue))
        if n_take <= 0:
            return
        with self.spans.span("admit"):
            self._admit_some(n_take)

    def _admit_some(self, n_take: int) -> None:
        import jax
        import jax.numpy as jnp

        from ...models.transformer import init_cache
        spans = self.spans
        admitted = self._select_admissions(n_take)
        t_admit = _time.perf_counter()
        groups: dict[tuple, list[ServeRequest]] = {}
        for req in admitted:
            groups.setdefault((self._route(req), len(req.tokens)),
                              []).append(req)
        for (variant, plen), reqs in groups.items():
            cfg = self.cfgs[variant]
            batch = self.batches[variant]
            pre_fn, _ = _jitted(cfg)
            G = len(reqs)
            toks = np.stack([r.tokens for r in reqs])
            pos = np.broadcast_to(np.arange(plen, dtype=np.int32)[None],
                                  (G, plen))
            with spans.span("prefill"):
                spans.count("prefill_tokens", G * plen)
                logits, pre_caches = pre_fn(
                    self.params, self._token_batch(cfg, toks, pos))
                self.n_prefill_batches += 1
                first = self._sample(logits)
            t_first = _time.perf_counter()
            with spans.span("splice"):
                if batch.caches is None:
                    batch.caches = init_cache(cfg, batch.n_lanes,
                                              self.max_len)
                free = batch.free_lanes()
                # shapes only: the padded cache is built where the prefill
                # cache lives (the replica's own device), not on the
                # default device
                full = jax.eval_shape(
                    lambda: init_cache(cfg, 1, self.max_len))
                for i, req in enumerate(reqs):
                    mine = _batch_axis_slice(pre_caches, i)

                    def splice(f, p, _plen=plen):
                        if p.shape == f.shape:
                            return p
                        if (f.ndim >= 3 and p.ndim == f.ndim
                                and p.shape[2] == _plen
                                and f.shape[2] == self.max_len):
                            pad = [(0, 0)] * f.ndim
                            pad[2] = (0, self.max_len - _plen)
                            return jnp.pad(p.astype(f.dtype), pad)
                        raise ValueError(f"prefill cache leaf {p.shape} "
                                         f"does not fit decode cache "
                                         f"{f.shape}")
                    one = jax.tree.map(splice, full, mine)
                    tok = int(first[i])
                    res = ServeResult(
                        uid=req.uid, variant=variant,
                        t_submit=getattr(req, "_t_submit", t_admit),
                        t_admit=t_admit, t_first=t_first)
                    spans.record("queue", res.t_submit, res.t_admit)
                    lane = _Lane(req=req, index=plen, tokens=[tok],
                                 last=tok, res=res)
                    if not self._maybe_finish(lane, t_first):
                        li = free.pop(0)
                        batch.lanes[li] = lane
                        batch.caches = _write_lane(batch.caches, li, one)

    # -- decode --------------------------------------------------------------
    def _sample(self, logits, routed=None):
        """Sampled ids on the host; with ``routed`` (a device array) the pair
        ``(ids, routed)``, fetched together."""
        import jax
        import jax.numpy as jnp
        if self.temperature > 0:
            self._sample_key, sub = jax.random.split(self._sample_key)
            ids = jax.random.categorical(sub, logits / self.temperature)
        else:
            ids = jnp.argmax(logits, -1)
        with self.spans.span("fetch"):
            if routed is None:
                return np.asarray(ids).astype(np.int32)
            ids, routed = jax.device_get((ids, routed))
            return np.asarray(ids).astype(np.int32), routed

    def _decode_dispatch(self) -> list[tuple]:
        """Phase 1 of a decode tick: launch ONE decode dispatch per
        active variant and return the in-flight ``(variant, active,
        logits)`` work items *without* blocking on the results — a router
        interleaves dispatches across replicas so each replica's compute
        overlaps its siblings' host work."""
        with self.spans.span("dispatch") as self._t_dispatch:
            return self._dispatch_variants()

    def _dispatch_variants(self) -> list[tuple]:
        pending = []
        for variant in sorted(self.batches):
            batch = self.batches[variant]
            active = batch.active()
            if not active:
                continue
            cfg = self.cfgs[variant]
            _, dec_fn = _jitted(cfg)
            # ONE fixed-shape dispatch over every lane of this variant
            # (idle lanes run at index 0 and are ignored; their cache is
            # rewritten wholesale at the next admission)
            N = batch.n_lanes
            toks = np.zeros((N, 1), np.int32)
            idx = np.zeros((N,), np.int32)
            for i, lane in active:
                toks[i, 0] = lane.last
                idx[i] = lane.index
            pos = idx[:, None]
            tb = {"tokens": toks, "positions": pos}
            if cfg.mrope:
                tb["positions3"] = np.broadcast_to(pos[..., None], (N, 1, 3))
            logits, batch.caches, routed = dec_fn(self.params, tb,
                                                  batch.caches, idx)
            self.n_decode_batches += 1
            pending.append((variant, active, logits, routed))
        return pending

    def _decode_complete(self, pending: list[tuple]) -> None:
        """Phase 2 of a decode tick: sample next tokens (this is where the
        host blocks on device results) and advance lane bookkeeping."""
        for variant, active, logits, routed in pending:
            batch = self.batches[variant]
            if routed is not None and self.spans is not OFF:
                nxt, routed = self._sample(logits, routed)
                self._count_routed(self.cfgs[variant], active, routed)
            else:
                nxt = self._sample(logits)
            t_now = _time.perf_counter()
            self.spans.record("decode", self._t_dispatch, t_now)
            for i, lane in active:
                lane.index += 1
                tok = int(nxt[i])
                lane.tokens.append(tok)
                lane.last = tok
                if self._maybe_finish(lane, t_now):
                    batch.lanes[i] = None

    def _count_routed(self, cfg, active, routed) -> None:
        """The routed counters of one decode execution over its busy lanes
        ``active`` (``routed``: the (MoE layers, lanes) pairs routed to held
        experts; idle lanes are computed but not counted)."""
        from ...models.moe import computes_densely
        (n_moe, lanes), busy = routed.shape, [i for i, _ in active]
        self.spans.count("moe_pairs", len(busy) * cfg.top_k * n_moe)
        self.spans.count("moe_pairs_here", int(np.sum(routed[:, busy])))
        self.spans.count("moe_expert_slots", cfg.n_experts_here * n_moe)
        if computes_densely(lanes):
            self.spans.count("moe_rows_dense",
                             lanes * cfg.n_experts_here * n_moe)

    def _decode_tick(self) -> None:
        self._decode_complete(self._decode_dispatch())

    def _maybe_finish(self, lane: _Lane, t_now: float) -> bool:
        req = lane.req
        done = (len(lane.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and lane.last == req.eos_id))
        if done:
            lane.res.tokens = list(lane.tokens)
            lane.res.t_done = t_now
            self.completed.append(lane.res)
            self._t_last = t_now
        return done

    # -- the loop ------------------------------------------------------------
    @property
    def busy(self) -> bool:
        return bool(self.queue) or self._n_in_flight() > 0

    def begin_step(self) -> list[tuple]:
        """The first half of a tick: admit + micro-batch prefill new
        requests, then *dispatch* (without blocking) the decode batch.
        Callers that drive several engines — the multi-replica router —
        begin every replica's step before finishing any, so device compute
        overlaps across replicas."""
        self._t_tick = self.spans.now()
        if self._t0 is None:
            self._t0 = _time.perf_counter()
        self.n_ticks += 1
        self._admit()
        return self._decode_dispatch()

    def finish_step(self, pending: list[tuple]) -> None:
        """The second half of a tick: block on the dispatched decode,
        sample, and retire finished lanes."""
        self._decode_complete(pending)
        self.spans.record("tick", self._t_tick, self.spans.now())

    def step(self) -> None:
        """One engine tick: admit + micro-batch prefill new requests, then
        advance every in-flight sequence one decode step."""
        self.finish_step(self.begin_step())

    def run(self, requests=None, *, stagger: int | None = None
            ) -> list[ServeResult]:
        """Drive to completion: optionally submit ``requests`` (all upfront,
        or ``stagger`` per tick — arrivals mid-stream are what continuous
        batching exists for), then tick until queue and slots drain.
        Returns results in completion order."""
        pending = deque(requests or [])
        if stagger is None:
            self.submit_many(pending)
            pending.clear()
        n_before = len(self.completed)
        while pending or self.busy:
            for _ in range(min(stagger or 0, len(pending))):
                self.submit(pending.popleft())
            self.step()
        return self.completed[n_before:]

    # -- stats + feedback ----------------------------------------------------
    def stats(self) -> dict:
        """Aggregate measured serving stats, overall and per variant.
        Total on every path the live loop hits: before the first tick,
        mid-run before any completion, and after all-rejected admissions
        the numbers are well-defined zeros, never negative and never a
        raise.  Variants that completed nothing still get a zeroed row (so
        canary guardrails can read ``per_variant["evolved"]["n"] == 0``
        instead of catching ``KeyError``)."""
        # _t_last stays 0.0 until the first completion, so a mid-run read
        # would see a negative span; clamp to "no completed work yet".
        wall = max(self._t_last - self._t0, 0.0) \
            if self._t0 is not None else 0.0
        out = {"n_completed": len(self.completed),
               "n_rejected": self.n_rejected,
               "wall_s": round(wall, 6),
               "ticks": self.n_ticks,
               "prefill_batches": self.n_prefill_batches,
               "decode_batches": self.n_decode_batches,
               "gen_tokens": sum(len(r.tokens) for r in self.completed),
               "per_variant": {}}
        out["throughput_tok_s"] = round(
            out["gen_tokens"] / wall, 3) if wall > 0 else 0.0
        for variant in self.cfgs:
            rs = [r for r in self.completed if r.variant == variant]
            if not rs:
                out["per_variant"][variant] = {
                    "n": 0, "gen_tokens": 0, "mean_latency_s": 0.0,
                    "p95_latency_s": 0.0, "mean_ttft_s": 0.0,
                    "s_per_token": 0.0}
                continue
            lat = np.array([r.latency for r in rs])
            toks = sum(len(r.tokens) for r in rs)
            out["per_variant"][variant] = {
                "n": len(rs),
                "gen_tokens": toks,
                "mean_latency_s": round(float(lat.mean()), 6),
                "p95_latency_s": round(float(np.percentile(lat, 95)), 6),
                "mean_ttft_s": round(
                    float(np.mean([r.ttft for r in rs])), 6),
                "s_per_token": round(float(lat.sum() / max(toks, 1)), 6),
            }
        return out

    def publish_stats(self, cache: FitnessCache, *, name: str, shape,
                      run: str = "", features=None,
                      meta: dict | None = None) -> list[str]:
        """Feed measured per-variant serving fitness back into a shared
        :class:`FitnessCache` as ``serve``-tagged records (fitness =
        ``(s_per_token, mean_latency_s)``).  The key is a content hash of
        the measurement configuration — arch, shape, variant, AND the
        engine schedule — so measurements under different schedules never
        collide; like every cache record, a key already present is left
        untouched (first measurement wins), so pass a distinct ``run`` tag
        to record repeated measurements of the same configuration.
        Returns the keys of records actually added (empty if everything
        was already recorded).  Searches warm-starting from the same store
        see what deployment measured.

        ``features`` (a numeric vector, e.g. ``ScheduleFeaturizer.
        of_genome(schedule)``) makes the records *surrogate training
        rows*; ``meta`` (e.g. a :meth:`~repro.core.liveloop.traces.Trace.
        spec`) rides along on the record so live traffic can later be
        re-synthesized from the store.  Variants that completed nothing
        are skipped — a zero measurement is not a measurement."""
        if cache.writer is None:
            cache.writer = "serve"
        added = []
        for variant, rec in self.stats()["per_variant"].items():
            if rec["n"] == 0:
                continue
            body = {"kind": "serve_latency", "name": name,
                    "shape": shape_tag(shape), "variant": variant,
                    "schedule": {"max_slots": self.max_slots,
                                 "prefill_chunk": self.prefill_chunk},
                    "run": run}
            key = "serve:" + hashlib.sha256(
                json.dumps(body, sort_keys=True).encode()).hexdigest()
            if key in cache:
                continue
            cache.put(key, EvalOutcome(
                fitness=(rec["s_per_token"], rec["mean_latency_s"])),
                features=features, meta=meta)
            added.append(key)
        return added


# --------------------------------------------------------------------------
# Reference paths + the serving-schedule search workload
# --------------------------------------------------------------------------


def oneshot_generate(cfg, params, prompts: np.ndarray, gen: int,
                     max_len: int | None = None,
                     temperature: float = 0.0) -> np.ndarray:
    """The pre-engine one-shot behavior (batch prefill + lockstep decode of
    equal-length prompts) for ``--oneshot`` demos and convenience tests.
    Returns the ``(B, gen)`` continuation of ``prompts`` (greedy unless
    ``temperature`` > 0).  Note this runs through :class:`ServeEngine`
    itself — the engine-independent correctness oracle is
    :func:`repro.models.transformer.greedy_reference`."""
    engine = ServeEngine(cfg, params,
                         max_len=max_len or (prompts.shape[1] + gen),
                         max_slots=len(prompts),
                         prefill_chunk=len(prompts),
                         temperature=temperature)
    reqs = [ServeRequest(uid=f"r{i}", tokens=p, max_new_tokens=gen)
            for i, p in enumerate(prompts)]
    results = {r.uid: r for r in engine.run(reqs)}
    return np.array([results[f"r{i}"].tokens for i in range(len(prompts))],
                    np.int32)


def demo_trace(cfg, *, n_requests: int, prompt_len: int, gen: int,
               seed: int = 0) -> list[ServeRequest]:
    """Deprecated: trace synthesis moved to ``repro.core.liveloop.traces``
    (:func:`~repro.core.liveloop.traces.demo_requests` is this function;
    :func:`~repro.core.liveloop.traces.synthesize` builds the richer
    scenario shapes).  This shim emits the same request list byte-for-byte
    and will be removed."""
    warnings.warn(
        "repro.core.deploy.demo_trace is deprecated; use "
        "repro.core.liveloop.traces.demo_requests (or synthesize) instead",
        DeprecationWarning, stacklevel=2)
    from ..liveloop.traces import demo_requests
    return demo_requests(cfg, n_requests=n_requests, prompt_len=prompt_len,
                         gen=gen, seed=seed)


def build_serve_workload(arch: str = "qwen3-0.6b", *, smoke: bool = True,
                         n_requests: int = 8, prompt_len: int = 16,
                         gen: int = 8, stagger: int = 2, seed: int = 0):
    """The serving schedule as a GEVO scenario: genome = engine schedule
    (``max_slots``, ``prefill_chunk``), fitness = measured
    ``(s_per_token, mean_request_latency)`` from replaying a fixed request
    trace through a fresh :class:`ServeEngine`.  Model compilation is shared
    across genomes (``_jitted`` is cfg-keyed), so the search measures the
    *schedule*, not recompilation."""
    import jax

    from ...configs import get_config, smoke_config
    from ...models.transformer import init_params
    from ..fitness import KernelWorkload
    cfg = smoke_config(arch) if smoke else get_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    space = serve_schedule_space(arch)
    max_len = prompt_len + gen

    def runner(genome: dict) -> tuple[float, float]:
        from ..liveloop.traces import demo_requests
        # the KV plan clamps residency: slots the plan's pages cannot fit
        # in the modeled byte budget are not granted
        plan = KVPlan.from_genome(genome)
        engine = ServeEngine(cfg, params, max_len=max_len,
                             max_slots=plan.effective_slots(
                                 genome["max_slots"], max_len),
                             prefill_chunk=genome["prefill_chunk"])
        engine.run(demo_requests(cfg, n_requests=n_requests,
                                 prompt_len=prompt_len, gen=gen, seed=seed),
                   stagger=stagger)
        s = engine.stats()
        per = s["per_variant"]["default"]
        return (s["wall_s"] / max(s["gen_tokens"], 1),
                per["mean_latency_s"])

    return KernelWorkload(
        name=f"serve/{arch}",
        program=space.encode(DEFAULT_SERVE_PLAN),
        space=space,
        runner=runner,
        time_mode="measured",
        kind="serve")
