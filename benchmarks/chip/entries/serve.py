"""Serving entry: a served model under a traffic mix, through ``ServeEngine``
or, where the plan asks for replicas, ``Router``.

Set-up makes the weights on the device from the seed, builds the server
that the configuration's plan (its ``serving`` group) describes, and drives
one warm-up pass through that same server: every prompt length of the mix,
every lane filled.  The window then offers the mix's requests as
they fall due, steps the server while it has work, and stamps each new
token with the end time of the step that produced it.  After the window,
arrivals go on and the server steps until every request due in the window
has finished, or for at most ``GIVE_UP_S``.

A traced run also hands the server the program's own recorder
(``repro.core.spans.Spans``); its intervals and counters in the window go
into the record apart from the benchmark's own.  The operations and bytes
the window's tokens needed come from the configuration's own module
(``prefill_flops``, ``decode_flops``, ``decode_bytes``): this entry reads
no architecture key.

The check runs once the window has closed, the memory peak has been read
and the server is gone: for a sample of finished requests, drawn from the
seed and holding the one with the most output, the configuration's plain
reference reads how far below its best logit each served token lies, and
the check compares the mean of those gaps over every served position.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench import devtrace, timeline
from chipbench.result import Check, Record
from chipbench.runtime import (CompileEvents, Spans, jax_key,
                               memory_peak_bytes, peaks)

GIVE_UP_S = 60.0


def model_config(doc: dict):
    from repro.models.common import ModelConfig
    return ModelConfig(**doc["model"])


def build_server(cell, cfg, weights, max_len: int, program_spans=None):
    """The engine, or the router of replicas, that the plan describes;
    ``program_spans`` is the program's own recorder (traced runs only)."""
    from repro.core.deploy import ServeEngine, build_router
    plan = cell.config["serving"]
    if int(plan.get("replicas", 1)) > 1:
        from repro.launch.mesh import make_smoke_mesh
        genome = {k: plan[k] for k in ("replicas", "max_slots",
                                       "prefill_chunk")}
        return build_router(cfg, weights, genome=genome, max_len=max_len,
                            mesh=make_smoke_mesh(*plan["mesh"]),
                            spans=program_spans)
    return ServeEngine(cfg, weights, max_len=max_len,
                       max_slots=plan["max_slots"],
                       prefill_chunk=plan["prefill_chunk"],
                       spans=program_spans)


def engines_of(server) -> list:
    replicas = getattr(server, "replicas", None)
    return [r.engine for r in replicas] if replicas else [server]


def n_waiting(server) -> int:
    """Requests submitted and not yet admitted to a lane."""
    engines = engines_of(server)
    routed = len(server.queue) if engines[0] is not server else 0
    return routed + sum(len(e.queue) for e in engines)


class Stamper:
    """After each step, gives every token that appeared in it the step's end
    time.  It reads the engines' lanes and finished results; it changes
    nothing."""

    def __init__(self, server, logs: dict[str, timeline.RequestLog]):
        self.engines = engines_of(server)
        self.logs = logs
        self.n_done = [len(e.completed) for e in self.engines]

    def _see(self, uid: str, tokens: list[int], t: float,
             replica: int) -> None:
        log = self.logs.get(uid)
        if log is None:
            return
        log.replica = replica
        new = len(tokens) - len(log.token_times)
        if new > 0:
            log.token_times.extend([t] * new)
        log.tokens = list(tokens)

    def after_step(self, t: float) -> None:
        for i, eng in enumerate(self.engines):
            for batch in eng.batches.values():
                for _, lane in batch.active():
                    self._see(lane.req.uid, lane.tokens, t, i)
            for res in eng.completed[self.n_done[i]:]:
                self._see(res.uid, res.tokens, t, i)
            self.n_done[i] = len(eng.completed)


def warm_up(server, lens: list[int], seed: int, vocab: int) -> None:
    """One pass through ``server`` that admits every prompt length on every
    replica and fills every lane, then drains: prefill per length, decode,
    sampling, the lane splice and the cache's allocation all happen here."""
    from repro.core.deploy import ServeRequest
    engines = engines_of(server)
    slots = engines[0].max_slots
    n = max(slots * len(engines), len(lens) * len(engines))
    rng = np.random.default_rng([seed, 2])
    for k in range(n):
        plen = lens[(k // len(engines)) % len(lens)]
        server.submit(ServeRequest(
            uid=f"warm{k}", max_new_tokens=slots + 2,
            tokens=rng.integers(0, vocab, plen).astype(np.int32)))
    while server.busy:
        server.step()


def sample_for_check(logs: list[timeline.RequestLog], seed: int,
                     n_tokens: int) -> list[timeline.RequestLog]:
    """Finished requests drawn from the seed: the one with the most output
    first, then one of every other replica, then others until ``n_tokens``
    served tokens are covered."""
    done = [r for r in logs if r.done and not r.rejected]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.out_len, r.prompt_len, r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    picked, seen = [], {longest.replica}
    for i in order:
        if rest[i].replica not in seen:
            seen.add(rest[i].replica)
            picked.append(i)
    covered = longest.out_len + sum(rest[i].out_len for i in picked)
    for i in order:
        if covered >= n_tokens:
            break
        if i not in picked:
            picked.append(i)
            covered += rest[i].out_len
    return [longest] + [rest[i] for i in picked]


def position_gaps(cell, weights, sample, max_len: int,
                  control: bool = False) -> list[np.ndarray]:
    """For each sampled request, the gap between the reference's best logit
    and its logit of the served token (or, with ``control``, of the token
    the int8 computation puts first), at every served position."""
    out = []
    for r in sample:
        P, n = r.prompt_len, r.out_len
        seq = np.zeros(max_len, np.int32)
        seq[:P] = r.prompt
        seq[P:P + n - 1] = r.tokens[:n - 1]
        targets = np.full(max_len, -1, np.int32)
        targets[P - 1:P - 1 + n] = r.tokens[:n]
        gaps = cell.reference.token_gaps(cell.config, weights, seq, targets,
                                         control=control)
        out.append(gaps[P - 1:P - 1 + n])
    return out


def mean_gap(gaps: list[np.ndarray]) -> float:
    """The mean of :func:`position_gaps` over every served position of the
    sample: each token that departs from the reference's best counts with
    the size of its gap (the widest gap alone does not separate the int8
    control from the served model; see ``PERF.md``)."""
    return float(np.concatenate(gaps).mean()) if gaps else float("inf")


def window_work(ref, doc: dict, logs: list[timeline.RequestLog],
                window: tuple[float, float],
                traced: tuple[float, float]) -> dict:
    """What the tokens stamped in a span needed, by the counts of the
    configuration's own module ``ref`` (a request's token 0 is its
    prefill's, every later one a decode's).  Over the whole ``window``:
    ``model_flops``.  Over the ``traced`` sub-window, whose device time the
    trace holds: ``traced_flops`` and its seconds ``traced_s``; of the
    decoded tokens alone ``decode_flops``; and for the decode executions
    that produced them, the bytes they had to move (``decode_bytes``) and
    their number (``decode_steps``).  Tokens of one replica stamped with
    one time came from one decode execution."""
    def flops(j: int, r) -> float:
        return (ref.prefill_flops(doc, r.prompt_len) if j == 0
                else ref.decode_flops(doc, r.prompt_len + j - 1))

    (t0, t_end), (a, b) = window, traced
    model_flops = traced_flops = decode_flops = 0.0
    decoded: dict[tuple, list[int]] = {}
    for r in logs:
        for j, t in enumerate(r.token_times):
            in_window, in_trace = t0 < t <= t_end, a < t <= b
            if not (in_window or in_trace):
                continue
            f = flops(j, r)
            if in_window:
                model_flops += f
            if in_trace:
                traced_flops += f
                if j > 0:
                    decode_flops += f
                    decoded.setdefault((t, r.replica), []).append(
                        r.prompt_len + j - 1)
    return {"model_flops": model_flops, "traced_flops": traced_flops,
            "traced_s": b - a, "decode_flops": decode_flops,
            "decode_bytes": sum(ref.decode_bytes(doc, p)
                                for p in decoded.values()),
            "decode_steps": len(decoded)}


def program_intervals(prog, t0: float, t_end: float) -> dict:
    """The program recorder's intervals that started in ``[t0, t_end)``,
    as durations by name."""
    spans: dict[str, list[float]] = {}
    for name, a, b in prog.intervals:
        if t0 <= a < t_end:
            spans.setdefault(name, []).append(b - a)
    return spans


class Session:
    """One cell's server with its weights, built once: calibration reuses it
    across seeds in one process."""

    def __init__(self, cell, seed: int, devices, spans: Spans,
                 program_spans=None):
        self.cell = cell
        self.spans = spans
        self.program_spans = program_spans
        self.window_counters: dict = {}
        self.traced: tuple[float, float] | None = None
        self.cfg = model_config(cell.config)
        self.max_len = int(cell.traffic["max_len"])
        self.weights = cell.reference.make_weights(
            cell.config, jax_key(seed), devices[0])
        self.server = build_server(cell, self.cfg, self.weights,
                                   self.max_len, program_spans)
        gen = cell.generator.make(cell.traffic, seed, self.cfg.vocab)
        warm_up(self.server, gen.prompt_lens(), seed, self.cfg.vocab)

    def drive(self, seed: int, seconds: float, trace_dir: str | None,
              trace_s: float, give_up_s: float = GIVE_UP_S,
              traffic: dict | None = None):
        """Offer the mix for ``seconds`` and drain.  Returns the logs and
        ``(t0, t_end, t_give_up)``: window start, end of its last step,
        and when waiting stopped; ``self.traced`` is the traced
        sub-window's span, if one was traced.  ``traffic`` replaces the
        cell's mix parameters (the rate sweep varies the rate)."""
        from repro.core.deploy import ServeRequest
        cell, server, spans = self.cell, self.server, self.spans
        gen = cell.generator.make(traffic or cell.traffic, seed,
                                  self.cfg.vocab)
        logs: dict[str, timeline.RequestLog] = {}
        stamper = Stamper(server, logs)
        prog = self.program_spans
        before = dict(prog.counters) if prog is not None else {}
        t0 = time.perf_counter()
        deadline = t0 + seconds
        sub = devtrace.SubWindow(trace_dir, t0, seconds, trace_s)
        t_end = t0

        def offer(now: float, in_window: bool) -> None:
            for req in gen.release(now - t0, n_waiting(server)):
                due = t0 + (req.due if req.due is not None else now - t0)
                log = timeline.RequestLog(
                    uid=req.uid, due=due, prompt_len=len(req.prompt),
                    out_len=req.out_len, in_window=in_window,
                    prompt=req.prompt)
                logs[req.uid] = log
                log.rejected = not server.try_submit(ServeRequest(
                    uid=req.uid, tokens=req.prompt,
                    max_new_tokens=req.out_len))

        def step() -> float:
            with spans.span("step"):
                server.step()
            t = time.perf_counter()
            stamper.after_step(t)
            return t

        def wait_for_arrival(limit: float) -> None:
            nd = gen.next_due()          # None: a backlog, never idle long
            until = limit if nd is None else min(t0 + nd, limit)
            with spans.span("arrival_wait"):
                time.sleep(max(0.0, until - time.perf_counter()))

        while (now := time.perf_counter()) < deadline:
            sub.poll(now)
            offer(now, True)
            if server.busy:
                t_end = step()
            else:
                wait_for_arrival(deadline)
                t_end = time.perf_counter()
        sub.close()
        self.traced = sub.span
        if prog is not None:
            self.window_counters = {k: v - before.get(k, 0)
                                    for k, v in prog.counters.items()}
        window = [r for r in logs.values() if r.in_window]
        give_up = t_end + give_up_s
        while any(not (r.done or r.rejected) for r in window) \
                and (now := time.perf_counter()) < give_up:
            offer(now, False)
            if server.busy:
                step()
            else:
                wait_for_arrival(give_up)
        return list(logs.values()), (t0, t_end, min(time.perf_counter(),
                                                    give_up))

    def close(self) -> None:
        """Drop the server and its caches; the weights stay for the
        reference, which the benchmark made and the program only read."""
        self.server = None
        gc.collect()


def run(cell, seed: int, seconds: float, trace: bool, devices,
        tamper=None, trace_dir: str | None = None) -> tuple[Record, float]:
    """One run of a serving cell: the record, and the host clock at which
    set-up ended and the window opened.  ``tamper``, if
    given, is applied to the built server before the window (the fault
    tests break the timed path with it)."""
    doc = cell.config
    spans = Spans(annotate=trace)
    program_spans = None
    if trace:
        from repro.core.spans import Spans as ProgramSpans
        program_spans = ProgramSpans(annotate=True)
    events = CompileEvents()
    sess = Session(cell, seed, devices, spans, program_spans)
    if tamper is not None:
        tamper(sess.server)
    t_window = time.perf_counter()

    snap = events.snapshot()
    logs, (t0, t_end, t_give_up) = sess.drive(
        seed, seconds, trace_dir if trace else None,
        devtrace.TRACE_SECONDS)
    compiled = events.since(snap)
    print(f"compile events in the window and drain: {compiled}",
          file=sys.stderr, flush=True)
    mem = memory_peak_bytes(devices)

    window_s = t_end - t0
    window = [r for r in logs if r.in_window]
    failed = sum(1 for r in window if r.rejected or not r.done)
    ttft, itl = timeline.ttft_s(logs, t_give_up), timeline.itl_s(logs)
    e2e = {"ttft_p85_ms": 1e3 * timeline.percentile(ttft, 85),
           "itl_p95_ms": 1e3 * timeline.percentile(itl, 95),
           "output_tok_s": timeline.tokens_between(logs, t0, t_end)
           / window_s}
    print("ttft ms p50/p75/p80/p85/p90/p95/p99 " + " ".join(
              f"{1e3 * timeline.percentile(ttft, q):.3f}"
              for q in (50, 75, 80, 85, 90, 95, 99))
          + f" of {len(ttft)}; itl ms p50/p90/p95/p99 " + " ".join(
              f"{1e3 * timeline.percentile(itl, q):.3f}"
              for q in (50, 90, 95, 99)) + f" of {len(itl)}",
          file=sys.stderr, flush=True)
    steps = spans.durations("step", t0, t_end)
    peak = peaks(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    # an untraced run, which reads no per-layer metric, counts the decode
    # over the whole window
    work = window_work(cell.reference, doc, logs, (t0, t_end),
                       sess.traced or (t0, t_end))
    counters = dict(work, chips=len(devices),
                    peak_flops_per_s=peak["bf16_flops_per_s"] if peak
                    else None,
                    peak_hbm_bytes_per_s=peak["hbm_bytes_per_s"] if peak
                    else None)
    prog_spans = (program_intervals(program_spans, t0, t_end)
                  if program_spans is not None else {})
    sess.close()

    sample = sample_for_check(logs, seed, int(doc["check"]["sample_tokens"]))
    gaps = position_gaps(cell, sess.weights, sample, sess.max_len)
    checks = [Check("mean_logit_gap", mean_gap(gaps),
                    doc["check"]["mean_logit_gap"])]
    print(f"checked {len(sample)} requests, "
          f"{sum(r.out_len for r in sample)} served tokens; widest gap of "
          f"each {[float(g.max()) for g in gaps]}", file=sys.stderr,
          flush=True)
    summary = None
    if trace and trace_dir:
        tr = devtrace.read_xplane(devtrace.find_xplane(trace_dir))
        summary = devtrace.reduce(tr, sorted(tr.ops)[:len(devices)])
    rec = Record(attempted=len(window), failed=failed, end_to_end=e2e,
                 checks=checks, memory_peak_bytes=mem, window_s=window_s,
                 spans={"step": steps}, counters=counters, trace=summary,
                 program_spans=prog_spans,
                 program_counters=sess.window_counters)
    return rec, t_window


def calibrate(cell, seed: int, seconds: float, devices) -> dict:
    """The compared number of one seed, for the program and for the control
    (the reference in int8 in the program's place), on the same sample of
    requests served in a short window at the cell's own load."""
    sess = Session(cell, seed, devices, Spans())
    logs, _ = sess.drive(seed, seconds, None, 0.0)
    sess.close()
    sample = sample_for_check(logs, seed,
                              int(cell.config["check"]["sample_tokens"]))
    prog = position_gaps(cell, sess.weights, sample, sess.max_len)
    ctrl = position_gaps(cell, sess.weights, sample, sess.max_len,
                         control=True)
    return {"number": "mean_logit_gap",
            "program": mean_gap(prog), "control": mean_gap(ctrl),
            "program_widest": max(float(g.max()) for g in prog),
            "control_widest": max(float(g.max()) for g in ctrl),
            "requests": len(sample),
            "tokens": sum(r.out_len for r in sample),
            "program_gaps": [g.tolist() for g in prog],
            "control_gaps": [g.tolist() for g in ctrl]}
