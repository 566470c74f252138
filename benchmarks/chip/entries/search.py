"""Search entry: ``GevoML`` over a training workload's step, with fitness
measured on the device, as a user of the paper's search runs it.

Set-up builds the workload as ``build_twofc_training_workload`` does, but
with the benchmark's initial weights and data, both made from the seed by
the configuration's file; runs the unpatched step once strictly (which
compiles the scoring pass and warms the input path); and builds the search.
The window runs ``GevoML.run`` generation by generation; every candidate
the evaluator executes passes through the benchmark's wrapper around the
workload's ``evaluate``, which times it and keeps the program and its scored
fitness.
The first candidate due to start after the window's end closes it.  Each
candidate compiles its own program, as in a user's search: the cell's
configuration keeps candidate programs out of the persistent compile cache,
so no run is served a program that an earlier run compiled.

The check runs after the window: for the executed candidates scored valid
(or a sample of them drawn from the seed), the configuration's reference
retrains each candidate program by the paper's protocol and scores it, at
the precision the configuration states (float32, the operands of matrix
products rounded to bfloat16) and again with every value in float64.  A
candidate whose two reference errors differ by more than ``stable_tol``
trains to a result that rounding alone moves (a classifier whose test
predictions flip on the last bit); the rule leaves it out of the
comparison, by what the reference shows and not by what the candidate is.
The number compared is the mean gap, over the other candidates, between the
error the search scored and the reference's error (see ``PERF.md``).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from chipbench import devtrace
from chipbench.result import Check, Record
from chipbench.runtime import CompileEvents, Spans, memory_peak_bytes


class WindowClosed(Exception):
    """Raised instead of starting a candidate after the window's end."""


class Search:
    """The workload and the search, built once from a seed."""

    def __init__(self, cell, seed: int, spans: Spans):
        from repro.core import GevoML
        from repro.core.fitness import TrainingWorkload
        from repro.workloads.twofc import (WEIGHT_NAMES, build_twofc_step,
                                           make_eval_fn)
        doc = cell.config
        w = doc["workload"]
        self.cell, self.spans = cell, spans
        self.init = cell.reference.make_weights(doc, seed)
        self.data = cell.reference.make_data(doc, seed)
        d = self.data
        self.wl = TrainingWorkload(
            name="2fcNet-training",
            program=build_twofc_step(batch=w["batch"], hidden=w["hidden"],
                                     lr=w["lr"]),
            weight_names=WEIGHT_NAMES,
            init_weights={k: v.copy() for k, v in self.init.items()},
            train_x=d["train_x"].copy(), train_y=d["train_y"].copy(),
            eval_fn=make_eval_fn(d["test_x"].copy(), d["test_y"].copy()),
            batch=w["batch"], steps=w["steps"], time_mode=doc["time_mode"])
        self.wl.run(self.wl.program)            # strict: failures propagate
        plan = cell.generator.make(cell.traffic, seed)
        s = doc["search"]
        self.generations = plan.generations
        self.search = GevoML(self.wl, pop_size=s["pop_size"],
                             n_elite=s["n_elite"], engine=s["engine"],
                             screen=s["screen"], seed=plan.seed)
        self.executed: list[tuple] = []     # (program, fitness | None, end)
        self.deadline = float("inf")
        self.sub: devtrace.SubWindow | None = None
        self._evaluate = self.wl.evaluate
        self.wl.evaluate = self._timed_evaluate

    def _timed_evaluate(self, program):
        from repro.core.fitness import InvalidVariant
        now = time.perf_counter()
        if self.sub is not None:
            self.sub.poll(now)
        if now >= self.deadline:
            raise WindowClosed
        with self.spans.span("evaluation"):
            try:
                fit = self._evaluate(program)
            except InvalidVariant:
                self.executed.append((program, None, time.perf_counter()))
                raise
        self.executed.append((program, fit, time.perf_counter()))
        return fit

    def window(self, seconds: float, trace_dir: str | None = None,
               trace_s: float = 0.0) -> tuple[float, float]:
        """Run the search until the window closes; ``(t0, t_end)``.  With
        ``trace_dir`` the middle ``trace_s`` seconds are traced."""
        t0 = time.perf_counter()
        self.deadline = t0 + seconds
        self.sub = devtrace.SubWindow(trace_dir, t0, seconds, trace_s)
        try:
            with self.spans.span("search"):
                self.search.run(generations=self.generations)
        except WindowClosed:
            pass
        t_end = time.perf_counter()
        self.sub.close()
        return t0, t_end


def error_gaps(cell, s: Search, picked, precision: str | None = None
               ) -> tuple[list[float], list[dict]]:
    """Gaps between each picked candidate's scored error (or, with
    ``precision``, the reference's error in that precision in the program's
    place) and the reference's error at the precision the configuration
    states (its ``check.reference``), over the candidates whose result
    rounding does not move: where the reference and its rounding probe
    (``check.probe``, the same in float64) differ by more than
    ``stable_tol``, the candidate is left out.  Also each candidate's
    readings."""
    doc, ref, chk = cell.config, cell.reference, cell.config["check"]
    tol = float(chk["stable_tol"])
    gaps, rows = [], []
    for program, fit in picked:
        e = ref.train_error(doc, program, s.init, s.data, chk["reference"])
        p = ref.train_error(doc, program, s.init, s.data, chk["probe"])
        got = fit[1] if precision is None else ref.train_error(
            doc, program, s.init, s.data, precision)
        kept = (e is None) == (p is None) and (e is None or abs(e - p) <= tol)
        rows.append({"got": got, "reference": e, "probe": p, "kept": kept})
        if kept:
            gaps.append(float("inf") if e is None or got is None
                        else abs(got - e))
    return gaps, rows


def pick(valid: list, seed: int, n: int) -> list:
    """Up to ``n`` of the valid candidates, drawn from the seed, in the
    order they ran."""
    rng = np.random.default_rng([int(seed), 5])
    return [valid[i] for i in sorted(rng.permutation(len(valid))[:n])]


def mean_gap(gaps: list[float]) -> float:
    """The mean error gap over the compared candidates (the widest alone
    does not separate the bfloat16 control from the program; see
    ``PERF.md``)."""
    return float(np.mean(gaps)) if gaps else float("inf")


def run(cell, seed: int, seconds: float, trace: bool, devices,
        tamper=None, trace_dir: str | None = None) -> tuple[Record, float]:
    """One run of a search cell: the record, and the host clock at which
    set-up ended and the window opened."""
    doc = cell.config
    spans = Spans(annotate=trace)
    events = CompileEvents()
    s = Search(cell, seed, spans)
    if tamper is not None:
        tamper(s)
    t_window = time.perf_counter()
    snap = events.snapshot()
    t0, t_end = s.window(seconds, trace_dir if trace else None,
                         devtrace.TRACE_SECONDS)
    compiled = events.since(snap)
    mem = memory_peak_bytes(devices)
    executed = [e for e in s.executed if t0 <= e[2] <= t_end]
    valid = [(p, f) for p, f, _ in executed if f is not None]
    print(f"window: {len(executed)} candidates executed, {len(valid)} valid;"
          f" compile events {compiled}", file=sys.stderr, flush=True)

    chk = doc["check"]
    picked = pick(valid, seed, chk["max_checked"])
    gaps, rows = error_gaps(cell, s, picked)
    checks = [Check("mean_error_gap", mean_gap(gaps),
                    chk["mean_error_gap"])]
    print(f"checked {len(gaps)} candidates, left out {len(rows) - len(gaps)}"
          f" that rounding moves; error gaps {gaps}; scored, reference, "
          f"probe {[(r['got'], r['reference'], r['probe']) for r in rows]}",
          file=sys.stderr, flush=True)
    summary = None
    if trace and trace_dir:
        tr = devtrace.read_xplane(devtrace.find_xplane(trace_dir))
        summary = devtrace.reduce(tr, sorted(tr.ops)[:len(devices)])
    rec = Record(attempted=len(executed), failed=0,
                 end_to_end={"evals_per_s": len(executed) / (t_end - t0)},
                 checks=checks, memory_peak_bytes=mem, window_s=t_end - t0,
                 counters={"evals": len(executed),
                           "backend_compile_s": compiled["backend_compile_s"]},
                 trace=summary)
    return rec, t_window


def calibrate(cell, seed: int, seconds: float, devices) -> dict:
    """The compared number of one seed for the program and for the control
    (the reference in bfloat16 in the program's place), over the same
    candidates as a run of ``seconds`` would check."""
    doc = cell.config
    s = Search(cell, seed, Spans())
    t0, t_end = s.window(seconds)
    valid = [(p, f) for p, f, t in s.executed if f is not None
             and t0 <= t <= t_end]
    picked = pick(valid, seed, doc["check"]["max_checked"])
    prog, rows = error_gaps(cell, s, picked)
    ctrl, ctrl_rows = error_gaps(cell, s, picked, "bf16")
    exact = [cell.reference.train_error(doc, p, s.init, s.data, "f32")
             for p, _ in picked]
    for r, c, x in zip(rows, ctrl_rows, exact):
        r.update(control=c["got"], f32=x)
    return {"number": "mean_error_gap", "program": mean_gap(prog),
            "control": mean_gap(ctrl), "checked": len(prog),
            "left_out": len(rows) - len(prog), "program_gaps": prog,
            "control_gaps": ctrl, "candidates": rows}
