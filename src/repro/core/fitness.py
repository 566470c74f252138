"""Fitness evaluation for GEVO-ML variants: argmin(time, error).

Section 4.3: individuals are only required to *execute successfully*; output
error is an objective, not a validity gate.  Two time modes:

* ``measured`` — wall-clock of the jitted variant on the host backend (the
  paper's mode, on a P100; here whatever backend JAX sees).
* ``static``  — deterministic TPU-v5e roofline estimate from the variant's
  per-op FLOPs/bytes.  Used in CI and on the CPU container so search results
  are reproducible; this is the hardware-adaptation noted in DESIGN.md.
"""

from __future__ import annotations

import time as _time
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .interp import evaluate, jit_program
from .ir import Program, op_bytes, op_flops
from .schedule import ScheduleSpace

# TPU v5e target constants (also used by the roofline harness).
PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip
HBM_BW = 819e9           # bytes/s per chip


class InvalidVariant(Exception):
    """The variant failed to execute (or broke the training feedback loop)."""


def static_time(program: Program, peak_flops: float = PEAK_FLOPS,
                hbm_bw: float = HBM_BW) -> float:
    """Roofline time estimate: sum over ops of max(compute, memory) time."""
    types = program.types()
    t = 0.0
    for op in program.ops:
        ots = [types[o] for o in op.operands]
        t += max(op_flops(op, ots) / peak_flops, op_bytes(op, ots) / hbm_bw)
    return t


def measured_time(fn, inputs, repeats: int = 3) -> float:
    """Median wall-clock of the jitted callable (after warmup)."""
    out = fn(inputs)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(inputs))
        times.append(_time.perf_counter() - t0)
    return float(np.median(times))


def _check_finite_scalar(x) -> float:
    v = float(x)
    if not np.isfinite(v):
        raise InvalidVariant("non-finite objective")
    return v


@dataclass
class PredictionWorkload:
    """Inference task (MobileNet/CIFAR10 in the paper): minimize forward-pass
    time and prediction error on a held-in dataset."""

    name: str
    program: Program                 # inputs: {"images"}; outputs: [logits]
    images: np.ndarray               # (N, ...) held-in eval data
    labels: np.ndarray               # (N,)
    batch: int = 256
    time_mode: str = "static"
    kind: str = "prediction"
    # rebuild recipe for ParallelEvaluator workers (see core/evaluator.py);
    # optional — this workload also pickles whole
    spec: object | None = None

    def run(self, program: Program) -> tuple[float, float]:
        """Execute and score ``program``; any failure propagates as raised
        (the unpatched program and shipped baselines are run this way)."""
        fn = jit_program(program)
        n = (len(self.images) // self.batch) * self.batch
        correct = 0
        t_meas = 0.0
        for i in range(0, n, self.batch):
            inp = {"images": self.images[i:i + self.batch]}
            if self.time_mode == "measured" and i == 0:
                t_meas = measured_time(fn, inp) * (n // self.batch)
            out = fn(inp)[0]
            if out.ndim != 2 or out.shape[0] != self.batch:
                raise InvalidVariant(f"bad logits shape {out.shape}")
            pred = np.argmax(np.nan_to_num(np.asarray(out, np.float32),
                                           nan=-1e30), axis=-1)
            k = min(out.shape[1], int(self.labels.max()) + 1)
            correct += int(np.sum(pred[: self.batch] ==
                                  self.labels[i:i + self.batch]))
        error = 1.0 - correct / max(n, 1)
        t = t_meas if self.time_mode == "measured" else \
            static_time(program) * (n // self.batch)
        return _check_finite_scalar(t), _check_finite_scalar(error)

    def evaluate(self, program: Program) -> tuple[float, float]:
        """:meth:`run` as the search sees it: a variant that fails to
        execute is invalid (Section 4.3), whatever the exception."""
        try:
            return self.run(program)
        except InvalidVariant:
            raise
        except Exception as e:  # any execution failure invalidates the variant
            raise InvalidVariant(str(e)) from e


@dataclass
class KernelWorkload:
    """Kernel-schedule task: ``program`` is a schedule genome encoded as
    HLO-lite constant ops (:mod:`repro.core.schedule`), and fitness is
    ``argmin(kernel time, max numerical error vs the kernel's reference)``.

    ``runner(genome)`` executes the scheduled kernel (so un-launchable or
    crashing configurations surface as :class:`InvalidVariant`, the paper's
    execute-successfully gate) and returns ``(time_s, max_abs_error)`` —
    time measured on this host in ``measured`` mode, or a deterministic
    schedule-aware roofline estimate in ``static`` mode (see
    ``repro.kernels.costs``).  Builders for the Pallas kernels live in
    ``repro.kernels.workloads``; GEVO-Shard (:mod:`repro.core.autotune`)
    builds one whose runner compiles a whole model cell."""

    name: str
    program: Program                 # the encoded schedule genome
    space: ScheduleSpace
    runner: Callable[[dict], tuple[float, float]]  # genome -> (time, err)
    time_mode: str = "static"
    kind: str = "kernel"
    # rebuild recipe for ParallelEvaluator workers (see core/evaluator.py);
    # required for parallel eval: runner is a closure and does not pickle
    spec: object | None = None
    # batched-fitness recipe (core.tensor_evo.TensorFitnessSpec); optional —
    # workloads without one fall back to per-genome evaluation.  Not part of
    # the fingerprint: it is an evaluation *strategy*, not a protocol change
    # (the batched path is bit-exact with the serial one).
    tensor_spec: object | None = None
    # launchability probe: the same static gate check the runner performs
    # first (``schedule_time`` raising InvalidVariant), exposed so the patch
    # screen (core.analysis) can reject un-launchable genomes without
    # executing anything.  Optional and advisory — also not fingerprinted.
    static_probe: Callable[[dict], float] | None = None
    # surrogate feature probe: genome -> flat {name: float} of roofline/VMEM
    # counters (``kernels.costs.schedule_features``), consumed by
    # core.surrogate's featurizers.  Optional and advisory — also not
    # fingerprinted (it changes what the surrogate sees, not what a variant
    # measures).
    feature_probe: Callable[[dict], dict] | None = None

    def run(self, program: Program) -> tuple[float, float]:
        """Decode, execute and score the genome; failures propagate."""
        t, err = self.runner(self.space.decode(program))
        return _check_finite_scalar(t), _check_finite_scalar(err)

    def evaluate(self, program: Program) -> tuple[float, float]:
        """:meth:`run` as the search sees it: any failure is invalid."""
        try:
            return self.run(program)
        except InvalidVariant:
            raise
        except Exception as e:  # ScheduleError, launch failure, numerics
            raise InvalidVariant(str(e)) from e


@dataclass
class TrainingWorkload:
    """Training task (2fcNet/MNIST in the paper): the IR program is ONE full
    SGD step (forward + backward + update, Figure 5).  Fitness retrains from
    the initial weights with the *variant* step, then measures error with the
    reference forward pass on the final weights."""

    name: str
    program: Program                 # inputs: weights... + {"x","y_onehot"}
    weight_names: tuple[str, ...]    # program inputs that are weights, in
                                     # 1:1 order with program outputs
    init_weights: dict[str, np.ndarray]
    train_x: np.ndarray
    train_y: np.ndarray              # int labels
    eval_fn: Callable[[dict[str, np.ndarray]], float]  # -> error in [0,1]
    batch: int = 32
    steps: int = 200
    num_classes: int = 10
    time_mode: str = "static"
    kind: str = "training"
    # rebuild recipe for ParallelEvaluator workers (see core/evaluator.py);
    # required for parallel eval: eval_fn is a closure and does not pickle
    spec: object | None = None

    def _batches(self):
        n = (len(self.train_x) // self.batch) * self.batch
        i = 0
        while True:
            j = i % n
            yield (self.train_x[j:j + self.batch],
                   self.train_y[j:j + self.batch])
            i += self.batch

    def run(self, program: Program) -> tuple[float, float]:
        """Train with ``program`` as the step and score the result; any
        failure propagates as raised (the unpatched program is run this
        way)."""
        fn = jit_program(program)
        weights = {k: jnp.asarray(v) for k, v in self.init_weights.items()}
        expected_shapes = {k: v.shape for k, v in self.init_weights.items()}
        t_meas = 0.0
        batches = self._batches()
        for step in range(self.steps):
            x, y = next(batches)
            y1h = np.eye(self.num_classes, dtype=np.float32)[y]
            inputs = dict(weights)
            inputs["x"] = x
            inputs["y_onehot"] = y1h
            if self.time_mode == "measured" and step == 1:
                t_meas = measured_time(fn, inputs) * self.steps
            outs = fn(inputs)
            if len(outs) != len(self.weight_names):
                raise InvalidVariant("variant lost weight outputs")
            for k, o in zip(self.weight_names, outs):
                if tuple(o.shape) != expected_shapes[k]:
                    # the variant changed a weight shape: the training
                    # feedback loop is broken -> invalid individual
                    raise InvalidVariant(
                        f"weight {k} shape drifted to {o.shape}")
                weights[k] = o
        final = {k: np.asarray(v, np.float32) for k, v in weights.items()}
        if any(not np.all(np.isfinite(v)) for v in final.values()):
            raise InvalidVariant("weights diverged to non-finite")
        error = self.eval_fn(final)
        t = t_meas if self.time_mode == "measured" else \
            static_time(program) * self.steps
        return _check_finite_scalar(t), _check_finite_scalar(error)

    def evaluate(self, program: Program) -> tuple[float, float]:
        """:meth:`run` as the search sees it: a variant that fails to
        execute is invalid (Section 4.3), whatever the exception."""
        try:
            return self.run(program)
        except InvalidVariant:
            raise
        except Exception as e:
            raise InvalidVariant(str(e)) from e
