"""Kernel-schedule workload builders: the Pallas kernels as GEVO scenarios.

Each builder wires one kernel (``rmsnorm`` / ``flash_attention`` /
``mamba_scan``) into a :class:`~repro.core.fitness.KernelWorkload` whose
genome is a :class:`~repro.core.schedule.ScheduleSpace` over the kernel's
schedule knobs — implementation choice (``ref`` oracle vs ``pallas``), block
sizes / chunking (grid shape is the derived ``dim // block``), and for
rmsnorm the epilogue-fusion choice (``unfused`` applies the scale multiply
as a separate jnp op after the kernel, costing one extra HBM round-trip in
the model and exercising fusion as a searchable knob).

Fitness = ``(time, max |out - ref|)``:

* the kernel is always *executed* on fixed seeded inputs (interpret mode on
  CPU hosts) — un-launchable configs fail here, and the error objective is
  the real numerical gap against the kernel's ``ref.py`` oracle;
* time is the schedule-aware roofline (``repro.kernels.costs``) in
  ``static`` mode (deterministic: CI-reproducible, parallel == serial), or
  median wall-clock of the jitted variant in ``measured`` mode.

Builders are deterministic given their kwargs and attach a
:class:`~repro.core.evaluator.WorkloadSpec`, so ParallelEvaluator workers
rebuild them (the runner closure does not pickle).  Test shapes are chosen
so every block choice divides its dimension — every genome in the space is
launchable (property-tested in ``tests/test_kernel_search.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.evaluator import WorkloadSpec
from ..core.fitness import InvalidVariant, KernelWorkload, measured_time
from ..core.schedule import ScheduleSpace
from .costs import schedule_features, schedule_time
from .flash_attention.ops import flash_attention
from .flash_attention.ref import attention_ref
from .mamba_scan.ops import mamba_scan
from .mamba_scan.ref import mamba_scan_ref
from .rmsnorm.ops import rmsnorm
from .rmsnorm.ref import rmsnorm_ref

KERNELS = ("rmsnorm", "flash_attention", "mamba_scan")

# Evaluation shapes: small enough for interpret-mode execution, and every
# block choice below divides its dimension (launchability by construction).
SHAPES: dict[str, dict[str, int]] = {
    "rmsnorm": {"rows": 512, "d": 512},
    "flash_attention": {"B": 1, "H": 2, "S": 256, "hd": 64},
    "mamba_scan": {"Bt": 1, "L": 128, "D": 32, "N": 16},
}

_SPACES: dict[str, dict[str, tuple]] = {
    "rmsnorm": {"impl": ("pallas", "ref"),
                "block_rows": (32, 64, 128, 256, 512),
                "epilogue": ("fused", "unfused")},
    "flash_attention": {"impl": ("pallas", "ref"),
                        "block_q": (32, 64, 128, 256),
                        "block_k": (32, 64, 128, 256)},
    "mamba_scan": {"impl": ("pallas", "ref"),
                   "chunk": (8, 16, 32, 64, 128)},
}

# The kernels' shipped defaults — the search baseline (empty patch).
BASELINES: dict[str, dict] = {
    "rmsnorm": {"impl": "pallas", "block_rows": 128, "epilogue": "fused"},
    "flash_attention": {"impl": "pallas", "block_q": 128, "block_k": 128},
    "mamba_scan": {"impl": "pallas", "chunk": 64},
}


# which evaluation-shape dimension each block-size knob must divide
BLOCK_DIMS = {"block_rows": "rows", "block_q": "S", "block_k": "S",
              "chunk": "L"}


def kernel_space(kernel: str) -> ScheduleSpace:
    if kernel not in _SPACES:
        raise KeyError(f"unknown kernel {kernel!r}; choose from {KERNELS}")
    return ScheduleSpace.of(f"kernel/{kernel}", _SPACES[kernel])


def model_width_shapes() -> dict[str, dict[str, int]]:
    """Each kernel at the widths of a model the repo serves: rmsnorm and
    flash attention at qwen3-0.6b's d_model and heads (4 x 512 and 2048
    tokens), mamba_scan at falcon-mamba-7b's d_inner and state size (256
    steps; D=8192 spans several channel tiles).  Those models run in bf16."""
    from ..configs import get_config
    qwen, mamba = get_config("qwen3-0.6b"), get_config("falcon-mamba-7b")
    return {"rmsnorm": {"rows": 4 * 512, "d": qwen.d_model},
            "flash_attention": {"B": 1, "H": qwen.n_heads, "S": 2048,
                                "hd": qwen.hd},
            "mamba_scan": {"Bt": 1, "L": 256, "D": mamba.d_inner,
                           "N": mamba.ssm_state}}


def kernel_inputs(kernel: str, seed: int = 0, shape: dict | None = None,
                  dtype=jnp.float32) -> dict:
    """The fixed seeded inputs of ``kernel`` at ``shape`` (default: its
    evaluation shape in ``SHAPES``) in ``dtype`` (mamba_scan's ``A`` stays
    f32, as in the models)."""
    k = jax.random.PRNGKey
    s = shape or SHAPES[kernel]

    def normal(i, dims):
        return jax.random.normal(k(seed + i), dims).astype(dtype)

    if kernel == "rmsnorm":
        return {"x": normal(0, (s["rows"], s["d"])),
                "scale": normal(1, (s["d"],))}
    if kernel == "flash_attention":
        qkv = (s["B"], s["H"], s["S"], s["hd"])
        return {"q": normal(0, qkv), "k": normal(1, qkv), "v": normal(2, qkv)}
    seq = (s["Bt"], s["L"], s["D"])
    return {"dt": jax.nn.softplus(
                jax.random.normal(k(seed), seq)).astype(dtype),
            "x": normal(1, seq),
            "A": -jnp.exp(jax.random.normal(
                k(seed + 2), (s["D"], s["N"])) * 0.3),
            "B": normal(3, (s["Bt"], s["L"], s["N"])),
            "C": normal(4, (s["Bt"], s["L"], s["N"]))}


def _full_precision(fn):
    """``fn`` with its matmuls at full f32 precision: the TPU's default
    rounds f32 matmul operands to bf16, and an oracle must not be less
    exact than the kernels it judges."""
    def run(inputs):
        with jax.default_matmul_precision("highest"):
            return fn(inputs)
    return run


def _variant_fn(kernel: str, genome: dict):
    """The scheduled computation as ``fn(inputs_dict) -> output``.  The
    ``ref`` implementation is the kernel's oracle itself."""
    if kernel == "rmsnorm":
        if genome["impl"] == "ref":
            return _full_precision(lambda i: rmsnorm_ref(i["x"], i["scale"]))
        br = genome["block_rows"]
        if genome["epilogue"] == "fused":
            return lambda i: rmsnorm(i["x"], i["scale"], block_rows=br)
        ones = jnp.ones(SHAPES["rmsnorm"]["d"], jnp.float32)
        return lambda i: rmsnorm(i["x"], ones, block_rows=br) * i["scale"]
    if kernel == "flash_attention":
        if genome["impl"] == "ref":
            return _full_precision(lambda i: attention_ref(
                i["q"], i["k"], i["v"], causal=True))
        bq, bk = genome["block_q"], genome["block_k"]
        return lambda i: flash_attention(i["q"], i["k"], i["v"], causal=True,
                                         block_q=bq, block_k=bk)
    if genome["impl"] == "ref":
        return _full_precision(lambda i: mamba_scan_ref(
            i["dt"], i["x"], i["A"], i["B"], i["C"]))
    ch = genome["chunk"]
    return lambda i: mamba_scan(i["dt"], i["x"], i["A"], i["B"], i["C"],
                                chunk=ch)


def kernel_reference(kernel: str, inputs) -> np.ndarray:
    """The kernel's ``ref.py`` oracle on ``inputs``, as f32 numpy."""
    return np.asarray(_variant_fn(kernel, {"impl": "ref"})(inputs),
                      np.float32)


# The knobs a kernel's *numerical error* actually depends on — the error
# equivalence classes of the batched fitness path (core.tensor_evo).  The
# excluded knobs only partition independent rows of the iteration space
# (rmsnorm's block_rows, flash's block_q: per-row arithmetic is unchanged),
# so error is class-constant.  The parity tests (tests/test_tensor_evo.py)
# assert batched == per-genome serial error on every kernel, which keeps
# this table honest.
ERROR_KNOBS: dict[str, tuple[str, ...]] = {
    "rmsnorm": ("impl", "epilogue"),
    "flash_attention": ("impl", "block_k"),
    "mamba_scan": ("impl", "chunk"),
}


def _kernel_error(kernel: str, genome: dict, inputs, ref_out) -> float:
    """Execute one scheduled kernel and return max |out - ref| — the single
    error implementation shared by the serial runners and the batched
    error-class path (parity by construction)."""
    fn = _variant_fn(kernel, genome)
    try:
        out = fn(inputs)
    except Exception as e:
        raise InvalidVariant(f"{kernel} failed to launch: {e}") from e
    return float(np.max(np.abs(np.asarray(out, np.float32) - ref_out)))


def build_kernel_workload(kernel: str = "rmsnorm", *,
                          time_mode: str = "static",
                          seed: int = 0) -> KernelWorkload:
    """One Pallas kernel as a GEVO scenario: schedule genome + (time, error)
    fitness.  Deterministic given kwargs (required by WorkloadSpec)."""
    from ..core.tensor_evo.fitness import KernelBlock, TensorFitnessSpec

    space = kernel_space(kernel)
    shape = SHAPES[kernel]
    inputs = kernel_inputs(kernel, seed)
    ref_out = kernel_reference(kernel, inputs)

    def static_probe(genome: dict) -> float:
        # the exact gate check the runner performs first, exposed for the
        # static patch screen (raises InvalidVariant on failed gates)
        return schedule_time(kernel, genome, **shape)

    def runner(genome: dict) -> tuple[float, float]:
        t = static_probe(genome)  # validates launchability
        err = _kernel_error(kernel, genome, inputs, ref_out)
        if time_mode == "measured":
            # jit the whole variant: the ref/epilogue paths are plain jnp
            # (eager per-op dispatch would drown the schedule signal)
            t = measured_time(jax.jit(_variant_fn(kernel, genome)), inputs)
        return t, err

    def feature_probe(genome: dict) -> dict:
        return schedule_features(kernel, genome, **shape)

    return KernelWorkload(
        name=f"kernel/{kernel}",
        program=space.encode(BASELINES[kernel]),
        space=space,
        runner=runner,
        static_probe=static_probe,
        feature_probe=feature_probe,
        time_mode=time_mode,
        spec=WorkloadSpec.make(
            "repro.kernels.workloads:build_kernel_workload",
            kernel=kernel, time_mode=time_mode, seed=seed),
        tensor_spec=TensorFitnessSpec(blocks=(KernelBlock.make(
            kernel, shape, ERROR_KNOBS[kernel],
            lambda g: _kernel_error(kernel, g, inputs, ref_out)),)),
    )


# Extended choice lists for the joint (all-kernels) space.  Deliberately
# include values that do NOT divide the evaluation shapes (48/192 vs 512 and
# 256; 12/48 vs 128): those configurations fail the launchability gates, so
# the joint space — unlike the per-kernel test spaces above, which stay
# launchable-by-construction — exercises the invalid-lane machinery at scale.
_JOINT_SPACES: dict[str, dict[str, tuple]] = {
    "rmsnorm": {"impl": ("pallas", "ref"),
                "block_rows": (32, 48, 64, 128, 192, 256, 512),
                "epilogue": ("fused", "unfused")},
    "flash_attention": {"impl": ("pallas", "ref"),
                        "block_q": (16, 32, 48, 64, 128, 192, 256),
                        "block_k": (16, 32, 48, 64, 128, 192, 256)},
    "mamba_scan": {"impl": ("pallas", "ref"),
                   "chunk": (8, 12, 16, 32, 48, 64, 128)},
}


def joint_space() -> ScheduleSpace:
    """One schedule space over every kernel's knobs, prefixed
    ``<kernel>.<knob>`` — ~38k genomes, the 100×-budget search target."""
    params = {f"{kernel}.{knob}": choices
              for kernel in KERNELS
              for knob, choices in _JOINT_SPACES[kernel].items()}
    return ScheduleSpace.of("kernel/joint", params)


def build_joint_kernel_workload(*, time_mode: str = "static",
                                seed: int = 0) -> KernelWorkload:
    """All three kernels as ONE genome: fitness is (sum of schedule times,
    max of kernel errors) over the prefixed joint space.  The serial runner
    and the batched tensor path combine per-kernel terms in the same
    (KERNELS) order, so they agree bit-exactly.  Static time only: a summed
    wall-clock of three separately-jitted kernels measures dispatch, not
    schedules."""
    from ..core.tensor_evo.fitness import KernelBlock, TensorFitnessSpec

    if time_mode != "static":
        raise ValueError("joint workload supports time_mode='static' only")
    space = joint_space()
    inputs = {k: kernel_inputs(k, seed) for k in KERNELS}
    refs = {k: kernel_reference(k, inputs[k]) for k in KERNELS}

    def sub_genome(genome: dict, kernel: str) -> dict:
        return {knob: genome[f"{kernel}.{knob}"]
                for knob in _JOINT_SPACES[kernel]}

    def static_probe(genome: dict) -> float:
        # gates first, in kernel order — the first unlaunchable kernel's
        # message is the variant's invalidity reason (matches the batched
        # path's first-invalid-block reporting)
        t = 0.0
        for kernel in KERNELS:
            t += schedule_time(kernel, sub_genome(genome, kernel),
                               **SHAPES[kernel])
        return t

    def runner(genome: dict) -> tuple[float, float]:
        t = static_probe(genome)
        err = None
        for kernel in KERNELS:
            e = _kernel_error(kernel, sub_genome(genome, kernel),
                              inputs[kernel], refs[kernel])
            err = e if err is None else max(err, e)
        return t, err

    def feature_probe(genome: dict) -> dict:
        # per-kernel counters under <kernel>.-prefixed names, mirroring the
        # joint space's knob naming
        feats: dict[str, float] = {}
        for kernel in KERNELS:
            sub = schedule_features(kernel, sub_genome(genome, kernel),
                                    **SHAPES[kernel])
            feats.update({f"{kernel}.{k}": v for k, v in sub.items()})
        return feats

    def error_fn(kernel: str):
        return lambda g: _kernel_error(kernel, g, inputs[kernel],
                                       refs[kernel])

    blocks = tuple(
        KernelBlock.make(
            kernel, SHAPES[kernel], ERROR_KNOBS[kernel], error_fn(kernel),
            knob_map={knob: f"{kernel}.{knob}"
                      for knob in _JOINT_SPACES[kernel]})
        for kernel in KERNELS)
    baseline = {f"{kernel}.{knob}": BASELINES[kernel][knob]
                for kernel in KERNELS
                for knob in _JOINT_SPACES[kernel]}
    return KernelWorkload(
        name="kernel/joint",
        program=space.encode(baseline),
        space=space,
        runner=runner,
        static_probe=static_probe,
        feature_probe=feature_probe,
        time_mode=time_mode,
        spec=WorkloadSpec.make(
            "repro.kernels.workloads:build_joint_kernel_workload",
            time_mode=time_mode, seed=seed),
        tensor_spec=TensorFitnessSpec(blocks=blocks),
    )


def kernel_artifact(kernel: str, genome: dict,
                    fitness: tuple[float, float] | None = None,
                    meta: dict | None = None):
    """A deployable :class:`~repro.core.deploy.Artifact` for one evolved
    kernel schedule, keyed by the kernel's evaluation shape — the form the
    registry stores and ``resolve_kernel_schedule`` looks up."""
    from ..core.deploy import Artifact
    return Artifact(kind="kernel", name=kernel, shape=SHAPES[kernel],
                    genome=dict(genome), fitness=fitness,
                    meta=dict(meta or {}))


def resolve_kernel_schedule(registry, kernel: str, shape=None) -> dict:
    """The schedule a serving path should run ``kernel`` with: the
    registry's winner for ``(kernel, shape)`` when one is registered (and
    it decodes into the kernel's schedule space), else the shipped
    ``BASELINES`` default.  ``registry=None`` short-circuits to the
    default, so call sites can be unconditional."""
    if registry is not None:
        art = registry.resolve(kernel, shape or SHAPES[kernel],
                               kind="kernel")
        if art is not None:
            space = kernel_space(kernel)
            if space.contains(art.genome):
                return dict(art.genome)
    return dict(BASELINES[kernel])


def scheduled_kernel_fn(kernel: str, registry=None, shape=None):
    """The kernel as a callable scheduled by the registry:
    ``fn(inputs_dict) -> output`` running the resolved winner schedule
    (falling back to the shipped default).  This is the hook by which
    kernel-schedule search winners reach execution paths."""
    return _variant_fn(kernel, resolve_kernel_schedule(registry, kernel,
                                                       shape))


def evolve_kernel_schedule(workload, *, generations: int = 6,
                           pop_size: int = 10, seed: int = 0,
                           evaluator=None, verbose: bool = False,
                           err_tol: float = 1e-3, surrogate: bool = False,
                           surrogate_keep: float = 0.5):
    """The canonical kernel-schedule search configuration, shared by the
    example, the benchmarks, and the A/B suite: NSGA-II over ``attr_tweak``
    patches (schedule genomes are a handful of genes, so a high mutation
    rate and a 2-tweak init drive the search; crossover recombines tweaks).

    Returns ``(search, result, best, within_tol)`` where ``best`` is the
    fastest Pareto member whose error stays within the default schedule's
    error + ``err_tol`` — or, when nothing meets the gate
    (``within_tol=False``), the fastest member outright.  The caller owns
    ``evaluator`` (or, when None, the search's internal one — closed by
    ``search.close()``)."""
    from ..core.search import GevoML
    s = GevoML(workload, pop_size=pop_size, n_elite=pop_size // 2,
               seed=seed, init_mutations=2, mutation_rate=0.9,
               operators={"attr_tweak": 1.0}, evaluator=evaluator,
               verbose=verbose, surrogate=surrogate,
               surrogate_keep=surrogate_keep)
    res = s.run(generations=generations)
    _, e_def = res.original_fitness
    ok = [i for i in res.pareto if i.fitness[1] <= e_def + err_tol]
    best = min(ok or res.pareto, key=lambda i: i.fitness[0])
    return s, res, best, bool(ok)
