"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads the cell, sets up (weights from the seed, the server or search, a
warm-up through it), measures for ``--seconds`` and prints, as the last line
of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced runs
only) and ``checks``, each number compared beside its limit.  The same
numbers are the last lines of standard error.

It runs on the TPU chips of the machine it is started on and exits nonzero,
printing no result, when JAX finds no TPU or fewer chips than the cell asks
for, or when the repository's ``src/`` is not in the checkout.  JAX's
persistent compilation cache is turned on through the program's
``enable_compile_cache``: ``$JAX_COMPILATION_CACHE_DIR`` if set, else a
fixed directory inside the checkout.
"""

from __future__ import annotations

import time

T_LOAD = time.perf_counter()    # set-up counts from here: loading included

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
SRC = HERE.parents[1] / "src"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell's name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights, inputs and traffic")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace a sub-window with the profiler and print "
                         "the per-layer metrics")
    return ap.parse_args(argv)


def setup_paths() -> None:
    for p in (str(HERE), str(SRC)):
        if p not in sys.path:
            sys.path.insert(0, p)


def configure_compile_cache(cell) -> str:
    """Turn the persistent cache on.  Every program is written to it, so a
    second run of a cell compiles nothing -- except in a cell whose
    configuration sets ``cache_programs`` false (the search, whose window
    compiles each candidate as a user's search does), where nothing is."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    if cell.config.get("cache_programs", True):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          1e9)
    return cache_dir


def main(argv=None) -> int:
    args = parse(argv)
    if not (SRC / "repro").is_dir():
        print(f"run.py: {SRC / 'repro'} not found: run from a checkout of "
              "the repository", file=sys.stderr)
        return 2
    setup_paths()
    from chipbench.cells import load_cell
    from chipbench.result import dumps, print_checks, result_line
    from chipbench.runtime import NoChip, chips

    cell = load_cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 1
    cache_dir = configure_compile_cache(cell)
    print(f"cell {cell.name}: seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}, {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache_dir}", file=sys.stderr, flush=True)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        rec, t_window = cell.entry.run(cell, args.seed, args.seconds,
                                      bool(args.trace), devices,
                                      trace_dir=trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = t_window - T_LOAD
    import jax
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices())}
    line = result_line(cell, rec, device, bool(args.trace), setup_s)
    print(f"setup_s {setup_s!r}, run {time.perf_counter() - T_LOAD:.1f} s",
          file=sys.stderr, flush=True)
    print_checks(rec)
    print(dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
