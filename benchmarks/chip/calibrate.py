"""Readings that set a cell's limits: the compared number of sound runs of
the program and of the control, seed by seed, in one process.

    python3 benchmarks/chip/calibrate.py --workload qwen3-0.6b.chat \\
        --seeds 101,102,103 --seconds 10 [--out cal.json]

For each seed it sets the cell up, runs a short window at the cell's own
load, and reads the number the cell's check compares twice: for what the
program produced, and for the control, the configuration's reference in the
nearest precision below the one the configuration states, put in the
program's place (int8 for the bfloat16 model, bfloat16 for the float32
search).  The lower reading is the largest the program gives over the
seeds, the upper the smallest the control gives; the limit lies between.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    import run as bench
    from chipbench.cells import load_cell
    from chipbench.runtime import NoChip, chips

    cell = load_cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 1
    bench.configure_compile_cache(cell)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = dict(seed=seed, **cell.entry.calibrate(cell, seed, args.seconds,
                                                     devices))
        rows.append(row)
        print(json.dumps({k: row[k] for k in ("seed", "number", "program",
                                              "control")}), flush=True)
    lower = max(r["program"] for r in rows)
    upper = min(r["control"] for r in rows)
    print(json.dumps({"lower": lower, "upper": upper,
                      "ratio": upper / lower if lower > 0 else math.inf}))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
