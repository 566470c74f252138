"""Rate sweep of an open-loop serving cell, to find its knee.

    python3 benchmarks/chip/sweep.py --workload qwen3-0.6b.chat \\
        --rates 2,4,6,8 --seconds 20 --seed 1 [--out sweep.json]

One process sets the cell up once, then offers the cell's mix at each rate
for ``--seconds``, lets the server drain, and prints one JSON line per rate:
the rate offered, requests due and finished in the window, the backlog left
at the window's end (requests due by then with no first token yet), time to
first token at p50 and p90, the gap between tokens at p95, and output
tokens per second.  The knee is the highest rate at which the server keeps
up: the backlog at the window's end stays near zero and the requests
finished per second match the rate offered: here, the highest rate of the
sweep, below which every rate also holds, that leaves no more requests
waiting at the window's end than the server has lanes.  The sweep is run
once, when a cell is defined; ``--write`` records the knee and four fifths
of it as ``knee_per_s`` and ``rate_per_s`` in the cell's traffic file.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="", help="also write the rows here")
    ap.add_argument("--write", action="store_true",
                    help="record the knee and the rate in the traffic file")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
    import run as bench
    from chipbench import timeline
    from chipbench.cells import load_cell
    from chipbench.runtime import NoChip, Spans, chips

    cell = load_cell(args.workload)
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 1
    bench.configure_compile_cache(cell)
    entry = cell.entry
    sess = entry.Session(cell, args.seed, devices, Spans())
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        logs, (t0, t_end, t_give_up) = sess.drive(
            args.seed, args.seconds, None, 0.0, give_up_s=10.0,
            traffic=traffic)
        while sess.server.busy:
            sess.server.step()
        window = [r for r in logs if r.in_window]
        ttft = timeline.ttft_s(logs, t_give_up)
        row = {"rate_per_s": rate, "due": len(window),
               "finished_in_window": sum(
                   1 for r in window if r.done and r.token_times[-1] <= t_end),
               "backlog_at_end": sum(
                   1 for r in logs if r.due <= t_end
                   and not (r.token_times and r.token_times[0] <= t_end)),
               "ttft_p50_ms": 1e3 * timeline.percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * timeline.percentile(ttft, 90),
               "itl_p95_ms": 1e3 * timeline.percentile(
                   timeline.itl_s(logs), 95),
               "output_tok_s": timeline.tokens_between(logs, t0, t_end)
               / (t_end - t0),
               "window_s": t_end - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    lanes = sum(e.max_slots for e in entry.engines_of(sess.server))
    knee = None
    for row in rows:
        if row["backlog_at_end"] > lanes:
            break
        knee = row["rate_per_s"]
    print(json.dumps({"lanes": lanes, "knee_per_s": knee}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    if args.write and knee is not None:
        path = HERE / "traffic" / f"{cell.traffic_name}.json"
        doc = json.loads(path.read_text())
        doc.update(knee_per_s=knee, rate_per_s=round(0.8 * knee, 3))
        path.write_text(json.dumps(doc, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
