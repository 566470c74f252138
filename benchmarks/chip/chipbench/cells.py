"""Cells of ``BENCHMARK.json`` and the files each one names.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Everything that belongs to one configuration, one mix or one per-layer metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` -- sizes, source, the serving plan or search
  settings, and ``entry``: the module under ``entries/`` that drives a run;
* ``configs/<config>.py``   -- the configuration's plain reference and the
  work its architecture needs.  A served model's module gives
  ``make_weights(doc, key, device)``, ``token_gaps(doc, weights, tokens,
  targets, control)``, ``prefill_flops(doc, prompt_len)``,
  ``decode_flops(doc, position)`` and ``decode_bytes(doc, positions)``;
  the serving entry reads no architecture key of the JSON file, only its
  ``model``, ``serving``, ``check`` and ``entry``;
* ``traffic/<traffic>.json`` -- the mix's parameters and ``generator``: the
  module under ``traffic/`` that turns them into requests;
* ``metrics/<metric>.py``   -- a ``read(record)`` that returns one number,
  or ``None`` where the record holds nothing for it.

What a reader may read: in a traced run every interval the program's own
recorder took in the window reaches ``record.program_spans`` and every
counter it raised there ``record.program_counters``, each under the
program's name for it, and the device seconds and executions of each named
program (``jit_<name>``) reach ``record.trace["modules"]``.
``record.spans`` and ``record.counters`` hold only the benchmark's own
readings, so no name the program chooses reaches them.  A later cell, mix
or metric is added by adding such files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = HERE.parents[1]                              # the checkout


def load_module(path: Path):
    """Import the Python file ``path`` under a name made from its place in
    the benchmark (file names may hold dots, so ``import`` cannot)."""
    rel = path.resolve().relative_to(HERE).with_suffix("")
    name = "chipbench_" + "_".join(rel.parts).replace(".", "_").replace(
        "-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


@dataclass
class Cell:
    """One cell, with its configuration and traffic documents loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def entry(self):
        return load_module(HERE / "entries" / f"{self.config['entry']}.py")

    @property
    def reference(self):
        return load_module(HERE / "configs" / f"{self.config_name}.py")

    @property
    def generator(self):
        return load_module(HERE / "traffic"
                           / f"{self.traffic['generator']}.py")

    def metric_reader(self, name: str):
        return load_module(HERE / "metrics" / f"{name}.py")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``bench``)."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _for_cell(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _for_cell(m, name)])
