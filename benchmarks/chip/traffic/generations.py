"""A search's window: ``GevoML.run`` generation by generation until the
window closes.

Parameters (the traffic file): ``generations``, the most the run may ask
for (the window closes long before).  The search's random stream is seeded
from ``--seed``, so a seed fixes the candidates it proposes.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class Generations:
    seed: int
    generations: int


def make(params: dict, seed: int, vocab: int | None = None) -> Generations:
    return Generations(seed=int(seed), generations=int(params["generations"]))
