"""Open-loop arrivals at a fixed rate: independent users who send on a
schedule, whether or not earlier requests have finished.

Parameters (the traffic file): ``rate_per_s``; ``prompt_lens`` and
``output_lens`` as ``[[length, count], ...]`` per block; ``max_len``.  The
gaps between arrivals of a block are the quantiles of an exponential
distribution at ``rate_per_s`` (a Poisson process's gaps), scaled so their
mean is exactly ``1 / rate_per_s``, in an order drawn from the seed.
"""

from __future__ import annotations

import numpy as np

from chipbench.mix import BlockMix, Request


class OpenLoop(BlockMix):
    def __init__(self, params: dict, seed: int, vocab: int):
        super().__init__(params, seed, vocab)
        self.rate = float(params["rate_per_s"])
        q = (np.arange(self.block) + 0.5) / self.block
        gaps = -np.log1p(-q)
        self.gap_block = gaps * (self.block / gaps.sum()) / self.rate
        self._gaps: list[float] = []
        self._next: Request | None = None
        self._t = 0.0

    def _peek(self) -> Request:
        if self._next is None:
            if not self._gaps:
                self._gaps = self._rng.permutation(self.gap_block).tolist()
            self._t += self._gaps.pop(0)
            self._next = self.make(self._t)
        return self._next

    def next_due(self) -> float:
        """Seconds after the window opens at which the next request is due."""
        return self._peek().due

    def release(self, now: float, n_waiting: int) -> list[Request]:
        """Every request due by ``now`` (seconds after the window opens)."""
        out = []
        while self._peek().due <= now:
            out.append(self._next)
            self._next = None
        return out


def make(params: dict, seed: int, vocab: int) -> OpenLoop:
    return OpenLoop(params, seed, vocab)
