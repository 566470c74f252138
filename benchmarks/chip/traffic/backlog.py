"""An offline backlog: a batch job that keeps the queue full.

Parameters (the traffic file): ``depth``, the number of requests the server
always has waiting; ``prompt_lens`` and ``output_lens`` as
``[[length, count], ...]`` per block; ``max_len``.  A request is due when it
joins the queue.
"""

from __future__ import annotations

from chipbench.mix import BlockMix, Request


class Backlog(BlockMix):
    def __init__(self, params: dict, seed: int, vocab: int):
        super().__init__(params, seed, vocab)
        self.depth = int(params["depth"])

    def next_due(self) -> None:
        return None

    def release(self, now: float, n_waiting: int) -> list[Request]:
        """Enough requests, due ``now``, to bring the waiting ones up to
        ``depth``."""
        return [self.make(now) for _ in range(max(0, self.depth - n_waiting))]


def make(params: dict, seed: int, vocab: int) -> Backlog:
    return Backlog(params, seed, vocab)
