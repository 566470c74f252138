"""Request mixes drawn from a seed, one block at a time.

Each block holds the same multiset of prompt lengths, output lengths and (for
open loops) gaps between arrivals, in an order drawn from the seed.  So every
seed asks for the same work, and only the order changes; a run's length mix
does not swing with the seed.  Token ids come from the seed too, one
generator per request, so they do not depend on timing.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    uid: str
    due: float | None         # seconds after the window opens; None: now
    prompt: np.ndarray        # int32 token ids
    out_len: int


def _multiset(pairs) -> list[int]:
    """``[[value, count], ...]`` as a flat list."""
    return [int(v) for v, n in pairs for _ in range(int(n))]


class BlockMix:
    """An endless stream of requests, block by block.

    ``prompt_lens`` and ``output_lens`` are ``[[length, count], ...]``
    with the same total count, the block size."""

    def __init__(self, params: dict, seed: int, vocab: int):
        self.prompt_block = _multiset(params["prompt_lens"])
        self.output_block = _multiset(params["output_lens"])
        if len(self.prompt_block) != len(self.output_block):
            raise ValueError("prompt_lens and output_lens must count the "
                             "same block size")
        self.block = len(self.prompt_block)
        self.max_len = int(params["max_len"])
        if max(self.prompt_block) + max(self.output_block) > self.max_len:
            raise ValueError("a request of the mix exceeds max_len")
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._rng = np.random.default_rng([self.seed, 0])
        self._queue: list[tuple[int, int]] = []
        self.n_made = 0

    def prompt_lens(self) -> list[int]:
        return sorted(set(self.prompt_block))

    def _sizes(self) -> tuple[int, int]:
        if not self._queue:
            p = self._rng.permutation(self.prompt_block)
            o = self._rng.permutation(self.output_block)
            self._queue = list(zip(p.tolist(), o.tolist()))
        return self._queue.pop(0)

    def make(self, due: float | None) -> Request:
        i = self.n_made
        self.n_made += 1
        plen, olen = self._sizes()
        toks = np.random.default_rng([self.seed, 1, i]).integers(
            0, self.vocab, plen).astype(np.int32)
        return Request(uid=f"req{i:06d}", due=due, prompt=toks,
                       out_len=olen)
