"""Request timelines and the end-to-end arithmetic over them.

The harness stamps every output token itself: after each ``step()`` of the
engine or router returns, every token that appeared during that step gets
the step's end time.  A request is timed from when it was *due*, not from
when it was submitted, so a stall delays every request due during it.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class RequestLog:
    """One request: when it was due, its sizes, and its token times."""

    uid: str
    due: float                      # host clock, seconds
    prompt_len: int
    out_len: int
    in_window: bool = False         # due inside the measured window
    rejected: bool = False
    replica: int = 0                # which replica served it
    token_times: list[float] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)
    prompt: np.ndarray | None = None

    @property
    def done(self) -> bool:
        return len(self.token_times) >= self.out_len


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (numpy's linear interpolation)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_s(logs: list[RequestLog], t_give_up: float) -> list[float]:
    """First-token time minus due time of every request due in the window.
    A refused request, or one with no first token by ``t_give_up``, misses:
    it counts as having waited until ``t_give_up``."""
    out = []
    for r in logs:
        if not r.in_window:
            continue
        first = r.token_times[0] if r.token_times and not r.rejected \
            else t_give_up
        out.append(first - r.due)
    return out


def itl_s(logs: list[RequestLog]) -> list[float]:
    """Every gap between consecutive output tokens of the requests due in
    the window."""
    gaps: list[float] = []
    for r in logs:
        if r.in_window and len(r.token_times) > 1:
            gaps.extend(np.diff(r.token_times).tolist())
    return gaps


def tokens_between(logs: list[RequestLog], t0: float, t1: float) -> int:
    """Output tokens whose time lies in ``(t0, t1]``, of any request."""
    return sum(1 for r in logs for t in r.token_times if t0 < t <= t1)
