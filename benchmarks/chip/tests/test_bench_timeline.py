"""The end-to-end arithmetic on hand-made timelines: a stall inside the
window moves the tails and the rate."""

import numpy as np
import pytest

from chipbench import timeline


def _timelines(stall_at=None, stall_s=0.0, n=50, gap=0.1, out=10,
               tick=0.02, ttft=0.01):
    """Requests due every ``gap`` s, first token ``ttft`` after due, then
    one every ``tick`` s; a stall at ``stall_at`` delays every later token
    by ``stall_s``."""
    logs = []
    for i in range(n):
        due = i * gap
        times = [due + ttft + j * tick for j in range(out)]
        if stall_at is not None:
            times = [t + stall_s if t >= stall_at else t for t in times]
        logs.append(timeline.RequestLog(
            uid=f"r{i}", due=due, prompt_len=8, out_len=out,
            in_window=True, token_times=times))
    return logs


def test_steady_timeline():
    logs = _timelines()
    assert timeline.percentile(timeline.ttft_s(logs, 99.0), 90) == \
        pytest.approx(0.01)
    assert timeline.percentile(timeline.itl_s(logs), 95) == \
        pytest.approx(0.02)


def test_a_stall_moves_the_first_token_tail_and_the_rate():
    calm, stalled = _timelines(), _timelines(stall_at=2.0, stall_s=3.0)
    t90 = [timeline.percentile(timeline.ttft_s(x, 99.0), 90)
           for x in (calm, stalled)]
    rate = [timeline.tokens_between(x, 0.0, 5.0) / 5.0
            for x in (calm, stalled)]
    assert t90[1] > t90[0] + 1.0 and rate[1] < 0.7 * rate[0]


def test_stalls_in_every_tenth_gap_move_the_gap_tail():
    """A long prompt admitted every tenth tick delays each lane's next
    token there: the 95th percentile of the gaps sees it, the median not."""
    calm = _timelines()
    slow = _timelines()
    for r in slow:
        r.token_times = [t + 0.05 * (j // 5) for j, t in
                         enumerate(r.token_times)]
    assert timeline.percentile(timeline.itl_s(slow), 95) > \
        timeline.percentile(timeline.itl_s(calm), 95) + 0.04
    assert timeline.percentile(timeline.itl_s(slow), 50) == \
        pytest.approx(timeline.percentile(timeline.itl_s(calm), 50))


def test_refused_and_unanswered_requests_miss():
    logs = _timelines(n=10)
    logs[3].rejected = True
    logs[4].token_times = []
    logs[5].in_window = False
    waits = timeline.ttft_s(logs, t_give_up=100.0)
    assert len(waits) == 9
    assert sorted(waits)[-2:] == [pytest.approx(100.0 - 0.4),
                                  pytest.approx(100.0 - 0.3)]


def test_percentile_is_numpys_linear():
    vals = np.arange(1, 101, dtype=float)
    assert timeline.percentile(vals, 90) == np.percentile(vals, 90)
    with pytest.raises(ValueError):
        timeline.percentile([], 90)
