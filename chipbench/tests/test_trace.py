"""Trace reduction: interval arithmetic by hand, and a recorded CPU trace."""

from pathlib import Path

import pytest

from chipbench import trace as trace_lib

DATA = Path(__file__).resolve().parent / "data" / "cpu_trace"


def test_union_length_by_hand():
    # [0,10] + [5,12] overlap -> 12; [20,25] apart -> 5; [21,22] inside -> 0
    assert trace_lib.union_length([(20, 25), (0, 10), (5, 12), (21, 22)]) == 17
    assert trace_lib.union_length([]) == 0


def test_gaps_by_hand():
    iv = [(2, 4), (3, 6), (8, 9)]
    assert trace_lib.gaps(iv, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert trace_lib.gaps(iv, 3, 8) == [(6, 8)]
    assert trace_lib.gaps([], 0, 5) == [(0, 5)]


@pytest.fixture(scope="module")
def reduced():
    return trace_lib.load(DATA)


def test_window_is_the_span(reduced):
    # The window span wraps three ~5 ms calls and a 50 ms sleep.
    assert 0.055 < reduced.window_s < 0.2
    assert reduced.n_devices == 1


def test_busy_union_and_idle_share(reduced):
    # Recompute the busy time with a sweep over start/end points.
    points = sorted([(e.start, 1) for e in reduced.ops] + [(e.end, -1) for e in reduced.ops])
    depth, busy, last = 0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert reduced.busy_s == pytest.approx(busy / 1e9)
    assert 0.0 < reduced.busy_s < reduced.window_s
    assert reduced.idle_share == pytest.approx(1 - reduced.busy_s / reduced.window_s)


def test_op_attribution(reduced):
    calls = reduced.matching(r"toy_step", where="modules")
    assert len(calls) == 3
    names = [name for name, _ in reduced.breakdown()["device_ops"]]
    assert any(n.startswith("dot_general") for n in names)
    total = sum(s for _, s in reduced.top_ops())
    assert total == pytest.approx(sum(e.seconds for e in reduced.ops))


def test_gap_attribution(reduced):
    gaps = dict(reduced.breakdown()["idle_gaps"])
    # The 50 ms sleep is the longest idle stretch, named by its span.
    assert max(gaps, key=gaps.get) == "chipbench.test.sleep"
    assert gaps["chipbench.test.sleep"] >= 0.049
    assert sum(gaps.values()) == pytest.approx(reduced.window_s - reduced.busy_s)
