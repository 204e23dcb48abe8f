"""The whole-round reducer counts exactly the ops of the rounds whose
time it counts, and nothing of the time between them."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib.rounds import whole_round_rate  # noqa: E402


def test_rate_is_ops_of_counted_rounds_over_their_time():
    # three rounds of 48k, 44k and 52k ops; 0.5 s and 0.25 s between
    # them when the clock was stopped (sampling), which must not count
    timeline = [(10.0, 12.0, 48_000), (12.5, 14.5, 44_000),
                (14.75, 17.75, 52_000)]
    assert whole_round_rate(timeline) == pytest.approx(144_000 / 7.0)


def test_partial_round_is_never_counted():
    # a window cut at 15 s would count 2.5 rounds of time; the reducer
    # only ever sees whole rounds, so the same work gives the same rate
    whole = [(0.0, 2.0, 50_000), (2.0, 4.0, 50_000)]
    assert whole_round_rate(whole) == pytest.approx(25_000.0)
    assert whole_round_rate(whole[:1]) == pytest.approx(25_000.0)


@pytest.mark.parametrize("timeline", [[], [(2.0, 1.0, 10)]])
def test_bad_timelines_raise(timeline):
    with pytest.raises(ValueError):
        whole_round_rate(timeline)
