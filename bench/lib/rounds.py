"""The whole-round rate of the fleet cells."""
from __future__ import annotations

from typing import Sequence, Tuple


def whole_round_rate(timeline: Sequence[Tuple[float, float, int]]) -> float:
    """Ops per second over whole rounds: ``timeline`` holds one
    ``(begin, end, ops)`` per round of the window (the fleet cells count
    the ops each round served), and the rate is every op of those rounds
    over the sum of their times. Time outside the
    rounds (the clock stopped between them) counts for neither."""
    if not timeline:
        raise ValueError("no round in the window")
    ops = 0
    seconds = 0.0
    for begin, end, n in timeline:
        if end < begin:
            raise ValueError(f"round ends before it begins: {begin} > {end}")
        ops += n
        seconds += end - begin
    return ops / seconds
